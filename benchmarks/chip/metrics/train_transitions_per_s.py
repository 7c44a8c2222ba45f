"""Training transitions per second: every transition of the whole
episodes in the window (rollout, updates, sync) over the window, the
first episode's start to the last one's end."""


def read(ctx):
    return ctx["delta"]["transitions"] / ctx["w"]["window_s"]
