"""Share of the traced serving window with no operation on the device,
in percent (1 - busy / window from the trace)."""


def read(ctx):
    t = ctx["trace"]
    if t is None or ctx["driver"] != "serve" or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
