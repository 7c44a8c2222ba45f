"""Host milliseconds per environment step in the rollout engine's own
bookkeeping: the program's ``rollout.select`` (eps-greedy selection),
``rollout.apply`` (rewards, transitions, slot advance) and
``rollout.flush`` (replay adds) spans inside the traced window, over the
window's env steps."""

from chip import program_spans

SPANS = ("rollout.select", "rollout.apply", "rollout.flush")


def read(ctx):
    d = ctx["delta"]
    steps = d["chem"]["env_steps"]
    tot = program_spans.window_totals(ctx)
    if ctx["driver"] != "train" or not steps or not tot \
            or not any(s in tot for s in SPANS):
        return None
    return 1e3 * sum(tot[s]["s"] for s in SPANS if s in tot) / steps
