"""Host milliseconds per learner update drawing the replay batch: the
program's ``learner.sample`` spans (every worker's packed sample,
stacked) inside the traced window, over the updates made in the window."""

from chip import program_spans


def read(ctx):
    d = ctx["delta"]
    tot = program_spans.window_totals(ctx)
    if ctx["driver"] != "train" or not d["updates"] or not tot \
            or "learner.sample" not in tot:
        return None
    return 1e3 * tot["learner.sample"]["s"] / d["updates"]
