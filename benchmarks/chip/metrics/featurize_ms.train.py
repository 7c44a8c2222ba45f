"""Host milliseconds per environment step building the predictors'
inputs: the program's ``predict.featurize`` spans (graph arrays, the
conformer check and pseudo-conformer of each distinct cache miss, then
stacking) inside the traced window, over the window's env steps."""

from chip import program_spans


def read(ctx):
    d = ctx["delta"]
    steps = d["chem"]["env_steps"]
    tot = program_spans.window_totals(ctx)
    if ctx["driver"] != "train" or not steps or not tot or "predict.featurize" not in tot:
        return None
    return 1e3 * tot["predict.featurize"]["s"] / steps
