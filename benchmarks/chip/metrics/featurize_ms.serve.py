"""Host milliseconds per service step in the predictors' featurisation
of the molecules not in their answer cache: the program's
``predict.featurize`` spans inside the traced window, over the window's
service steps."""

from chip import program_spans


def read(ctx):
    steps = ctx["delta"].get("service_steps")
    tot = program_spans.window_totals(ctx)
    if ctx["driver"] != "serve" or not steps or not tot or "predict.featurize" not in tot:
        return None
    return 1e3 * tot["predict.featurize"]["s"] / steps
