"""Host milliseconds per environment step in the property service's key
work: the program's ``predict.keys`` spans (``iso_key`` of every
molecule, the answer-cache lookups, in-batch dedup) inside the traced
window, over the window's env steps."""

from chip import program_spans


def read(ctx):
    d = ctx["delta"]
    steps = d["chem"]["env_steps"]
    tot = program_spans.window_totals(ctx)
    if ctx["driver"] != "train" or not steps or not tot or "predict.keys" not in tot:
        return None
    return 1e3 * tot["predict.keys"]["s"] / steps
