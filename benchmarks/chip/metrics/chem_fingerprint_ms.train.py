"""Host milliseconds per environment step in candidate fingerprints: the
program's ``chem.fingerprint`` spans (fingerprints, packing, cache puts)
inside the traced window, over the window's env steps."""

from chip import program_spans


def read(ctx):
    d = ctx["delta"]
    steps = d["chem"]["env_steps"]
    tot = program_spans.window_totals(ctx)
    if ctx["driver"] != "train" or not steps or not tot or "chem.fingerprint" not in tot:
        return None
    return 1e3 * tot["chem.fingerprint"]["s"] / steps
