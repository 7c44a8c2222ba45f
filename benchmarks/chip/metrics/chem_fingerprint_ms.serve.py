"""Host milliseconds per service step in candidate fingerprints
(fingerprints, pack, cache put): the program's ``chem.fingerprint``
spans inside the traced window, over the window's service steps."""

from chip import program_spans


def read(ctx):
    steps = ctx["delta"].get("service_steps")
    tot = program_spans.window_totals(ctx)
    if ctx["driver"] != "serve" or not steps or not tot or "chem.fingerprint" not in tot:
        return None
    return 1e3 * tot["chem.fingerprint"]["s"] / steps
