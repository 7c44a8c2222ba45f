"""Host milliseconds per service step in candidate enumeration (binding
a request's start, the chemistry cache's lookups, in-batch dedup,
enumerating the misses): the program's ``chem.enumerate`` spans inside
the traced window, over the window's service steps."""

from chip import program_spans


def read(ctx):
    steps = ctx["delta"].get("service_steps")
    tot = program_spans.window_totals(ctx)
    if ctx["driver"] != "serve" or not steps or not tot or "chem.enumerate" not in tot:
        return None
    return 1e3 * tot["chem.enumerate"]["s"] / steps
