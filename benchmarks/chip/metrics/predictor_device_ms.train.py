"""Device milliseconds of the two property predictors per environment
step, from the trace: the programs ``jit_bde_apply`` and ``jit_ip_apply``
(named here; a program that compiles both as ``jit_apply`` reports
nothing)."""

PROGRAMS = ("jit_bde_apply", "jit_ip_apply")


def read(ctx):
    t = ctx["trace"]
    steps = ctx["delta"]["chem"]["env_steps"]
    if t is None or ctx["driver"] != "train" or not steps:
        return None
    found = [t["programs"][p][0] for p in PROGRAMS if p in t["programs"]]
    if not found:
        return None
    return 1e3 * sum(found) / steps
