"""Device milliseconds of the fleet-Q dispatch program per environment
step, from the trace (the program named in the configuration's
``programs.q_dispatch``)."""


def read(ctx):
    t = ctx["trace"]
    name = ctx["cfg"].get("programs", {}).get("q_dispatch")
    steps = ctx["delta"]["chem"]["env_steps"]
    if t is None or ctx["driver"] != "train" or name not in t["programs"] or not steps:
        return None
    return 1e3 * t["programs"][name][0] / steps
