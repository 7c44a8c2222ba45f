"""Device milliseconds of the serving Q dispatch per service step, from
the trace: the program named in the configuration's
``programs.q_dispatch`` (``jit_serve_q_apply``)."""


def read(ctx):
    t = ctx["trace"]
    if t is None or ctx["driver"] != "serve":
        return None
    name = ctx["cfg"].get("programs", {}).get("q_dispatch")
    steps = ctx["delta"].get("service_steps")
    if name not in t["programs"] or not steps:
        return None
    return 1e3 * t["programs"][name][0] / steps
