"""Mean share of the router's slots bound to a request in a service
step, in percent: the slots the serving Q dispatch was given rows for,
counted by the driver at each step, over service steps times slots."""


def read(ctx):
    d = ctx["delta"]
    steps = d.get("service_steps")
    if ctx["driver"] != "serve" or not steps:
        return None
    return 100.0 * d["bound"] / (steps * ctx["cfg"]["serve"]["n_slots"])
