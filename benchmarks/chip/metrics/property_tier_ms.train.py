"""Host milliseconds per environment step inside the property service's
``predict`` (the benchmark's ``bench.predict`` span; predict blocks on
the predictors' results)."""


def read(ctx):
    d = ctx["delta"]
    steps = d["chem"]["env_steps"]
    if ctx["driver"] != "train" or not steps:
        return None
    return 1e3 * d["spans"]["total"].get("bench.predict", 0.0) / steps
