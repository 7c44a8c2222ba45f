"""Device milliseconds of the episode sync program per episode, from the
trace (``programs.sync``; the trainer calls it for the parameters and
both Adam moments)."""


def read(ctx):
    t = ctx["trace"]
    name = ctx["cfg"].get("programs", {}).get("sync")
    eps = ctx["delta"]["episodes"] if ctx["driver"] == "train" else 0
    if t is None or not eps or name not in t["programs"]:
        return None
    return 1e3 * t["programs"][name][0] / eps
