"""The fleet-Q dispatch's share of its roofline: the least time the chip
could take at the operand shapes (``flops.fleet_q_packed``: the larger of
operations over peak FLOP/s and bytes over HBM bandwidth) over the
measured device time per call, in percent."""


def read(ctx):
    t = ctx["trace"]
    name = ctx["cfg"].get("programs", {}).get("q_dispatch")
    if t is None or ctx["driver"] != "train" or name not in t["programs"]:
        return None
    seconds, calls = t["programs"][name]
    if not calls or seconds <= 0:
        return None
    q, W = ctx["cfg"]["qnet"], ctx["cfg"]["trainer"]["n_workers"]
    fl, nb = ctx["flops"].fleet_q_packed(W, ctx["cap"], q["in_dim"], tuple(q["hidden"]))
    pk = ctx["peaks"]
    least = max(fl / pk["flops_per_s"], nb / pk["hbm_bytes_per_s"])
    return 100.0 * least / (seconds / calls)
