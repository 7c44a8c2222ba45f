"""Model FLOPs of the training window over the chip's peak, in percent:
acting Q rows actually evaluated (forward), the learner's forward passes
(online on the sampled states, online and target on their useful
successor rows) and backward pass on the states, and the molecules the
predictors ran; padding and the episode sync do not count."""


def read(ctx):
    if ctx["driver"] != "train":
        return None
    d, f, cfg = ctx["delta"], ctx["flops"], ctx["cfg"]
    q, p = cfg["qnet"], cfg["predictors"]
    hid, ind = tuple(q["hidden"]), q["in_dim"]
    states = d["updates"] * cfg["trainer"]["n_workers"] * cfg["trainer"]["train_batch_size"]
    total = (f.qnet_forward_flops(d["rows"] + states + 2 * d["next_rows"], ind, hid)
             + f.qnet_backward_flops(states, ind, hid)
             + d["predict_mols"] * (
                 f.alfabet_flops(p["max_atoms"], p["atom_feat"], p["bde_hidden"],
                                 p["bde_rounds"])
                 + f.aimnet_flops(p["max_atoms"], p["atom_feat"], p["conf_feat"],
                                  p["ip_hidden"], p["ip_ensemble"])))
    return 100.0 * total / (ctx["window_s"] * ctx["peaks"]["flops_per_s"])
