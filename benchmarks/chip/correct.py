"""How ``correct`` is decided: what the timed path produced, against the
plain float32 references in ``reference/``.

The program's *readings* and the reference's are the same structure;
``*_numbers`` turns a pair into the compared numbers, each with its limit
in the configuration file.  The control (``control.py``) puts the
reference, computed one precision step lower (``mode="fp8"``), in the
program's place, and planted faults put a broken reference there.

Training (the warm episode, which the window's own call ran):
    q_gap          acting: worst |Q - Q_ref| over every valid candidate row
                   of every acting dispatch (initial weights), over the
                   median |Q_ref|
    fp_rows_wrong  candidate rows, of a seed-drawn sample, whose packed
                   fingerprint differs from the reference's (exact)
    loss_gap       the first learner call: worst relative gap of the
                   per-update mean loss
    grad_gap       the first clipped gradient, read from the optimizer's
                   state after one update: worst leaf (per worker)
    change_gap     the parameters' change after the learner call and the
                   episode sync: worst leaf (per worker)
    bde_gap, ip_gap   worst relative gap of the predictors' answers on a
                   seed-drawn sample of the molecules they were asked about,
                   over the same gap of the control on the same sample: an
                   untrained predictor's weights amplify round-off by a
                   factor that differs from seed to seed (on a TPU v5e the
                   control's gap spans 0.017-0.16 over seeds), and the
                   share of the control's gap does not

Serving (a seeded sample of the window's service steps, ``drivers/serve.py``):
    fp_rows_wrong  candidate rows, of a seed-drawn sample of the rows the
                   serving Q dispatch was given, whose fingerprint differs
                   from the reference's Morgan fingerprint of the candidate,
                   holds a value other than 0 or 1, or whose steps-left
                   column differs from the slot's (exact)
    serve_q_gap    worst |Q - Q_ref| over every dispatched row over the
                   median |Q_ref|, as a share of the same gap of the control
                   on the same rows (as the predictor gaps: the gap itself
                   swings with the seed's weights, the share does not)
    action_wrong   greedy choices that are not an argmax of the program's own
                   Q values for the slot (a tie at the top counts as right)
    bde_gap, ip_gap   as in training, over the molecules the predictors
                   computed afresh in the sampled steps

A gap of norms is measured against the larger of the reference's norm of
that leaf and of the median kept leaf.  Leaves whose reference gradient
is under ``leaf_grad_floor`` times the median leaf's are left out of both
norm gaps (Adam moves them by round-off alone).
"""

from __future__ import annotations

import numpy as np

from .reference import features as rfeat, morgan, predictors as rpred, qnet as rq

FP_SAMPLE = 2048
PRED_SAMPLE = 512


def _median_abs(x: np.ndarray) -> float:
    m = float(np.median(np.abs(x))) if x.size else 0.0
    return m if m > 0 else 1.0


def qnet_sizes(cfg: dict) -> tuple[int, ...]:
    q = cfg["qnet"]
    return (q["in_dim"],) + tuple(q["hidden"]) + (1,)


def learner_hp(cfg: dict) -> dict:
    lr = cfg["learner"]
    return {k: lr[k] for k in ("lr", "b1", "b2", "eps", "clip", "discount")}


def weight_keys(pseed: int):
    """(Q, BDE, IP) keys: the same derivation the harness gives the
    program (``drivers.common.weight_keys``), written out here."""
    import jax

    base = jax.random.PRNGKey(pseed)
    return tuple(jax.random.fold_in(base, i) for i in (0, 1, 2))


# ------------------------------------------------------------------ #
# samples of what the program produced
# ------------------------------------------------------------------ #
def sample(items: list, limit: int, rng: np.random.Generator) -> list:
    if len(items) <= limit:
        return list(items)
    return [items[i] for i in np.sort(rng.choice(len(items), limit, replace=False))]


def train_fp_rows(capture: dict) -> list:
    """(program packed row, candidate action) of every acting row."""
    rows = []
    for d in capture["dispatches"]:
        for bits, cands in zip(d["bits"], d["cands"]):
            rows += [(bits[r], cands[r]) for r in range(len(cands))]
    return rows


def predicted_pairs(record: list) -> list:
    """Distinct (molecule, properties) pairs of the recorded predict
    calls.  The service answers a molecule from its cache by isomorphism
    key, and the IP network's conformer features depend on the atom
    labelling, so each pair takes the molecule as the service first saw it
    (the labelling it computed): the answer cache is empty when recording
    starts."""
    seen, pairs = set(), []
    for mols, props in record:
        for m, p in zip(mols, props):
            k = m.iso_key()
            if p is not None and k not in seen:
                seen.add(k)
                pairs.append((m, p))
    return pairs


# ------------------------------------------------------------------ #
# the reference
# ------------------------------------------------------------------ #
def ref_fingerprints(actions: list) -> np.ndarray:
    out = [morgan.packed_fingerprint(a.result.elements, a.result.bonds)
           for a in actions]
    return np.stack(out) if out else np.zeros((0, 256), np.uint8)


def ref_predictions(cfg: dict, pseed: int, mols: list, modes: tuple) -> dict:
    """Reference BDE and IP of each molecule (its elements and bonds as
    the program asked about it), on the reference's own features, at each
    matmul precision of ``modes``: ``{mode: {"bde": ..., "ip": ...}}``."""
    import jax.numpy as jnp

    p = cfg["predictors"]
    if not mols:
        return {m: {"bde": np.zeros(0), "ip": np.zeros(0)} for m in modes}
    f = rfeat.features([(m.elements, m.bonds) for m in mols], p["max_atoms"])
    keys = weight_keys(pseed)
    bde_w = rpred.init_bde(keys[1], p["atom_feat"], p["bde_hidden"], p["bde_rounds"])
    ip_w = rpred.init_ip(keys[2], p["atom_feat"] + p["conf_feat"], p["ip_hidden"],
                         p["ip_ensemble"])
    a, mask = jnp.asarray(f["atom_feat"]), jnp.asarray(f["mask"])
    out = {}
    for mode in modes:
        bde = np.asarray(rpred.bde(bde_w, a, jnp.asarray(f["adj"]), mask, mode=mode))
        ip = np.asarray(rpred.ip(ip_w, a, jnp.asarray(f["conf_feat"]), mask,
                                 mode=mode))
        out[mode] = {"bde": np.where(f["has_oh"], bde, np.nan),
                     "ip": np.where(f["conf_valid"] > 0.5, ip, np.nan)}
    return out


def program_predictions(pairs: list) -> dict:
    nan = float("nan")
    return {"bde": np.array([nan if p.bde is None else p.bde for _, p in pairs]),
            "ip": np.array([nan if p.ip is None else p.ip for _, p in pairs])}


def initial_qnet(cfg: dict, pseed: int):
    """The trainer's initial weights, every worker's: worker 0's key of
    the seed's split over the fleet."""
    import jax

    W = cfg["trainer"]["n_workers"]
    return rq.init_qnet(jax.random.split(jax.random.PRNGKey(pseed), W)[0],
                        qnet_sizes(cfg))


def ref_acting(cfg: dict, pseed: int, capture: dict, mode: str) -> list:
    """Reference Q of every acting row of the warm episode: every worker
    still holds the initial weights there."""
    p0 = initial_qnet(cfg, pseed)
    out = []
    for d in capture["dispatches"]:
        sizes = [b.shape[0] for b in d["bits"]]
        q = rq.q_rows_blocked(p0, np.concatenate(d["bits"]),
                              np.concatenate(d["frac"]).astype(np.float32), mode)
        out.append(np.split(q, np.cumsum(sizes)[:-1]))
    return out


def ref_learner(cfg: dict, pseed: int, batches: list, mode: str,
                rows: int | None = None, sync: bool = True) -> dict:
    return rq.learner_reference(initial_qnet(cfg, pseed), batches,
                                hp=learner_hp(cfg), mode=mode, rows=rows,
                                sync=sync)


# ------------------------------------------------------------------ #
# the compared numbers
# ------------------------------------------------------------------ #
def q_gap(prog_q: list, ref_q: list) -> float:
    diffs, refs = [], []
    for pd, rd in zip(prog_q, ref_q):
        for p, r in zip(pd, rd):
            if r.size:
                diffs.append(np.abs(np.asarray(p, np.float32)[:r.size] - r))
                refs.append(r)
    if not diffs:
        return float("inf")
    return float(np.max(np.concatenate(diffs)) / _median_abs(np.concatenate(refs)))


def fp_rows_wrong(prog_rows: np.ndarray, ref_rows: np.ndarray) -> int:
    return int(np.count_nonzero(np.any(prog_rows != ref_rows, axis=-1)))


def rel_gap(prog: np.ndarray, ref: np.ndarray) -> float:
    """Worst gap where the reference has an answer, over the larger of
    that answer's size and the median answer's (an untrained predictor's
    answer can lie near 0); inf where the program has none there."""
    fr = np.isfinite(ref)
    if not fr.any():
        return 0.0
    if np.any(fr & ~np.isfinite(prog)):
        return float("inf")
    den = np.maximum(np.abs(ref[fr]), _median_abs(ref[fr]))
    return float(np.max(np.abs(prog[fr] - ref[fr]) / den))


def control_share(prog: np.ndarray, ctl: np.ndarray, ref: np.ndarray) -> float:
    """``rel_gap`` of the program over ``rel_gap`` of the control, both
    against the reference on the same sample; 1 for the control itself."""
    p, c = rel_gap(prog, ref), rel_gap(ctl, ref)
    if c > 0:
        return p / c
    return 0.0 if p == 0 else float("inf")


def kept_leaves(ref_grad_norms: np.ndarray, cfg: dict) -> np.ndarray:
    med = float(np.median(ref_grad_norms))
    return ref_grad_norms >= cfg["limits"]["leaf_grad_floor"] * med


def _norm_gaps(prog: np.ndarray, ref: np.ndarray, keep: np.ndarray) -> np.ndarray:
    med = float(np.median(ref[keep])) if keep.any() else 1.0
    return np.where(keep, np.abs(prog - ref) / np.maximum(ref, med), 0.0)


def norm_gap(prog: np.ndarray, ref: np.ndarray, keep: np.ndarray) -> float:
    return float(np.max(_norm_gaps(prog, ref, keep)))


def worst_leaf(prog: np.ndarray, ref: np.ndarray, keep: np.ndarray) -> str:
    """Where a norm gap peaks: ``w<worker>/leaf<index>`` (leaves in
    ``tree_leaves`` order: each layer's bias, then its weight)."""
    w, leaf = np.unravel_index(np.argmax(_norm_gaps(prog, ref, keep)), ref.shape)
    return f"w{w}/leaf{leaf}"


def learner_numbers(prog: dict, ref: dict, cfg: dict) -> dict:
    keep = kept_leaves(ref["grad_norms"], cfg)
    n = min(len(prog["losses"]), len(ref["losses"]))
    return {
        "loss_gap": float(np.max(np.abs(prog["losses"][:n] - ref["losses"][:n])
                                 / np.abs(ref["losses"][:n]))),
        "grad_gap": norm_gap(prog["grad_norms"], ref["grad_norms"], keep),
        "change_gap": norm_gap(prog["change_norms"], ref["change_norms"], keep),
    }


def judge(numbers: dict, limits: dict) -> dict:
    return {k: {"value": float(v), "limit": limits[k], "ok": bool(v <= limits[k])}
            for k, v in numbers.items()}


# ------------------------------------------------------------------ #
# one cell's check, program against reference
# ------------------------------------------------------------------ #
def check_train(cfg: dict, pseed: int, capture: dict,
                rng: np.random.Generator) -> tuple[dict, dict]:
    """Numbers of the program against the reference, and what the
    control needs to reuse (samples and the reference's readings)."""
    rows = sample(train_fp_rows(capture), FP_SAMPLE, rng)
    pairs = sample(predicted_pairs(capture["predictions"]), PRED_SAMPLE, rng)
    fp_ref = ref_fingerprints([a for _, a in rows])
    fp_prog = np.stack([r for r, _ in rows])
    q_ref = ref_acting(cfg, pseed, capture, "highest")
    learn_ref = ref_learner(cfg, pseed, capture["batches"], "highest")
    preds = ref_predictions(cfg, pseed, [m for m, _ in pairs], ("highest", "fp8"))
    pred_ref, pred_ctl = preds["highest"], preds["fp8"]
    pred_prog = program_predictions(pairs)
    prog_learn = {k: capture[k] for k in ("losses", "grad_norms", "change_norms")}
    numbers = {"q_gap": q_gap([d["q"] for d in capture["dispatches"]], q_ref),
               "fp_rows_wrong": fp_rows_wrong(fp_prog, fp_ref),
               **learner_numbers(prog_learn, learn_ref, cfg),
               **{f"{k}_gap": control_share(pred_prog[k], pred_ctl[k], pred_ref[k])
                  for k in ("bde", "ip")}}
    gaps = {f"{k} gap {who}": rel_gap(x[k], pred_ref[k])
            for k in ("bde", "ip") for who, x in (("program", pred_prog),
                                                  ("control", pred_ctl))}
    return numbers, {"q_ref": q_ref, "learn_ref": learn_ref, "pred_ref": pred_ref,
                     "pred_ctl": pred_ctl, "pred_prog": pred_prog,
                     "counts": {"acting rows": sum(r.size for d in q_ref for r in d),
                                "leaves left out": int(
                                    (~kept_leaves(learn_ref["grad_norms"], cfg)).sum()),
                                "grad gap at": worst_leaf(
                                    prog_learn["grad_norms"], learn_ref["grad_norms"],
                                    kept_leaves(learn_ref["grad_norms"], cfg)),
                                "change gap at": worst_leaf(
                                    prog_learn["change_norms"], learn_ref["change_norms"],
                                    kept_leaves(learn_ref["grad_norms"], cfg)),
                                "fingerprint rows": len(rows),
                                "learner updates": len(learn_ref["losses"]),
                                "molecules": len(pairs), **gaps}}


# ------------------------------------------------------------------ #
# serving: the sampled service steps
# ------------------------------------------------------------------ #
def serve_rows(cfg: dict, capture: dict) -> dict:
    """Every row the sampled steps dispatched: the program's packed bits,
    steps-left column and Q, whether its fingerprint held only 0s and 1s,
    its candidate, the steps-left value the slot implies, and the number
    of rows short of (or past) the slot's candidates."""
    fp_bits, steps = cfg["qnet"]["in_dim"] - 1, cfg["serve"]["max_steps"]
    out = {k: [] for k in ("bits", "frac", "binary", "q", "want_frac")}
    out["cands"], out["segments"], out["mismatch"] = [], [], 0
    for st in capture["steps"]:
        if "x" not in st:
            continue                  # a step that dispatched nothing
        for x, q, cands, slots in zip(st["x"], st["q"], st["cands"], st["slots"]):
            want = np.concatenate([np.full(n, (left - 1) / steps, np.float32)
                                   for left, n in slots]) if slots else \
                np.zeros(0, np.float32)
            n = min(len(x), len(cands))
            out["mismatch"] += abs(len(x) - len(cands))
            if not n:
                continue
            fp = np.asarray(x[:n, :fp_bits])
            out["bits"].append(np.packbits(fp > 0.5, axis=-1))
            out["binary"].append(np.all((fp == 0) | (fp == 1), axis=-1))
            out["frac"].append(np.asarray(x[:n, fp_bits], np.float32))
            out["q"].append(np.asarray(q[:n], np.float32))
            out["want_frac"].append(want[:n])
            out["cands"] += list(cands[:n])
            out["segments"].append(n)
    for k in ("bits", "frac", "binary", "q", "want_frac"):
        out[k] = np.concatenate(out[k]) if out[k] else np.zeros(0)
    return out


def serve_qnet(cfg: dict, pseed: int):
    """The served Q-network's weights: drawn from the seed's Q key."""
    return rq.init_qnet(weight_keys(pseed)[0], qnet_sizes(cfg))


def q_share(prog: np.ndarray, ctl: np.ndarray, ref: np.ndarray) -> float:
    """The worst gap over the median |Q_ref| of the program, over the same
    gap of the control; 1 for the control itself."""
    if not ref.size:
        return float("inf")
    med = _median_abs(ref)
    p = float(np.max(np.abs(prog - ref))) / med
    c = float(np.max(np.abs(ctl - ref))) / med
    if c > 0:
        return p / c
    return 0.0 if p == 0 else float("inf")


def action_wrong(capture: dict, shift: int = 0) -> int:
    """Greedy choices (each moved ``shift`` rows on, for a fault) that are
    not an argmax of the program's Q values of their slot."""
    bad = 0
    for st in capture["steps"]:
        for w, a in st["choices"]:
            q = np.asarray(st["q"][w], np.float32)
            a = (a + shift) % max(len(q), 1)
            bad += int(not (0 <= a < len(q)) or q[a] < q.max())
    return bad


def check_serve(cfg: dict, pseed: int, capture: dict,
                rng: np.random.Generator) -> tuple[dict, dict]:
    """Numbers of the served steps against the reference, and what the
    control needs to reuse."""
    rows = serve_rows(cfg, capture)
    pick = sample(list(range(len(rows["cands"]))), FP_SAMPLE, rng)
    pairs = sample(predicted_pairs(capture["predictions"]), PRED_SAMPLE, rng)
    fp_ref = ref_fingerprints([rows["cands"][i] for i in pick])
    fp_prog = rows["bits"][pick] if pick else np.zeros((0, 256), np.uint8)
    ok_rows = (rows["binary"][pick].astype(bool)
               & (rows["frac"][pick] == rows["want_frac"][pick])) if pick else \
        np.zeros(0, bool)
    # the reference's Q takes its own operands: the sampled rows' reference
    # fingerprints and the steps left that the client's budget implies
    params = serve_qnet(cfg, pseed)
    frac_ref = rows["want_frac"][pick] if pick else np.zeros(0, np.float32)
    q_ref, q_ctl = (rq.q_rows_blocked(params, fp_ref, frac_ref, mode)
                    if pick else np.zeros(0, np.float32)
                    for mode in ("highest", "fp8"))
    q_prog = rows["q"][pick] if pick else np.zeros(0, np.float32)
    preds = ref_predictions(cfg, pseed, [m for m, _ in pairs], ("highest", "fp8"))
    pred_ref, pred_ctl = preds["highest"], preds["fp8"]
    pred_prog = program_predictions(pairs)
    wrong = np.any(fp_prog != fp_ref, axis=-1) | ~ok_rows
    numbers = {"fp_rows_wrong": int(np.count_nonzero(wrong)) + rows["mismatch"],
               "serve_q_gap": q_share(q_prog, q_ctl, q_ref),
               "action_wrong": action_wrong(capture),
               **{f"{k}_gap": control_share(pred_prog[k], pred_ctl[k], pred_ref[k])
                  for k in ("bde", "ip")}}
    gaps = {f"{k} gap {who}": rel_gap(x[k], pred_ref[k])
            for k in ("bde", "ip") for who, x in (("program", pred_prog),
                                                  ("control", pred_ctl))}
    med = _median_abs(q_ref) if q_ref.size else 1.0
    return numbers, {"rows": rows, "pick": pick, "q_prog": q_prog,
                     "q_ref": q_ref, "q_ctl": q_ctl,
                     "fp_prog": fp_prog, "fp_ref": fp_ref,
                     "pred_ref": pred_ref, "pred_ctl": pred_ctl,
                     "pred_prog": pred_prog,
                     "counts": {"service steps": len(capture["steps"]),
                                "Q rows": int(q_ref.size),
                                "choices": sum(len(s["choices"])
                                               for s in capture["steps"]),
                                "fingerprint rows": len(pick),
                                "molecules": len(pairs),
                                "Q gap program": float(np.max(np.abs(
                                    q_prog - q_ref)) / med) if q_ref.size else 0.0,
                                "Q gap control": float(np.max(np.abs(
                                    q_ctl - q_ref)) / med) if q_ref.size else 0.0,
                                **gaps}}
