"""Train the surrogate predictors against the chemistry oracle.

The paper's predictors come pre-trained on >100k molecules; ours are small
enough to train here, but they must generalise to the molecules the *RL
agent* visits, not just the dataset — so the training corpus augments the
antioxidant sets with random edit-walks (the same action space the agent
uses).  Accuracy target is the paper's: <5% average relative error (§2.2).

``ensure_trained`` is the entry point everything else uses: it trains once
and caches params + a metrics json under ``.cache/predictors`` (or the
``cache_dir`` it is given); ``trained_service`` wraps its result in the
``PropertyService`` that training, serving and the benchmarks share.
"""

from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.chem.actions import enumerate_actions
from repro.chem.molecule import Molecule
from repro.chem.oracle import oracle_bde, oracle_ip
from repro.checkpoint import load_pytree, save_pytree
from repro.data.datasets import antioxidant_dataset, public_antioxidant_dataset
from repro.optim import adam
from repro.optim.adam import apply_updates
from repro.predictors.gnn import AlfabetS, BDE_MEAN, BDE_SCALE
from repro.predictors.ip_net import AIMNetS, IP_MEAN, IP_SCALE
from repro.predictors.service import (
    MAX_ATOMS, PropertyService, featurize, stack_features)

DEFAULT_CACHE_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..", ".cache", "predictors")


# ------------------------------------------------------------------ #
# corpus
# ------------------------------------------------------------------ #
def build_corpus(n_walk_steps: int = 3, seed: int = 11, max_mols: int = 4000) -> list[Molecule]:
    """Dataset molecules + random edit-walk intermediates (dedup'd)."""
    rng = np.random.default_rng(seed)
    base = antioxidant_dataset(600) + public_antioxidant_dataset(256)
    out: list[Molecule] = []
    seen: set[int] = set()

    def add(m: Molecule) -> None:
        key = m.iso_key()
        if key not in seen and m.num_atoms <= MAX_ATOMS:
            seen.add(key)
            out.append(m)

    for m in base:
        add(m)
    for m in base:
        cur = m
        for _ in range(n_walk_steps):
            acts = enumerate_actions(cur, protect_oh=True)
            if len(acts) <= 1:
                break
            cur = acts[int(rng.integers(1, len(acts)))].result
            add(cur)
        if len(out) >= max_mols:
            break
    return out[:max_mols]


def featurized_corpus(mols: list[Molecule]) -> tuple[dict, np.ndarray, np.ndarray, np.ndarray]:
    """Stacked features + oracle targets + validity masks."""
    feats = stack_features([featurize(m) for m in mols])
    bde = np.array([oracle_bde(m) if m.has_oh_bond() else np.nan for m in mols], np.float32)
    ip = np.array([oracle_ip(m) for m in mols], np.float32)
    has_bde = np.isfinite(bde)
    return feats, bde, ip, has_bde


# ------------------------------------------------------------------ #
# training loops
# ------------------------------------------------------------------ #
def _minibatches(rng: np.random.Generator, n: int, batch: int):
    while True:
        order = rng.permutation(n)
        for s in range(0, n - batch + 1, batch):
            yield order[s : s + batch]


def train_bde_model(
    mols: list[Molecule] | None = None,
    *,
    steps: int = 1500,
    batch_size: int = 128,
    lr: float = 3e-4,
    seed: int = 0,
    log_every: int = 0,
) -> tuple[AlfabetS, dict, dict]:
    """Returns (model, params, metrics)."""
    model = AlfabetS()
    mols = mols if mols is not None else build_corpus()
    feats, bde, _, has_bde = featurized_corpus(mols)
    idx = np.nonzero(has_bde)[0]
    n_hold = max(len(idx) // 10, 1)
    hold, train = idx[:n_hold], idx[n_hold:]

    params = model.init(jax.random.PRNGKey(seed))
    opt = adam(lr, clip_norm=1.0)
    state = opt.init(params)

    target_n = (bde - BDE_MEAN) / BDE_SCALE

    @jax.jit
    def step(params, state, batch, tgt):
        def loss_fn(p):
            _, mol_bde = model.apply(p, batch)
            pred_n = (mol_bde - BDE_MEAN) / BDE_SCALE
            return jnp.mean(jnp.square(pred_n - tgt))
        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, state2 = opt.update(grads, state, params)
        return apply_updates(params, updates), state2, loss

    rng = np.random.default_rng(seed)
    gen = _minibatches(rng, len(train), min(batch_size, len(train)))
    for it in range(steps):
        sel = train[next(gen)]
        batch = {k: jnp.asarray(v[sel]) for k, v in feats.items()}
        params, state, loss = step(params, state, batch, jnp.asarray(target_n[sel]))
        if log_every and (it + 1) % log_every == 0:
            print(f"[bde] step {it+1}: loss {float(loss):.4f}")

    metrics = _eval_bde(model, params, feats, bde, hold)
    return model, params, metrics


def _eval_bde(model, params, feats, bde, idx) -> dict:
    batch = {k: jnp.asarray(v[idx]) for k, v in feats.items()}
    _, pred = jax.jit(model.apply)(params, batch)
    pred = np.asarray(pred)
    rel = np.abs(pred - bde[idx]) / np.abs(bde[idx])
    return {"rel_err_mean": float(rel.mean()), "rel_err_p95": float(np.percentile(rel, 95)),
            "mae": float(np.abs(pred - bde[idx]).mean()), "n_eval": int(len(idx))}


def train_ip_model(
    mols: list[Molecule] | None = None,
    *,
    steps: int = 1500,
    batch_size: int = 128,
    lr: float = 3e-4,
    seed: int = 1,
    log_every: int = 0,
) -> tuple[AIMNetS, dict, dict]:
    model = AIMNetS()
    mols = mols if mols is not None else build_corpus()
    feats, _, ip, _ = featurized_corpus(mols)
    valid = np.nonzero(feats["conf_valid"] > 0.5)[0]
    n_hold = max(len(valid) // 10, 1)
    hold, train = valid[:n_hold], valid[n_hold:]

    params = model.init(jax.random.PRNGKey(seed))
    opt = adam(lr, clip_norm=1.0)
    state = opt.init(params)
    target_n = (ip - IP_MEAN) / IP_SCALE

    @jax.jit
    def step(params, state, batch, tgt):
        def loss_fn(p):
            pred = model.apply(p, batch)
            return jnp.mean(jnp.square((pred - IP_MEAN) / IP_SCALE - tgt))
        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, state2 = opt.update(grads, state, params)
        return apply_updates(params, updates), state2, loss

    rng = np.random.default_rng(seed)
    gen = _minibatches(rng, len(train), min(batch_size, len(train)))
    for it in range(steps):
        sel = train[next(gen)]
        batch = {k: jnp.asarray(v[sel]) for k, v in feats.items()}
        params, state, loss = step(params, state, batch, jnp.asarray(target_n[sel]))
        if log_every and (it + 1) % log_every == 0:
            print(f"[ip] step {it+1}: loss {float(loss):.4f}")

    batch = {k: jnp.asarray(v[hold]) for k, v in feats.items()}
    pred = np.asarray(jax.jit(model.apply)(params, batch))
    rel = np.abs(pred - ip[hold]) / np.abs(ip[hold])
    metrics = {"rel_err_mean": float(rel.mean()), "rel_err_p95": float(np.percentile(rel, 95)),
               "mae": float(np.abs(pred - ip[hold]).mean()), "n_eval": int(len(hold))}
    return model, params, metrics


# ------------------------------------------------------------------ #
# disk-cached entry point
# ------------------------------------------------------------------ #
def ensure_trained(cache_dir: str | None = None, *, steps: int = 1500, verbose: bool = True):
    """Train-or-load both predictors.  Returns (bde_model, bde_params,
    ip_model, ip_params, metrics)."""
    cache_dir = os.path.abspath(cache_dir or DEFAULT_CACHE_DIR)
    os.makedirs(cache_dir, exist_ok=True)
    bde_path = os.path.join(cache_dir, "alfabet_s.npz")
    ip_path = os.path.join(cache_dir, "aimnet_s.npz")
    meta_path = os.path.join(cache_dir, "metrics.json")

    bde_model, ip_model = AlfabetS(), AIMNetS()
    if os.path.exists(bde_path) and os.path.exists(ip_path) and os.path.exists(meta_path):
        bde_params = load_pytree(bde_path, bde_model.init(jax.random.PRNGKey(0)))
        ip_params = load_pytree(ip_path, ip_model.init(jax.random.PRNGKey(1)))
        with open(meta_path) as f:
            metrics = json.load(f)
        return bde_model, bde_params, ip_model, ip_params, metrics

    if verbose:
        print("[predictors] training Alfabet-S + AIMNet-S against the oracle ...")
    mols = build_corpus()
    bde_model, bde_params, bde_metrics = train_bde_model(mols, steps=steps)
    ip_model, ip_params, ip_metrics = train_ip_model(mols, steps=steps)
    metrics = {"bde": bde_metrics, "ip": ip_metrics, "steps": steps}
    if verbose:
        print(f"[predictors] BDE rel err {bde_metrics['rel_err_mean']:.3%}, "
              f"IP rel err {ip_metrics['rel_err_mean']:.3%}")
    save_pytree(bde_path, bde_params)
    save_pytree(ip_path, ip_params)
    with open(meta_path, "w") as f:
        json.dump(metrics, f, indent=2)
    return bde_model, bde_params, ip_model, ip_params, metrics


def trained_service(cache_dir: str | None = None, *, steps: int = 1500,
                    verbose: bool = False) -> tuple[PropertyService, dict]:
    """The trained BDE+IP ``PropertyService`` (train-or-load through
    ``ensure_trained``) and the predictors' held-out metrics."""
    bm, bp, im, ip_, metrics = ensure_trained(cache_dir, steps=steps,
                                              verbose=verbose)
    return PropertyService(bm, bp, im, ip_), metrics
