"""Plain float32 references of the two property predictors.

BDE: a message-passing network over the molecular graph (atom embedding,
``rounds`` rounds of per-bond-order linear messages summed over
neighbours plus a self term, layer norm, gated residual; a per-atom head;
the molecule's BDE is the least over its O-H oxygens).  IP: a per-atom
MLP over chemical and conformer features, masked mean pooling, an MLP
head.  Both written from that description; they import nothing of the
program.  Weights are drawn from the seed by the same ``jax.random``
calls (key splits in parameter order, ``normal * sqrt(2 / fan_in)``,
zero biases, unit layer-norm scales).

``mode`` is the matmul precision, as in ``reference.qnet``.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .qnet import matmul

BOND_ORDERS = 3
OH_CHANNEL = 14          # atom feature: oxygen carrying a hydrogen
BDE_MEAN, BDE_SCALE = 80.0, 10.0
IP_MEAN, IP_SCALE = 150.0, 25.0


def _dense(key, fan_in, fan_out):
    return {"w": jax.random.normal(key, (fan_in, fan_out), jnp.float32)
            * (2.0 / fan_in) ** 0.5,
            "b": jnp.zeros((fan_out,), jnp.float32)}


def init_bde(key, feat: int, d: int, rounds: int) -> dict:
    k = iter(jax.random.split(key, 6 + 2 * rounds * BOND_ORDERS))
    p = {"embed": _dense(next(k), feat, d),
         "head1": _dense(next(k), d, d // 2),
         "head2": _dense(next(k), d // 2, 1), "rounds": []}
    for _ in range(rounds):
        p["rounds"].append({
            "msg": [_dense(next(k), d, d) for _ in range(BOND_ORDERS)],
            "self": _dense(next(k), d, d),
            "ln_scale": jnp.ones((d,), jnp.float32),
            "ln_bias": jnp.zeros((d,), jnp.float32)})
    return p


def init_ip(key, in_dim: int, d: int, ensemble: int = 1) -> dict:
    def one(k):
        k1, k2, k3, k4 = jax.random.split(k, 4)
        return {"atom1": _dense(k1, in_dim, d), "atom2": _dense(k2, d, d),
                "pool1": _dense(k3, d, d // 2), "pool2": _dense(k4, d // 2, 1)}
    return {"ensemble": [one(k) for k in jax.random.split(key, ensemble)]}


def _lin(x, p, mode):
    return matmul(x, p["w"], mode) + p["b"]


def _layer_norm(x, scale, bias):
    mu = x.mean(axis=-1, keepdims=True)
    var = ((x - mu) ** 2).mean(axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + 1e-6) * scale + bias


@partial(jax.jit, static_argnames=("mode",))
def bde(params, atom_feat, adj, mask, mode: str = "highest"):
    """[B, A, F], [B, A, A, 3], [B, A] -> molecule BDE [B] (kcal/mol);
    +inf where the molecule has no O-H oxygen."""
    h = _lin(atom_feat, params["embed"], mode) * mask[..., None]
    for rp in params["rounds"]:
        msg = jnp.zeros_like(h)
        for o in range(BOND_ORDERS):
            m = _lin(h, rp["msg"][o], mode)
            msg = msg + jnp.einsum("bij,bjd->bid", adj[..., o], m,
                                   precision=jax.lax.Precision.HIGHEST)
        upd = _layer_norm(msg + _lin(h, rp["self"], mode),
                          rp["ln_scale"], rp["ln_bias"])
        h = (h + jax.nn.relu(upd)) * mask[..., None]
    z = jax.nn.relu(_lin(h, params["head1"], mode))
    per_atom = _lin(z, params["head2"], mode)[..., 0] * BDE_SCALE + BDE_MEAN
    oh = atom_feat[..., OH_CHANNEL] * mask
    return jnp.min(jnp.where(oh > 0.5, per_atom, jnp.inf), axis=-1)


@partial(jax.jit, static_argnames=("mode",))
def ip(params, atom_feat, conf_feat, mask, mode: str = "highest"):
    """[B, A, F], [B, A, G], [B, A] -> IP [B] (kcal/mol)."""
    x = jnp.concatenate([atom_feat, conf_feat], axis=-1)
    preds = []
    for p in params["ensemble"]:
        h = jax.nn.relu(_lin(x, p["atom1"], mode))
        h = jax.nn.relu(_lin(h, p["atom2"], mode)) * mask[..., None]
        pooled = h.sum(axis=1) / jnp.maximum(mask.sum(axis=1, keepdims=True), 1.0)
        z = jax.nn.relu(_lin(pooled, p["pool1"], mode))
        preds.append(_lin(z, p["pool2"], mode)[..., 0] * IP_SCALE + IP_MEAN)
    return jnp.mean(jnp.stack(preds), axis=0)
