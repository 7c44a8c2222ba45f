"""Plain float32 reference of the DA-MolDQN Q-network and its learner.

Written from the published description (MolDQN: an MLP over a Morgan
fingerprint of the candidate successor plus a steps-left feature; double
DQN with a target network, Huber loss, Adam) and imports nothing of the
program.  Weights are drawn from the seed here, by the same
``jax.random`` calls a He-initialised MLP makes: one key split per layer,
``normal * sqrt(2 / fan_in)`` weights and zero biases.

``mode`` picks the matmul precision: ``"highest"`` is the reference
(float32, all passes); ``"fp8"`` is the control, every matmul operand
rounded to float8 e4m3 in the forward and the backward pass, the step
below the bfloat16 single pass that DEFAULT precision gives on a TPU.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def init_qnet(key, sizes: tuple[int, ...]) -> list[dict]:
    keys = jax.random.split(key, len(sizes) - 1)
    return [{"w": jax.random.normal(k, (i, o), jnp.float32) * (2.0 / i) ** 0.5,
             "b": jnp.zeros((o,), jnp.float32)}
            for k, (i, o) in zip(keys, zip(sizes[:-1], sizes[1:]))]


def _fp8(x):
    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)


@jax.custom_vjp
def matmul_fp8(x, w):
    return jnp.matmul(_fp8(x), _fp8(w), precision=HIGHEST)


def _mm_fwd(x, w):
    return matmul_fp8(x, w), (x, w)


def _mm_bwd(res, g):
    x, w = res
    g8 = _fp8(g)
    dx = jnp.matmul(g8, _fp8(w).T, precision=HIGHEST)
    xr = _fp8(x).reshape(-1, x.shape[-1])
    dw = jnp.matmul(xr.T, g8.reshape(-1, g.shape[-1]), precision=HIGHEST)
    return dx, dw


matmul_fp8.defvjp(_mm_fwd, _mm_bwd)


def matmul(x, w, mode: str):
    if mode == "highest":
        return jnp.matmul(x, w, precision=HIGHEST)
    if mode == "fp8":
        return matmul_fp8(x, w)
    raise ValueError(f"unknown precision mode {mode!r}")


def q_forward(layers: list[dict], x, mode: str = "highest"):
    """x [..., in_dim] -> q [...]."""
    h = x
    for i, layer in enumerate(layers):
        h = matmul(h, layer["w"], mode) + layer["b"]
        if i < len(layers) - 1:
            h = jax.nn.relu(h)
    return h[..., 0]


def unpack(bits):
    """u8 [..., 256] -> f32 [..., 2048]: bit 8i+k is bit (7-k) of byte i."""
    shifts = jnp.arange(7, -1, -1, dtype=jnp.uint8)
    x = (bits[..., None] >> shifts) & 1
    return x.reshape(bits.shape[:-1] + (-1,)).astype(jnp.float32)


# ------------------------------------------------------------------ #
# acting: Q over candidate rows
# ------------------------------------------------------------------ #
@partial(jax.jit, static_argnames=("mode",))
def q_rows(layers, bits, frac, mode: str = "highest"):
    """bits u8 [N, 256], frac f32 [N] -> q [N] under one parameter set."""
    x = jnp.concatenate([unpack(bits), frac[..., None]], axis=-1)
    return q_forward(layers, x, mode)


def q_rows_blocked(layers, bits: np.ndarray, frac: np.ndarray,
                   mode: str = "highest", block: int = 8192) -> np.ndarray:
    """``q_rows`` over many rows in fixed-size blocks (one compile)."""
    n = bits.shape[0]
    out = np.empty((n,), np.float32)
    for lo in range(0, n, block):
        b = bits[lo:lo + block]
        f = frac[lo:lo + block]
        k = b.shape[0]
        if k < block:
            b = np.concatenate([b, np.zeros((block - k,) + b.shape[1:], b.dtype)])
            f = np.concatenate([f, np.zeros((block - k,), f.dtype)])
        out[lo:lo + k] = np.asarray(q_rows(layers, b, f, mode))[:k]
    return out


# ------------------------------------------------------------------ #
# learner: double DQN + Adam (global-norm clip) per worker, episode sync
# ------------------------------------------------------------------ #
def dense_batch(packed: dict) -> dict:
    """One worker's packed replay batch (``state_bits [B, 256]``,
    ``state_frac``, ``rewards``, ``dones``, ``next_bits [B, C, 256]``,
    ``next_frac``, ``next_counts``) -> dense float32 arrays; successor
    rows past a transition's count, and all of a terminal one's, are 0."""
    states = jnp.concatenate([unpack(packed["state_bits"]),
                              packed["state_frac"][..., None]], axis=-1)
    C = packed["next_bits"].shape[-2]
    eff = jnp.where(packed["dones"] > 0, 0,
                    jnp.minimum(packed["next_counts"], C))
    mask = (jnp.arange(C) < eff[..., None]).astype(jnp.float32)
    nxt = jnp.concatenate([unpack(packed["next_bits"]) * mask[..., None],
                           (packed["next_frac"][..., None] * mask)[..., None]],
                          axis=-1)
    return {"states": states, "rewards": packed["rewards"].astype(jnp.float32),
            "dones": packed["dones"].astype(jnp.float32), "next": nxt,
            "mask": mask}


def huber(x, delta: float = 1.0):
    a = jnp.abs(x)
    return jnp.where(a <= delta, 0.5 * x * x, delta * (a - 0.5 * delta))


def dqn_loss(p, tp, b, discount: float, mode: str, rows: int | None):
    """Double-DQN Huber loss of one worker's batch.  ``rows`` keeps only
    the first rows of the batch (a planted fault: half the batch left
    out, the mean taken over the rest)."""
    if rows is not None:
        b = {k: v[:rows] for k, v in b.items()}
    q_sa = q_forward(p, b["states"], mode)
    q_on = q_forward(p, b["next"], mode)
    q_on = jnp.where(b["mask"] > 0, q_on, -jnp.inf)
    a_star = jnp.argmax(q_on, axis=-1)
    q_tg = q_forward(tp, b["next"], mode)
    v = jnp.take_along_axis(q_tg, a_star[:, None], axis=-1)[:, 0]
    v = jnp.where(b["mask"].sum(-1) > 0, v, 0.0)
    y = jax.lax.stop_gradient(b["rewards"] + discount * (1.0 - b["dones"]) * v)
    return jnp.mean(huber(q_sa - y))


def adam_step(p, m, v, g, step: int, lr: float, b1: float, b2: float,
              eps: float, clip: float):
    """One Adam step with the gradient clipped to a global norm."""
    leaves = jax.tree_util.tree_leaves(g)
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in leaves))
    g = jax.tree_util.tree_map(
        lambda x: x * jnp.minimum(1.0, clip / (norm + 1e-12)), g)
    m = jax.tree_util.tree_map(lambda m_, g_: b1 * m_ + (1 - b1) * g_, m, g)
    v = jax.tree_util.tree_map(lambda v_, g_: b2 * v_ + (1 - b2) * g_ * g_, v, g)
    bc1 = 1.0 - b1 ** jnp.float32(step)
    bc2 = 1.0 - b2 ** jnp.float32(step)
    p = jax.tree_util.tree_map(
        lambda p_, m_, v_: p_ - lr * ((m_ / bc1) / (jnp.sqrt(v_ / bc2) + eps)),
        p, m, v)
    return p, m, v, g


def learner_reference(p0: list[dict], batches: list[dict], *, hp: dict,
                      mode: str = "highest", rows: int | None = None,
                      sync: bool = True) -> dict:
    """Follow the program's first learner call from the initial weights.

    ``batches[u]`` holds update ``u``'s stacked packed batch, each array
    ``[W, B, ...]`` (``dense_batch`` unpacks one worker's on the device).  Every worker starts from ``p0`` (target ``p0``, zero
    moments), takes ``len(batches)`` Adam steps on its own rows, and then
    (``sync``) parameters and both moments are averaged over the workers.

    Returns per-update mean losses ``[U]``, the first clipped gradient's
    norm per worker and leaf ``[W, L]``, and the parameter change's norm
    per worker and leaf after the learner call ``[W, L]``."""
    W = batches[0]["state_bits"].shape[0]
    disc, lr = hp["discount"], hp["lr"]
    b1, b2, eps, clip = hp["b1"], hp["b2"], hp["eps"], hp["clip"]

    def leaf_norms(tree):
        return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x)))
                          for x in jax.tree_util.tree_leaves(tree)])

    @jax.jit
    def one_worker(p, m, v, tp, packed, step):
        b = dense_batch(packed)
        loss, g = jax.value_and_grad(dqn_loss)(p, tp, b, disc, mode, rows)
        p, m, v, gc = adam_step(p, m, v, g, step, lr, b1, b2, eps, clip)
        return p, m, v, loss, leaf_norms(gc)

    zeros = jax.tree_util.tree_map(jnp.zeros_like, p0)
    state = [(p0, zeros, zeros) for _ in range(W)]
    losses, g1 = [], None
    for u, batch in enumerate(batches):
        step_losses, norms = [], []
        for w in range(W):
            b = {k: jnp.asarray(x[w]) for k, x in batch.items()}
            p, m, v, loss, gn = one_worker(*state[w], p0, b, u + 1)
            state[w] = (p, m, v)
            step_losses.append(loss)
            norms.append(gn)
        losses.append(float(np.mean(np.asarray(jnp.stack(step_losses)),
                                    dtype=np.float32)))
        if u == 0:
            g1 = np.asarray(jnp.stack(norms))
    params = [s[0] for s in state]
    if sync:
        mean = jax.tree_util.tree_map(
            lambda *xs: jnp.sum(jnp.stack(xs), axis=0) / W, *params)
        params = [mean] * W
    change = np.stack([np.asarray(leaf_norms(
        jax.tree_util.tree_map(lambda a, b: a - b, p, p0))) for p in params])
    return {"losses": np.asarray(losses), "grad_norms": g1,
            "change_norms": change}
