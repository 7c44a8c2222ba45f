"""jit'd wrapper: adapts QNetwork param pytrees, packs W1 into bit-plane
slices, pads row counts, and picks the implementation:

* ``impl="pallas"`` — the fused bit-plane kernel (interpret mode off-TPU);
* ``impl="xla"``    — unpack-in-jit + dense forward (the portable default
                      everywhere but TPU);
* ``impl=None``     — pallas on TPU, xla otherwise.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.chem.fingerprint import FP_BITS
from repro.kernels.packed_qnet.packed_qnet import (
    ROW_BLOCK, packed_qnet_rows, packed_qnet_stacked_rows)
from repro.kernels.packed_qnet.ref import packed_qnet_ref


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def pack_w1(w1: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """W1 [FP_BITS+1, H1] -> (w1r [8, FP_BITS/8, H1], w1f [1, H1]).

    ``w1r[k, i] == w1[8*i + k]``: bit-plane k of byte i (np.unpackbits
    order, MSB first) multiplies exactly the weight rows its bits select."""
    wbits = w1[:FP_BITS].reshape(FP_BITS // 8, 8, -1).transpose(1, 0, 2)
    return wbits, w1[FP_BITS:]


def kernel_weights(params: dict):
    """QNetwork pytree -> the row kernel's operands ``(w1r, w1f, b1,
    tail)``: W1 packed into bit-plane slices, every bias a ``[1, H]`` row,
    and the last layer's weight transposed to ``[1, H4]`` (the kernel
    writes lane-dense ``[1, rows]`` output tiles)."""
    weights = [(l["w"], l["b"][None, :]) for l in params["layers"]]
    w1r, w1f = pack_w1(weights[0][0])
    tail = weights[1:-1] + [(weights[-1][0].T, weights[-1][1])]
    return w1r, w1f, weights[0][1], tail


@partial(jax.jit, static_argnames=("impl", "interpret"))
def packed_qnet(params: dict, bits: jnp.ndarray, frac: jnp.ndarray, *,
                impl: str | None = None, interpret: bool | None = None) -> jnp.ndarray:
    """params: QNetwork pytree ({"layers": [{"w","b"}, ...x5]});
    bits u8 [N, FP_BITS/8]; frac f32 [N] -> q f32 [N]."""
    if impl is None:
        impl = "pallas" if _on_tpu() else "xla"
    if impl == "xla":
        return packed_qnet_ref(
            bits, frac, [(l["w"], l["b"]) for l in params["layers"]])
    interpret = (not _on_tpu()) if interpret is None else interpret
    n = bits.shape[0]
    padded = ((n + ROW_BLOCK - 1) // ROW_BLOCK) * ROW_BLOCK
    if padded != n:
        bits = jnp.concatenate(
            [bits, jnp.zeros((padded - n, bits.shape[1]), bits.dtype)])
        frac = jnp.concatenate([frac, jnp.zeros((padded - n,), frac.dtype)])
    w1r, w1f, b1, tail = kernel_weights(params)
    q = packed_qnet_rows(bits, frac[:, None].astype(jnp.float32), w1r, w1f,
                         b1, tail, interpret=interpret)
    return q[0, :n]


@partial(jax.jit, static_argnames=("impl", "interpret"))
def packed_qnet_stacked(params: dict, bits: jnp.ndarray, frac: jnp.ndarray, *,
                        impl: str | None = None,
                        interpret: bool | None = None) -> jnp.ndarray:
    """The fleet-acting shape: params is a STACKED QNetwork pytree (leaves
    ``[W, ...]``, one tree per worker); bits u8 [W, C, FP_BITS/8]; frac f32
    [W, C] -> q f32 [W, C] — the packed twin of ``QNetwork.apply_stacked``."""
    if impl is None:
        impl = "pallas" if _on_tpu() else "xla"
    if impl == "xla":
        weights = [(l["w"], l["b"]) for l in params["layers"]]
        return jax.vmap(packed_qnet_ref, in_axes=(0, 0, 0))(bits, frac, weights)
    interpret = (not _on_tpu()) if interpret is None else interpret
    n = bits.shape[1]
    padded = max(((n + ROW_BLOCK - 1) // ROW_BLOCK) * ROW_BLOCK, ROW_BLOCK)
    if padded != n:
        pad = ((0, 0), (0, padded - n), (0, 0))
        bits = jnp.pad(bits, pad)
        frac = jnp.pad(frac, pad[:2])
    # per-worker kernel operands: w1r [W, 8, FP_BITS/8, H1], biases
    # [W, 1, H], last weight [W, 1, H4]
    w1r, w1f, b1, tail = jax.vmap(kernel_weights)(params)
    q = packed_qnet_stacked_rows(bits, frac[..., None].astype(jnp.float32),
                                 w1r, w1f, b1, tail, interpret=interpret)
    return q[:, 0, :n]
