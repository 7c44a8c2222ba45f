"""jit'd wrapper: adapts QNetwork param pytrees + pads row counts."""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.fused_qnet.fused_qnet import ROW_BLOCK, fused_qnet_rows


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@partial(jax.jit, static_argnames=("interpret",))
def fused_qnet(params: dict, x: jnp.ndarray, *, interpret: bool | None = None) -> jnp.ndarray:
    """params: QNetwork pytree ({"layers": [{"w","b"}, ...x5]}); x [N, 2049]."""
    interpret = (not _on_tpu()) if interpret is None else interpret
    weights = [(l["w"], l["b"][None, :]) for l in params["layers"]]
    weights[-1] = (weights[-1][0].T, weights[-1][1])
    n = x.shape[0]
    padded = ((n + ROW_BLOCK - 1) // ROW_BLOCK) * ROW_BLOCK
    if padded != n:
        x = jnp.concatenate([x, jnp.zeros((padded - n, x.shape[1]), x.dtype)])
    q = fused_qnet_rows(x, weights, interpret=interpret)
    return q[0, :n]
