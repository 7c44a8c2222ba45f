"""Fused DQN Q-network evaluation (Pallas TPU).

The paper's per-step hot loop (§3.1): every environment step evaluates
Q over ~10^2 candidate-action fingerprints per molecule x the worker's
modification batch — thousands of rows through the MolDQN MLP
(2049 -> 1024 -> 512 -> 128 -> 32 -> 1).  The XLA path launches 5 matmul
kernels with HBM round-trips for each intermediate; this kernel keeps ALL
weights plus one row-block resident in VMEM and fuses the whole forward:

Block shapes follow the TPU's (8, 128) tiling: biases are ``[1, H]`` rows,
and the output is a lane-dense ``[1, N]`` row written in ``(1, row_block)``
tiles (the last layer is computed as ``w5^T @ h^T``).  Every matmul asks
for ``Precision.HIGHEST`` (float32 on the MXU).

  VMEM budget (f32, paper widths), every input block double-buffered by
  the Pallas pipeline:
      weights  W1 (2049 rows) 8.0 MiB + W2 2.0 MiB + W3 0.25 MiB
               + W4/W5/biases (lane-padded) < 0.2 MiB    ~= 10.3 MiB
      x2 (double buffering)                              ~= 20.6 MiB
      x block (128 x 2049, lane-padded to 2176) 1.06 MiB x2 ~= 2.1 MiB
      intermediates (h1 0.5 MiB, h2..h4)                 <  1 MiB
                                                         ~= 24 MiB
  which is over v5e's 16 MiB default scoped-VMEM limit, so the kernel
  raises it to ``VMEM_LIMIT_BYTES`` (v5e has 128 MiB of VMEM per core).

Grid = (row blocks,): one pass over HBM for x, one output write.  Row
blocks of 128 keep the MXU M-dim aligned.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ROW_BLOCK = 128
VMEM_LIMIT_BYTES = 48 * 2**20


def _mm(a, b):
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               precision=jax.lax.Precision.HIGHEST)


def _qnet_kernel(x_ref, w1, b1, w2, b2, w3, b3, w4, b4, w5t, b5, out_ref):
    h = x_ref[...].astype(jnp.float32)
    h = jnp.maximum(_mm(h, w1[...]) + b1[...], 0.0)
    h = jnp.maximum(_mm(h, w2[...]) + b2[...], 0.0)
    h = jnp.maximum(_mm(h, w3[...]) + b3[...], 0.0)
    h = jnp.maximum(_mm(h, w4[...]) + b4[...], 0.0)
    # [1, H4] x [rows, H4]^T -> lane-dense [1, rows]
    q = jax.lax.dot_general(w5t[...], h, (((1,), (1,)), ((), ())),
                            precision=jax.lax.Precision.HIGHEST) + b5[...]
    out_ref[...] = q.astype(out_ref.dtype)


def fused_qnet_rows(
    x: jnp.ndarray,            # [N, in_dim]
    weights: list[tuple[jnp.ndarray, jnp.ndarray]],   # [(w, b [1, out])] x5,
                               # the last w transposed to [1, H4]
    *,
    row_block: int = ROW_BLOCK,
    interpret: bool = False,
) -> jnp.ndarray:
    """-> q ``[1, N]``."""
    N, in_dim = x.shape
    assert len(weights) == 5, "fused kernel is specialised to the MolDQN 5-layer MLP"
    row_block = min(row_block, N)
    assert N % row_block == 0, f"rows {N} % block {row_block}"
    grid = (N // row_block,)

    full = lambda w: pl.BlockSpec(w.shape, lambda i: (0,) * w.ndim)
    in_specs = [pl.BlockSpec((row_block, in_dim), lambda i: (i, 0))]
    flat_w = []
    for w, b in weights:
        in_specs += [full(w), full(b)]
        flat_w += [w, b]

    return pl.pallas_call(
        _qnet_kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, row_block), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, N), x.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="fused_qnet_rows",
    )(x, *flat_w)
