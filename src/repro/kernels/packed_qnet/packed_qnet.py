"""Fused DQN Q-network evaluation from PACKED fingerprints (Pallas TPU).

``fused_qnet`` already keeps the whole MolDQN MLP resident in VMEM, but it
still reads a DENSE float32 ``[N, 2049]`` input from HBM — 8 KB per row for
what is fundamentally 2048 bits + one scalar.  The learner's replay batches
arrive bit-packed (``ReplayBuffer.sample_packed``), so this kernel consumes
them directly: uint8 ``[N, 256]`` bit planes + a ``[N, 1]`` steps-left
column, 32x less input HBM traffic per row.

Because the fingerprint input is binary, the first 2049->1024 layer is a
masked row-sum of W1: row n's pre-activation is the sum of the W1 rows whose
bit is set, plus ``frac * W1[2048]`` and the bias.  The kernel realises that
sum on the MXU WITHOUT materialising a dense [N, 2048] unpack: byte plane k
(bit k of every byte, an ``[N, 256]`` 0/1 matrix) multiplies the strided
weight slice ``W1[k::8]`` (prepacked as ``w1r[8, 256, 1024]`` by ops.py),
and the 8 bit-plane matmuls accumulate —

    h1 = sum_k bits_k @ w1r[k] + frac @ w1f + b1

which is algebraically the dense ``x @ W1`` with the 2048-term reduction
re-associated into 8 x 256 (hence the 1e-5 parity tolerance vs the dense
reference instead of bit equality).  Layers 2..5 are then fused exactly as
in ``fused_qnet``; the last layer is computed as ``w5^T @ h^T`` so the
output row block is lane-dense (``[1, rows]``).  Every matmul asks for
``Precision.HIGHEST`` (float32 on the MXU), so the kernel is held to the
float32 reference at 1e-5 on the chip as in interpret mode.

Block shapes follow the TPU's (8, 128) tiling: every bias is a 2-D
``[1, H]`` row (``[W, 1, H]`` stacked), and the output is ``[1, N]``
(``[W, 1, N]`` stacked) written in ``(1, row_block)`` tiles, so the last
two dims of every block are either (8, 128)-aligned or the full array dims.

  VMEM budget (f32, paper widths), every input block double-buffered by
  the Pallas pipeline:
      weights  w1r 8.0 MiB + W2 2.0 MiB + W3 0.25 MiB + W4/W5/biases
               (lane-padded) < 0.2 MiB                    ~= 10.4 MiB
      x2 (double buffering)                               ~= 20.9 MiB
      row blocks (bits 32 KiB, frac lane-padded 64 KiB, out) x2 < 0.3 MiB
      intermediates (h1 0.5 MiB, bit planes, h2..h4)      <  2 MiB
                                                          ~= 23 MiB
  (the v5e compiler allocates 20.6 MiB of scoped VMEM for the stacked
  kernel at W=64, C=128), over v5e's 16 MiB default scoped-VMEM limit, so
  the kernels raise it to ``VMEM_LIMIT_BYTES`` (v5e has 128 MiB of VMEM
  per core).

Grid = (row blocks,) for ``packed_qnet_rows`` and (workers, row blocks) for
``packed_qnet_stacked_rows``: one packed pass over HBM for x, one output
write.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ROW_BLOCK = 128
VMEM_LIMIT_BYTES = 48 * 2**20

_COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES)
_HIGHEST = jax.lax.Precision.HIGHEST


def _mm(a, b):
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               precision=_HIGHEST)


def _qnet_body(bits_u8, frac, w1r, w1f, b1, w2, b2, w3, b3, w4, b4, w5t, b5):
    """One row block through the MLP: ``bits_u8 [rows, 256]``, ``frac
    [rows, 1]`` -> q ``[1, rows]`` (lane-dense).  ``w5t`` is the last
    layer's ``[1, H4]`` transposed weight."""
    bytes32 = bits_u8.astype(jnp.int32)
    h = _mm(frac.astype(jnp.float32), w1f) + b1
    for k in range(8):                                   # np.unpackbits order:
        plane = ((bytes32 >> (7 - k)) & 1).astype(jnp.float32)  # bit k = MSB-k
        h = h + _mm(plane, w1r[k])
    h = jnp.maximum(h, 0.0)
    h = jnp.maximum(_mm(h, w2) + b2, 0.0)
    h = jnp.maximum(_mm(h, w3) + b3, 0.0)
    h = jnp.maximum(_mm(h, w4) + b4, 0.0)
    # [1, H4] x [rows, H4]^T -> [1, rows]
    q = jax.lax.dot_general(w5t, h, (((1,), (1,)), ((), ())),
                            precision=_HIGHEST)
    return q + b5


def _packed_qnet_kernel(bits_ref, frac_ref, w1r, w1f, b1,
                        w2, b2, w3, b3, w4, b4, w5t, b5, out_ref):
    out_ref[...] = _qnet_body(
        bits_ref[...], frac_ref[...], w1r[...], w1f[...], b1[...],
        w2[...], b2[...], w3[...], b3[...], w4[...], b4[...],
        w5t[...], b5[...])


def packed_qnet_rows(
    bits: jnp.ndarray,         # uint8 [N, FP_BITS/8]
    frac: jnp.ndarray,         # f32 [N, 1] steps-left feature column
    w1r: jnp.ndarray,          # f32 [8, FP_BITS/8, H1] bit-plane slices of W1
    w1f: jnp.ndarray,          # f32 [1, H1] the steps-left row of W1
    b1: jnp.ndarray,           # f32 [1, H1]
    tail: list[tuple[jnp.ndarray, jnp.ndarray]],  # [(w, b [1, out])] layers
                               # 2..5; the last w transposed to [1, H4]
    *,
    row_block: int = ROW_BLOCK,
    interpret: bool = False,
) -> jnp.ndarray:
    """-> q f32 ``[1, N]``."""
    N, n_bytes = bits.shape
    assert len(tail) == 4, "packed kernel is specialised to the MolDQN 5-layer MLP"
    row_block = min(row_block, N)
    assert N % row_block == 0, f"rows {N} % block {row_block}"
    grid = (N // row_block,)

    full = lambda w: pl.BlockSpec(w.shape, lambda i: (0,) * w.ndim)
    in_specs = [
        pl.BlockSpec((row_block, n_bytes), lambda i: (i, 0)),
        pl.BlockSpec((row_block, 1), lambda i: (i, 0)),
        full(w1r), full(w1f), full(b1),
    ]
    flat_w = [w1r, w1f, b1]
    for w, b in tail:
        in_specs += [full(w), full(b)]
        flat_w += [w, b]

    return pl.pallas_call(
        _packed_qnet_kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, row_block), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, N), jnp.float32),
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name="packed_qnet_rows",
    )(bits, frac, *flat_w)


def _packed_qnet_stacked_kernel(bits_ref, frac_ref, w1r, w1f, b1,
                                w2, b2, w3, b3, w4, b4, w5t, b5, out_ref):
    # one (worker, row-block) grid cell: every ref carries a leading
    # singleton worker axis — index it away and run the row kernel's math
    # under THIS worker's parameter slices
    out_ref[0] = _qnet_body(
        bits_ref[0], frac_ref[0], w1r[0], w1f[0], b1[0],
        w2[0], b2[0], w3[0], b3[0], w4[0], b4[0], w5t[0], b5[0])


def packed_qnet_stacked_rows(
    bits: jnp.ndarray,         # uint8 [W, C, FP_BITS/8]
    frac: jnp.ndarray,         # f32 [W, C, 1] steps-left feature column
    w1r: jnp.ndarray,          # f32 [W, 8, FP_BITS/8, H1] bit-plane W1 slices
    w1f: jnp.ndarray,          # f32 [W, 1, H1] the steps-left rows of W1
    b1: jnp.ndarray,           # f32 [W, 1, H1]
    tail: list[tuple[jnp.ndarray, jnp.ndarray]],  # [(w, b [W, 1, out])]
                               # layers 2..5, the last w as [W, 1, H4]
    *,
    row_block: int = ROW_BLOCK,
    interpret: bool = False,
) -> jnp.ndarray:
    """The fleet-acting shape: grid (W, row blocks).  Each cell evaluates
    one worker's candidate-row block under that worker's own parameter
    slices (per-worker parameter selection moves into the BlockSpec index
    maps — the kernel body is the per-worker row kernel unchanged).
    -> q f32 ``[W, 1, C]``."""
    n_workers, N, n_bytes = bits.shape
    assert len(tail) == 4, "packed kernel is specialised to the MolDQN 5-layer MLP"
    row_block = min(row_block, N)
    assert N % row_block == 0, f"rows {N} % block {row_block}"
    grid = (n_workers, N // row_block)

    per_w = lambda w: pl.BlockSpec((1,) + w.shape[1:],
                                   lambda wi, i, nd=w.ndim: (wi,) + (0,) * (nd - 1))
    in_specs = [
        pl.BlockSpec((1, row_block, n_bytes), lambda wi, i: (wi, i, 0)),
        pl.BlockSpec((1, row_block, 1), lambda wi, i: (wi, i, 0)),
        per_w(w1r), per_w(w1f), per_w(b1),
    ]
    flat_w = [w1r, w1f, b1]
    for w, b in tail:
        in_specs += [per_w(w), per_w(b)]
        flat_w += [w, b]

    return pl.pallas_call(
        _packed_qnet_stacked_kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, row_block), lambda wi, i: (wi, 0, i)),
        out_shape=jax.ShapeDtypeStruct((n_workers, 1, N), jnp.float32),
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name="packed_qnet_stacked_rows",
    )(bits, frac, *flat_w)
