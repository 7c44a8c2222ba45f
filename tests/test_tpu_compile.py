"""Compile the Q-network kernels and the packed fleet dispatch for one
described (not attached) TPU v5e chip, at the paper's widths.

Nothing runs: the TPU compiler that ships with jaxlib lowers and compiles
each program against a ``v5e:2x2`` topology description, which refuses
what interpret mode accepts — block shapes off the (8, 128) tiling, more
scoped VMEM than the kernel asked for, layouts Mosaic and XLA disagree on.
The topology is described inside a module fixture (never at import time)
so that only the test worker that runs this file loads the TPU library.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, \
    SingleDeviceSharding

from repro.chem.fingerprint import FP_BITS
from repro.core.agent import HIDDEN_SIZES, STATE_DIM, QNetwork
from repro.core.distributed import fleet_q_dispatches

FP_BYTES = FP_BITS // 8
H1, H2, H3, H4 = HIDDEN_SIZES


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "not here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled.as_text()


def _tail_shapes(lead, sharding):
    """Layers 2..5 as the kernels take them: ``[.., in, out]`` weights,
    ``[.., 1, out]`` biases, the last weight transposed to ``[.., 1, H4]``."""
    layers = [(H1, H2), (H2, H3), (H3, H4)]
    tail = [(_spec(lead + (i, o), jnp.float32, sharding),
             _spec(lead + (1, o), jnp.float32, sharding)) for i, o in layers]
    tail.append((_spec(lead + (1, H4), jnp.float32, sharding),
                 _spec(lead + (1, 1), jnp.float32, sharding)))
    return tail


@pytest.mark.parametrize("n_workers", [64, 512])
def test_packed_qnet_stacked_rows_compiles_for_v5e(one_chip, n_workers):
    from repro.kernels.packed_qnet.packed_qnet import packed_qnet_stacked_rows
    W, C = n_workers, 128
    s = one_chip
    args = (_spec((W, C, FP_BYTES), jnp.uint8, s),
            _spec((W, C, 1), jnp.float32, s),
            _spec((W, 8, FP_BYTES, H1), jnp.float32, s),
            _spec((W, 1, H1), jnp.float32, s),
            _spec((W, 1, H1), jnp.float32, s),
            _tail_shapes((W,), s))
    hlo = _compile(packed_qnet_stacked_rows, *args)
    assert "tpu_custom_call" in hlo


def test_packed_qnet_rows_compiles_for_v5e(one_chip):
    from repro.kernels.packed_qnet.packed_qnet import packed_qnet_rows
    N, s = 512, one_chip
    args = (_spec((N, FP_BYTES), jnp.uint8, s),
            _spec((N, 1), jnp.float32, s),
            _spec((8, FP_BYTES, H1), jnp.float32, s),
            _spec((1, H1), jnp.float32, s),
            _spec((1, H1), jnp.float32, s),
            _tail_shapes((), s))
    hlo = _compile(packed_qnet_rows, *args)
    assert "tpu_custom_call" in hlo


def test_fused_qnet_rows_compiles_for_v5e(one_chip):
    from repro.kernels.fused_qnet.fused_qnet import fused_qnet_rows
    N, s = 512, one_chip
    weights = [(_spec((STATE_DIM, H1), jnp.float32, s),
                _spec((1, H1), jnp.float32, s))] + _tail_shapes((), s)
    hlo = _compile(fused_qnet_rows, _spec((N, STATE_DIM), jnp.float32, s),
                   weights)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("use_pallas_qnet", [False, True])
def test_packed_fleet_dispatch_compiles_for_v5e(topo, use_pallas_qnet):
    """The trainer's packed fleet-Q dispatch at W=64, C=128 over a
    one-chip mesh, from ``QNetwork()`` shapes: the default XLA path and
    the Pallas kernel path (which must lower to the kernel)."""
    W, C = 64, 128
    net = QNetwork()
    mesh = Mesh(np.asarray(topo.devices[:1]), ("data",))
    fleet = NamedSharding(mesh, P("data"))
    params = jax.eval_shape(jax.vmap(net.init),
                            jax.ShapeDtypeStruct((W, 2), jnp.uint32))
    params = jax.tree_util.tree_map(
        lambda x: _spec(x.shape, x.dtype, fleet), params)
    _, packed = fleet_q_dispatches(net, mesh, use_pallas_qnet=use_pallas_qnet)
    compiled = packed.lower(params, _spec((W, C, FP_BYTES), jnp.uint8, fleet),
                            _spec((W, C), jnp.float32, fleet)).compile()
    assert compiled.output_shardings.spec == P("data")
    assert ("tpu_custom_call" in compiled.as_text()) == use_pallas_qnet
