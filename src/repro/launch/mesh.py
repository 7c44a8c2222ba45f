"""Production mesh construction.

Kept as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — the dry-run must set XLA_FLAGS before any
device query, and smoke tests must keep seeing 1 CPU device.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """TPU-v5e production mesh: 16x16 = 256 chips per pod ("data","model"),
    or 2 pods = 512 chips ("pod","data","model")."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_host_mesh(nd: int | None = None) -> Mesh:
    """The first ``nd`` locally-visible devices (default: all of them) on a
    single "data" axis (RL trainer).

    The ONE mesh-construction code path for single-axis data-parallel
    training: ``DistributedTrainer`` defaults to this, and the multi-device
    verification suite (``repro.launch.verify``) sizes it with ``nd``.

    ``nd`` selects a SUBMESH over the first nd visible devices.  The
    verification suite relies on this: XLA-CPU's kernel/threading choices
    depend on the *client's* device count (a plain single-device matmul
    changes its last bits between a 1-device and a 4-device client), so
    cross-nd bit-equality is only meaningful when every scenario runs in an
    identically-configured client — fixed forced device pool, varying
    submesh — rather than one client per device count.

    The mesh's axis is of JAX's default ``Auto`` type (``jax.make_mesh``
    would make it ``Explicit``, under which the stacked parameters' vmap
    refuses the unplaced per-call operands of the per-worker path).
    """
    devices = jax.devices()
    nd = len(devices) if nd is None else nd
    if nd <= 0 or nd > len(devices):
        raise ValueError(f"nd={nd} outside [1, {len(devices)}] visible devices")
    return Mesh(np.asarray(devices[:nd]), ("data",))


def padded_worker_count(n_workers: int, mesh: Mesh) -> int:
    """Smallest worker count >= ``n_workers`` that tiles the mesh evenly.

    A fleet whose worker count does not divide the device count is padded
    to this size with DEAD worker slots (no molecules, zero dense rows,
    masked out of every cross-worker mean) instead of erroring — see
    ``DistributedTrainer``.
    """
    if n_workers <= 0:
        raise ValueError(f"n_workers must be positive, got {n_workers}")
    nd = mesh.devices.size
    return -(-n_workers // nd) * nd


def fleet_sharding(mesh: Mesh) -> NamedSharding:
    """Placement for fleet-stacked values (the ``[W, ...]`` parameter tree
    and the ``[W, C, D]`` acting batch): leading worker axis split over
    "data", everything else replicated."""
    return NamedSharding(mesh, P("data"))


def batch_axes(mesh: Mesh) -> tuple[str, ...]:
    """The data-parallel axes of a mesh (everything except "model")."""
    return tuple(a for a in mesh.axis_names if a != "model")


def mesh_tp(mesh: Mesh) -> int:
    return mesh.shape["model"] if "model" in mesh.axis_names else 1
