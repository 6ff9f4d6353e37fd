"""Device mesh context for distributed training.

The reference's cluster layer (rabit tracker rendezvous + rank/world,
``subtree/rabit/tracker/rabit_tracker.py:125-309``) collapses to a
``jax.sharding.Mesh``: the JAX runtime owns rendezvous and the mesh
axis name is the communicator.  The flagship mode is row-split data
parallelism over axis ``"data"`` (SURVEY.md §2.4 item 2 → psum over ICI).

Multi-host: build the mesh over ``jax.devices()`` after
``jax.distributed.initialize()`` — same code path, collectives ride
ICI within a slice and DCN across slices.
"""

from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import Mesh

DATA_AXIS = "data"

_default_mesh: Optional[Mesh] = None


def mesh_available(min_devices: int = 2) -> bool:
    """True when THIS process sees at least ``min_devices`` devices (the
    test skipif gate: tests/conftest.py forces 8 virtual CPU devices)."""
    return len(jax.devices()) >= min_devices


def set_mesh(mesh: Optional[Mesh]) -> None:
    """Install a process-wide default mesh for dsplit=row training."""
    global _default_mesh
    _default_mesh = mesh


def get_mesh() -> Optional[Mesh]:
    return _default_mesh


def data_parallel_mesh(n_devices: Optional[int] = None) -> Mesh:
    """1-D mesh over the first n (default all) devices, axis 'data'.

    Auto axis types: tree traversal gathers (replicated node tables,
    row-sharded indices) rely on GSPMD propagation, which Explicit mode
    rejects as ambiguous.
    """
    devs = jax.devices()
    if n_devices is not None:
        devs = devs[:n_devices]
    return make_mesh((len(devs),), (DATA_AXIS,), devices=devs)


def make_mesh(shape, names, devices=None) -> Mesh:
    """``jax.make_mesh`` with Auto axis types (see
    :func:`data_parallel_mesh` for why not Explicit)."""
    return jax.make_mesh(
        tuple(shape), tuple(names), devices=devices,
        axis_types=tuple(jax.sharding.AxisType.Auto for _ in names))
