"""The jax distributed API as this repo uses it.

Every caller in this repo builds meshes and shard_maps through the
wrappers here, so the settings they fix (no replication check, `Auto`
mesh axes) hold everywhere.

Nothing in this module touches jax device state at import time, so it is
safe to import before `force_host_device_count` (see `hostenv.py`).
"""
from __future__ import annotations

import contextlib
from typing import Callable

import jax
from jax.sharding import AxisType


def shard_map(f: Callable, *, mesh, in_specs, out_specs, check: bool = False):
    """`jax.shard_map` with the replication check (`check_vma`) off by
    default."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check)


def make_mesh(shape, axis_names):
    """`jax.make_mesh` with every axis `Auto`: the SPMD partitioner
    propagates shardings through the jitted programs. On jax 0.9 the
    default is `Explicit`, under which the sharded ingest's `select`
    refuses operands of differing shardings."""
    return jax.make_mesh(tuple(shape), tuple(axis_names),
                         axis_types=(AxisType.Auto,) * len(shape))


@contextlib.contextmanager
def use_mesh(mesh):
    """Ambient-mesh context manager (`jax.set_mesh`) that yields the
    mesh."""
    with jax.set_mesh(mesh):
        yield mesh
