"""Device mesh helpers for the 1D vertex-parallel layout.

The reference's process topology is flat: k MPI ranks / k torch.distributed
workers, one graph part each (``Parallel-GCN/main.c:101-103``,
``GPU/PGCN.py:241-253``).  The TPU-native equivalent is a 1D
``jax.sharding.Mesh`` over the chips with a single ``'v'`` (vertex) axis;
per-chip arrays are stacked along a leading k axis and sharded with
``PartitionSpec('v')``, replicated arrays use ``PartitionSpec()``.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AXIS = "v"


def make_mesh_1d(k: int, devices=None) -> Mesh:
    devices = devices if devices is not None else jax.devices()
    if len(devices) < k:
        raise ValueError(f"need {k} devices, have {len(devices)}")
    return Mesh(list(devices[:k]), (AXIS,))


def local_chip_slice(mesh: Mesh) -> slice:
    """Positions along the stacked k axis owned by THIS process.

    ``jax.devices()`` orders chips process-contiguously, so a process's
    chips form one contiguous run of the 1D mesh; verified here because
    ``make_array_from_process_local_data`` needs the local chunk to be
    exactly that run.
    """
    pid = jax.process_index()
    mine = [i for i, d in enumerate(mesh.devices.flat)
            if d.process_index == pid]
    if not mine:
        return slice(0, 0)
    if mine != list(range(mine[0], mine[-1] + 1)):
        raise ValueError(f"process {pid}'s mesh positions are not "
                         f"contiguous: {mine}")
    return slice(mine[0], mine[-1] + 1)


def shard_stacked(mesh: Mesh, tree):
    """Place a pytree of (k, ...)-stacked arrays with the leading axis sharded.

    Single-process: plain ``device_put``.  Multi-process (every process
    holding the full stacked array, e.g. the plan arrays every host builds
    identically): the SUPPORTED path is
    ``jax.make_array_from_process_local_data`` fed each process's slice of
    the leading axis — ``device_put`` of a host-local array to a global
    sharding is not (the reference's analogous step is each rank reading its
    own ``H.r``/``A.r`` shard, ``Parallel-GCN/main.c:456-504``).
    """
    sh = NamedSharding(mesh, P(AXIS))
    if jax.process_count() == 1:
        return jax.tree.map(lambda x: jax.device_put(x, sh), tree)
    sl = local_chip_slice(mesh)

    def put(x):
        x = np.asarray(x)
        return jax.make_array_from_process_local_data(sh, x[sl], x.shape)

    return jax.tree.map(put, tree)


def replicate(mesh: Mesh, tree):
    """Replicate a pytree on every chip (params / optimizer state)."""
    sh = NamedSharding(mesh, P())
    if jax.process_count() == 1:
        return jax.tree.map(lambda x: jax.device_put(x, sh), tree)

    def put(x):
        x = np.asarray(x)
        return jax.make_array_from_process_local_data(sh, x, x.shape)

    return jax.tree.map(put, tree)


def vary(tree, axis_name: str = AXIS):
    """``tree`` with every leaf varying over ``axis_name`` (inside
    ``shard_map``): a replicated leaf is cast (``lax.pcast``), one that
    already varies is left alone (casting it again is an error).  A
    function differentiated with respect to the result returns PER-CHIP
    PARTIAL gradients, which the caller completes with one explicit
    ``psum``; differentiated with respect to the replicated leaf itself,
    the transposition of the cast has already summed them."""
    import jax

    return jax.tree.map(
        lambda x: x if axis_name in jax.typeof(x).vma
        else jax.lax.pcast(x, axis_name, to="varying"), tree)

