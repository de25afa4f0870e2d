"""Device-mesh construction and management.

TPU-native replacement for the reference's communicator plumbing
(``pylops_mpi/utils/_mpi.py``, ``utils/_nccl.py``, and the
``DistributedMixIn`` dispatch in ``pylops_mpi/Distributed.py:24-349``):
instead of per-rank MPI/NCCL communicators, a single controller process
drives a :class:`jax.sharding.Mesh` over the TPU slice, and all
collectives are XLA ops (``psum``/``all_gather``/``all_to_all``/
``ppermute``) emitted either implicitly by the partitioner or explicitly
inside ``shard_map``.

Sub-communicators (``MPI.Comm.Split`` / ``nccl_split``,
ref ``pylops_mpi/DistributedArray.py:74-100``) map to named mesh axes or
``axis_index_groups`` — see :mod:`pylops_mpi_tpu.parallel.collectives`.
"""

from __future__ import annotations

import os
from typing import Optional, Sequence, Tuple

import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "make_mesh",
    "make_mesh_2d",
    "make_mesh_hybrid",
    "initialize_multihost",
    "default_mesh",
    "set_default_mesh",
    "local_device_count",
    "best_grid_2d",
    "stack_sharded",
    "concat_sharded",
]

# The default axis name for 1-D sharding ("shard-parallel"); mirrors the
# single flat COMM_WORLD of the reference.
SP_AXIS = "sp"

_DEFAULT_MESH: Optional[Mesh] = None


def local_device_count() -> int:
    return len(jax.devices())


def make_mesh(n_devices: Optional[int] = None, axis_name: str = SP_AXIS) -> Mesh:
    """Build a 1-D device mesh over the first ``n_devices`` devices.

    Equivalent role to ``MPI.COMM_WORLD`` in the reference: every
    DistributedArray / operator is laid out over one of these.
    """
    devs = jax.devices()
    if n_devices is None:
        n_devices = len(devs)
    if n_devices > len(devs):
        raise ValueError(
            f"requested {n_devices} devices but only {len(devs)} available")
    return Mesh(np.asarray(devs[:n_devices]), (axis_name,))


def best_grid_2d(n: int) -> Tuple[int, int]:
    """Largest (pr, pc) grid with pr*pc == n and pr as close to sqrt(n).

    TPU-native analog of the reference's ``active_grid_comm``
    (``pylops_mpi/basicoperators/MatrixMult.py:24-79``), which drops ranks
    to get a square grid: on a mesh we instead factor the device count so
    no device idles.
    """
    pr = int(np.sqrt(n))
    while n % pr != 0:
        pr -= 1
    return pr, n // pr


def make_mesh_2d(
    n_devices: Optional[int] = None,
    axis_names: Tuple[str, str] = ("r", "c"),
    grid: Optional[Tuple[int, int]] = None,
) -> Mesh:
    """Build a 2-D device mesh (process grid) for SUMMA-style matmuls.

    Replaces the reference's row/column sub-communicators
    (``pylops_mpi/basicoperators/MatrixMult.py:305-314,549-608``).
    """
    devs = jax.devices()
    if n_devices is None:
        n_devices = len(devs)
    if grid is None:
        grid = best_grid_2d(n_devices)
    pr, pc = grid
    if pr * pc != n_devices:
        raise ValueError(f"grid {grid} does not tile {n_devices} devices")
    return Mesh(np.asarray(devs[:n_devices]).reshape(pr, pc), axis_names)


def initialize_multihost(coordinator_address: Optional[str] = None,
                         num_processes: Optional[int] = None,
                         process_id: Optional[int] = None,
                         retries: Optional[int] = None,
                         backoff_s: Optional[float] = None) -> None:
    """Join a multi-host TPU job (DCN-connected slices / pods).

    The analog of the reference's ``mpiexec -n P`` bootstrap + NCCL
    unique-id handshake (``pylops_mpi/utils/_nccl.py:98-132``): each host
    calls this once before building meshes; afterwards ``jax.devices()``
    spans every host and all collectives ride ICI within a slice and DCN
    across slices. Arguments default to the standard cluster env vars
    (``jax.distributed.initialize`` auto-detection on TPU pods).

    Bring-up is the flakiest moment of a pod job — the coordinator may
    not be listening yet, a preempted peer may rejoin late — so the
    init runs under the bounded retry/backoff of
    :func:`pylops_mpi_tpu.resilience.retry.retry_call`
    (``PYLOPS_MPI_TPU_RETRIES`` / ``PYLOPS_MPI_TPU_RETRY_BACKOFF``;
    per-call ``retries=``/``backoff_s=`` override). The final failure
    propagates unchanged.

    It is also the canonical place to block FOREVER: ``initialize``
    waits for every peer, so one dead host hangs the rest past any
    retry. Under supervision (or ``PYLOPS_MPI_TPU_WATCHDOG=on``) the
    whole retried bring-up therefore runs under the collective
    watchdog (stage ``multihost_init`` of the central
    ``STAGE_BUDGETS`` table) and raises
    :class:`~pylops_mpi_tpu.resilience.elastic.WatchdogTimeout` at the
    deadline — the worker exits, the supervisor reclassifies and
    relaunches on the surviving hosts. Unsupervised processes see a
    plain direct call, bit-identical to before."""
    import jax.distributed
    from ..resilience.elastic import watched_call
    from ..resilience.retry import retry_call
    watched_call(retry_call, jax.distributed.initialize,
                 coordinator_address=coordinator_address,
                 num_processes=num_processes,
                 process_id=process_id,
                 retries=retries, backoff_s=backoff_s,
                 describe="jax.distributed.initialize",
                 stage="multihost_init")


def make_mesh_hybrid(ici_axis: str = SP_AXIS, dcn_axis: str = "dcn",
                     dcn_size: Optional[int] = None) -> Mesh:
    """2-level mesh for multi-slice jobs: the inner axis maps to ICI
    (fast, within a slice), the outer to DCN (across slices).

    Shard the long/data axis over ``dcn_axis`` and the compute-heavy
    axis over ``ici_axis`` so the frequent collectives (halo ppermute,
    SUMMA bcast, dot psum) stay on ICI — the scaling-book layout recipe.
    Falls back to a 1-level mesh when there is a single process."""
    nproc = jax.process_count()
    if dcn_size is None:
        dcn_size = nproc
    devs = jax.devices()
    dcn_size = int(dcn_size)
    if dcn_size > 1 and len(devs) % dcn_size:
        divisors = [d for d in range(1, len(devs) + 1)
                    if len(devs) % d == 0]
        raise ValueError(
            f"make_mesh_hybrid: dcn_size={dcn_size} does not divide the "
            f"device count {len(devs)}; every slice must hold the same "
            f"number of devices. Valid dcn_size values here: {divisors}")
    if dcn_size <= 1:
        return Mesh(np.asarray(devs).reshape(1, -1), (dcn_axis, ici_axis))
    try:
        from jax.experimental import mesh_utils
        arr = mesh_utils.create_hybrid_device_mesh(
            (1, len(devs) // dcn_size), (dcn_size, 1), devices=devs)
        arr = arr.reshape(dcn_size, -1)
    except Exception:  # non-TPU topologies: plain contiguous split
        arr = np.asarray(devs).reshape(dcn_size, -1)
    return Mesh(arr, (dcn_axis, ici_axis))


def default_mesh() -> Mesh:
    """Process-wide default mesh (created lazily over all devices)."""
    global _DEFAULT_MESH
    if _DEFAULT_MESH is None:
        _DEFAULT_MESH = make_mesh()
    return _DEFAULT_MESH


def set_default_mesh(mesh: Optional[Mesh]) -> None:
    global _DEFAULT_MESH
    _DEFAULT_MESH = mesh


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def axis_sharding(mesh: Mesh, ndim: int, axis: int,
                  axis_name: Optional[str] = None) -> NamedSharding:
    """NamedSharding that shards dimension ``axis`` of an ``ndim`` array
    over ``axis_name``. Default: the mesh's single axis, or — on a
    multi-level mesh (e.g. ``make_mesh_hybrid``'s dcn×ici) — the product
    of ALL mesh axes in outer-to-inner order, so one logical shard axis
    spans every device and the device-order block layout matches the
    1-D case."""
    if axis_name is None:
        axis_name = mesh.axis_names[0] if len(mesh.axis_names) == 1 \
            else tuple(mesh.axis_names)
    spec = [None] * ndim
    spec[axis] = axis_name
    return NamedSharding(mesh, P(*spec))


def stack_sharded(mats: Sequence, mesh: Mesh, dtype=None) -> jax.Array:
    """``stack(mats)`` as one ``(nblk, m, n)`` array block-sharded over
    ``mesh`` (``len(mats)`` divisible by the device count), stored at
    ``dtype`` when given.

    Host (NumPy) blocks are stacked per shard and sent straight to the
    device that owns them: no device ever holds more than its own
    share, and nothing passes through device 0 — a deployment-sized
    operator (gigabytes per chip) does not fit twice. Device blocks
    are stacked where they are and resharded."""
    sharding = axis_sharding(mesh, 3, 0)
    if not all(isinstance(m, np.ndarray) for m in mats):
        import jax.numpy as jnp
        A = jnp.stack([jnp.asarray(m) for m in mats])
        return jax.device_put(A if dtype is None else A.astype(dtype),
                              sharding)
    if dtype is None:
        dtype = np.result_type(*{m.dtype for m in mats})
    dtype = jax.dtypes.canonicalize_dtype(dtype)
    mats = list(mats)
    shape = (len(mats),) + tuple(mats[0].shape)
    shards = []
    for dev, idx in sharding.addressable_devices_indices_map(shape).items():
        part = np.stack(mats[idx[0]], dtype=dtype, casting="unsafe")
        shards.append(jax.device_put(part, dev))
    return jax.make_array_from_single_device_arrays(shape, sharding, shards)


def concat_sharded(parts: Sequence, mesh: Mesh) -> jax.Array:
    """``concatenate(parts)`` along their leading axis (a 0-d part counts
    as one element) as one array sharded over ``mesh`` along it, each
    device's shard made of the contiguous parts it owns (``len(parts)``
    divisible by the device count, every part of one shape).

    A part that is alone on its device and already lies there IS that
    device's shard: no copy is made, so blocks made where they are to
    lie (gigabytes of tables a chip) are assembled in place. Any other
    shard is concatenated on its own device."""
    import jax.numpy as jnp
    k = len(parts) // int(mesh.devices.size)
    lead = tuple(np.shape(parts[0])) or (1,)
    shape = (len(parts) * lead[0],) + lead[1:]
    sharding = axis_sharding(mesh, len(shape), 0)
    shards = []
    for dev, idx in sharding.addressable_devices_indices_map(shape).items():
        c = (idx[0].start or 0) // (k * lead[0])
        own = parts[c * k:(c + 1) * k]
        if k == 1 and isinstance(own[0], jax.Array) \
                and own[0].shape == lead and own[0].devices() == {dev}:
            shards.append(own[0])
        else:
            shards.append(jnp.concatenate([
                jnp.reshape(jax.device_put(p, dev), lead) for p in own]))
    return jax.make_array_from_single_device_arrays(shape, sharding, shards)
