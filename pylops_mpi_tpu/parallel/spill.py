"""Host-RAM spill tier: double-buffered host staging (round 14).

The round-13 planner (:mod:`pylops_mpi_tpu.parallel.reshard`) refuses a
move whose scratch budget cannot fit even one chunk row — correct for a
planner that must never silently materialize a full gather, but a dead
end for the caller: an elastic shrink that concentrates a carry onto
fewer devices, or a destination that simply does not fit in HBM, has
nowhere to go. This module turns those refusals into slower-but-working
schedules by staging chunks through host RAM:

- a ``host_stage`` plan step (``plan_reshard`` with a resolved spill
  mode builds all-``host_stage`` plans): each chunk is carved on
  device, copied D2H into pinned-size host scratch, and either placed
  back H2D onto the destination devices or written straight into a
  host-resident destination buffer when the destination itself is
  over budget;
- :func:`run_spilled`, the double-buffered executor — under
  ``overlap="on"`` (the default) chunk ``k`` drains to the host buffer
  on a one-slot worker thread while the main thread carves chunk
  ``k+1``, so the D2H copy and the carve genuinely overlap (both sides
  release the GIL); ``overlap="off"`` serializes every chunk;
- :class:`HostArray`, a host-resident stand-in for
  :class:`~pylops_mpi_tpu.DistributedArray`: the logical (unpadded)
  value in host RAM plus the full layout metadata, so
  :func:`~pylops_mpi_tpu.parallel.reshard.reshard` and
  :meth:`to_device` can move it back when room frees up.

Mode comes from ``PYLOPS_MPI_TPU_SPILL`` (``utils/deps.spill_mode``):
``off`` keeps the round-13 refusal bit-identical, ``auto`` (default)
converts ONLY moves the device planner would refuse, ``on`` forces
host staging for every concrete cross-layout move. Traced moves never
spill — a ``device_get`` needs a concrete array — and the refusal
floor remains: a budget below one chunk row (``min_budget =
row_bytes``) still raises, because even the host path stages one row
at a time.

The chunk count comes from the budget (or the ``chunks`` kwarg) and
the overlap choice from the ``overlap`` kwarg (default ``"on"``).
H2D/D2H bytes are accounted per step in trace events and per move in
the metrics registry (``bytes_h2d`` / ``bytes_d2h`` next to the
ici/dcn split). The
:func:`~pylops_mpi_tpu.resilience.faults.maybe_kill_spill` seam fires
once per staged chunk so chaos tests can kill a worker mid-spill.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from typing import Optional, Sequence, Tuple

import numpy as np
import jax

from ..diagnostics import trace as _trace
from .mesh import replicated_sharding
from .partition import Partition, local_split
from . import topology as _topo
from . import reshard as _rs

__all__ = [
    "HostArray",
    "run_spilled",
    "to_host",
    "reshard_from_host",
]


class HostArray:
    """A distributed array's layout, parked in host RAM.

    Holds the LOGICAL (unpadded) global value as one numpy array plus
    the same layout metadata a :class:`~pylops_mpi_tpu.DistributedArray`
    carries (mesh, partition, axis, per-shard local shapes, mask) — the
    spill tier's destination when the target layout does not fit the
    device budget, and a valid *source* for
    :func:`~pylops_mpi_tpu.parallel.reshard.reshard` /
    :func:`to_device`. Host RAM is process-shared in this library's
    single-controller model, so a host→host relayout is metadata-only:
    the new :class:`HostArray` aliases the same value buffer.
    """

    def __init__(self, value, mesh, partition: Partition = Partition.SCATTER,
                 axis: int = 0, local_shapes=None, mask=None):
        value = np.asarray(value)
        global_shape = tuple(int(s) for s in value.shape)
        if partition not in Partition:
            raise ValueError(f"Should be one of {[p for p in Partition]}")
        axis = int(axis)
        if axis < 0:
            axis += len(global_shape)
        if partition == Partition.SCATTER and not (0 <= axis < len(global_shape)):
            raise IndexError(f"axis {axis} out of range for shape {global_shape}")
        self.value = value
        self.mesh = mesh
        self.n_shards = int(mesh.devices.size)
        self.partition = partition
        self.axis = axis
        if local_shapes is None:
            local_shapes = local_split(global_shape, self.n_shards,
                                       partition, axis)
        else:
            local_shapes = tuple(tuple(int(v) for v in np.atleast_1d(s))
                                 for s in local_shapes)
            if len(local_shapes) != self.n_shards:
                raise ValueError(f"need {self.n_shards} local shapes, "
                                 f"got {len(local_shapes)}")
            if partition == Partition.SCATTER:
                tot = sum(s[axis] for s in local_shapes)
                if tot != global_shape[axis]:
                    raise ValueError(f"local shapes sum to {tot} != "
                                     f"global dim {global_shape[axis]}")
        self.local_shapes = local_shapes
        if mask is not None:
            mask = tuple(mask)
            if len(mask) != self.n_shards:
                raise ValueError(f"mask must have {self.n_shards} entries")
        self.mask = mask

    @property
    def global_shape(self) -> Tuple[int, ...]:
        return tuple(self.value.shape)

    @property
    def dtype(self):
        return self.value.dtype

    @property
    def ndim(self) -> int:
        return self.value.ndim

    @property
    def nbytes(self) -> int:
        return int(self.value.nbytes)

    @property
    def _axis_sizes(self) -> Tuple[int, ...]:
        if self.partition != Partition.SCATTER:
            return ()
        return tuple(s[self.axis] for s in self.local_shapes)

    def asarray(self) -> np.ndarray:
        """The logical global value (a view, not a copy)."""
        return self.value

    def __array__(self, dtype=None):
        return np.asarray(self.value, dtype=dtype)

    def to_device(self, *, budget=_rs._UNSET, chunks: Optional[int] = None,
                  overlap: Optional[str] = None):
        """Stream this host-resident array back onto its mesh as a
        :class:`~pylops_mpi_tpu.DistributedArray`, chunk-at-a-time
        under the budget (the unspill)."""
        return reshard_from_host(self, budget=budget, chunks=chunks,
                                 overlap=overlap, host_dst=False)

    def __repr__(self) -> str:
        return (f"HostArray(shape={self.global_shape}, "
                f"dtype={self.dtype}, partition={self.partition.name}, "
                f"axis={self.axis}, n_shards={self.n_shards})")


def _resolve_overlap(overlap) -> str:
    """The ``overlap`` kwarg, else ``"on"``."""
    if overlap is None:
        return "on"
    s = str(overlap).strip().lower()
    if s in ("1", "true"):
        s = "on"
    if s in ("0", "false"):
        s = "off"
    if s not in ("on", "off"):
        raise ValueError(f"overlap={overlap!r}: expected 'on' or 'off'")
    return s


# ------------------------------------------------------------ executor

def _store_host(host_out: np.ndarray, piece, lo: int, hi: int,
                move_axis: int) -> None:
    sl = [slice(None)] * host_out.ndim
    sl[move_axis] = slice(lo, hi)
    host_out[tuple(sl)] = np.asarray(piece)


def run_spilled(plan, *, dst=None, host_out=None, src=None,
                host_value=None, overlap: Optional[str] = None):
    """Execute an all-``host_stage`` plan, chunk by chunk through host
    RAM. Exactly one of ``dst`` (a fresh
    :class:`~pylops_mpi_tpu.DistributedArray`) or ``host_out`` (a
    logical-shape numpy buffer) is the destination; the source is
    ``src`` (a device array or a :class:`HostArray`) or ``host_value``
    (a host-replicated numpy array).

    ``overlap="on"`` double-buffers the device→host direction: chunk
    ``k`` drains to the host buffer on a one-slot worker thread (the
    ``np.asarray`` D2H copy plus the host memcpy, both of which release
    the GIL) while the main thread carves chunk ``k+1`` — so the two
    memcpys genuinely overlap even when the backend executes dispatches
    inline. The modeled peak device scratch (``plan.cost_model()``) is
    one staging chunk; the one-slot drain holds at most two chunks in
    flight, which is the documented approximation of the spill cost
    model. ``overlap="off"`` blocks after every chunk — the serialized
    baseline.

    Both chaos seams (:func:`~pylops_mpi_tpu.resilience.faults.
    maybe_kill_reshard` and ``maybe_kill_spill``) fire once per staged
    chunk, before its transfer is dispatched."""
    from ..resilience import faults as _faults
    if isinstance(src, HostArray):
        if host_value is None:
            host_value = src.value
        src = None
    move = plan.move_axis
    ov = _resolve_overlap(overlap)

    def _seams_and_event(st):
        _faults.maybe_kill_reshard()
        _faults.maybe_kill_spill()
        _trace.event("collective.reshard.step", kind="host_stage",
                     lo=st.lo, hi=st.hi, nbytes=st.nbytes,
                     nbytes_h2d=st.nbytes_h2d, nbytes_d2h=st.nbytes_d2h,
                     scratch_bytes=st.scratch_bytes, overlap=ov)

    if host_out is not None:
        # ---- destination in host RAM (device/host → host)
        if ov == "off":
            for st in plan.steps:
                _seams_and_event(st)
                piece = _rs._carve(src, host_value, st.lo, st.hi, move)
                piece = jax.block_until_ready(piece)
                _store_host(host_out, piece, st.lo, st.hi, move)
            return host_out
        # one-slot drain thread: the main thread carves chunk k+1 and
        # pulls it D2H (``np.asarray`` releases the GIL for the copy)
        # while the worker memcpys chunk k into the destination buffer;
        # waiting on the previous future before handing over the next
        # chunk bounds the transient at two chunks in flight
        pool = ThreadPoolExecutor(max_workers=1)
        try:
            fut = None
            for st in plan.steps:
                _seams_and_event(st)
                # block_until_ready (not a bare np.asarray) so the wait
                # releases the GIL and the worker's memcpy proceeds
                piece = np.asarray(jax.block_until_ready(
                    _rs._carve(src, host_value, st.lo, st.hi, move)))
                if fut is not None:
                    fut.result()
                fut = pool.submit(_store_host, host_out, piece,
                                  st.lo, st.hi, move)
            if fut is not None:
                fut.result()
        finally:
            pool.shutdown(wait=True)
        return host_out

    # ---- destination on device (device/host → staged → device)
    out = dst._arr
    for st in plan.steps:
        _seams_and_event(st)
        piece = _rs._carve(src, host_value, st.lo, st.hi, move)
        if src is not None:
            # device source: stage the chunk through host RAM (the
            # D2H half of the spill; blocking by construction)
            piece = np.asarray(piece)
        piece = jax.device_put(piece, replicated_sharding(dst._mesh))
        out = _rs._place_piece(out, piece, st.lo, st.hi, dst, move)
        out = dst._place(out)   # re-pin so scratch stays chunk-bounded
        if ov == "off":
            out = jax.block_until_ready(out)
    return dst._place(out)


# ------------------------------------------------------- entry points

def to_host(x, *, budget=_rs._UNSET, chunks: Optional[int] = None,
            overlap: Optional[str] = None) -> HostArray:
    """Evacuate a :class:`~pylops_mpi_tpu.DistributedArray` to host
    RAM, chunk-at-a-time under the budget, preserving its layout
    metadata — the explicit spill. The inverse is
    :meth:`HostArray.to_device` (or a plain :func:`reshard` with the
    HostArray as source)."""
    if _rs._is_tracer(x._arr):
        raise ValueError("to_host: spilling to host RAM is a concrete "
                         "device_get and cannot run under a trace")
    lay = _rs._layout_of(x)
    plan = _rs.plan_reshard(x.global_shape, np.dtype(x.dtype).itemsize,
                            lay, lay, budget=budget, chunks=chunks,
                            slice_ids=_topo.slice_map(x.mesh),
                            spill="on", dst_host=True,
                            topo_key=_topo.topology_key(x.mesh))
    host_out = np.empty(x.global_shape, dtype=x.dtype)
    if plan.steps:
        _rs._span_and_run(plan, None, src=x, host_out=host_out,
                          overlap=overlap, op="to_host")
    return HostArray(host_out, x.mesh, x.partition, x.axis,
                     local_shapes=x.local_shapes, mask=x.mask)


def reshard_from_host(h: HostArray, *, mesh=None, partition=None,
                      axis=None, local_shapes=None, budget=_rs._UNSET,
                      chunks: Optional[int] = None,
                      spill: Optional[str] = None,
                      overlap: Optional[str] = None,
                      host_dst: Optional[bool] = None):
    """Move a :class:`HostArray` to a new layout. A device destination
    streams host→device chunks under the budget (the ``place_replica``
    path, spilled or not); a host destination — forced with
    ``host_dst=True`` or chosen automatically when a spilled plan's
    destination is over budget — is metadata-only, aliasing the same
    host value. Mask and zero-row refusals mirror :func:`reshard`."""
    from ..distributedarray import DistributedArray
    tgt_mesh = mesh if mesh is not None else h.mesh
    tgt_part = partition if partition is not None else h.partition
    tgt_axis = h.axis if axis is None else int(axis)
    n_new = int(tgt_mesh.devices.size)
    if h.mask is not None and n_new != h.n_shards:
        raise _rs.ReshardError(
            f"reshard: array carries a mask (per-shard group colors) and "
            f"the move changes the shard count {h.n_shards} -> {n_new}; "
            "drop the mask or re-derive it for the new world first", 0)
    dst_l, ax_n, lsh = _rs._dst_layout(h.global_shape, n_new, tgt_part,
                                       tgt_axis, local_shapes)
    plan = _rs.plan_reshard(h.global_shape, np.dtype(h.dtype).itemsize,
                            _rs.Layout.replicated(1), dst_l,
                            budget=budget, chunks=chunks,
                            slice_ids=_topo.slice_map(tgt_mesh),
                            spill=spill, src_host=True, dst_host=host_dst,
                            topo_key=_topo.topology_key(tgt_mesh))
    if plan.spilled and plan.host_dst:
        # host → host: relayout is metadata-only, the value aliases
        return HostArray(h.value, tgt_mesh, tgt_part, ax_n,
                         local_shapes=lsh, mask=h.mask)
    out = DistributedArray(h.global_shape, tgt_mesh, tgt_part, tgt_axis,
                           local_shapes=local_shapes, mask=h.mask,
                           dtype=h.dtype)
    out._arr = _rs._span_and_run(plan, out, host_value=h.value,
                                 overlap=overlap, op="reshard")
    return out
