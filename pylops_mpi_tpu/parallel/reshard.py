"""Bounded-memory resharding planner (round 13).

"Memory-efficient array redistribution through portable collective
communication" (arXiv 2112.01075) frames any layout change as a short
program of collective steps whose peak scratch is bounded by the chunk
size, not the array size. This module is that planner for the
library's :class:`~pylops_mpi_tpu.parallel.partition.Partition` model:
it decomposes an arbitrary Partition→Partition move — uneven (ragged)
shard splits, partition-axis regrids, mesh reshapes over the *same*
device set, and shrink/grow onto a *different* device count — into a
sequence of carve / exchange / place steps, streamed in chunks so the
peak scratch never exceeds ``PYLOPS_MPI_TPU_RESHARD_BUDGET``.

Three layers:

- :func:`plan_reshard` — pure host math. Builds a :class:`ReshardPlan`
  from the two :class:`Layout`\\ s: exact per-pair communication bytes
  from interval overlaps (same-axis moves) or the product measure
  (axis changes), an ici/dcn split per pair from
  :func:`~pylops_mpi_tpu.parallel.topology.slice_map`, and a chunk
  count that keeps ``peak_scratch <= budget``. A budget below
  ``min_budget`` (one row of scratch per live buffer) raises
  :class:`ReshardError` naming the minimum budget that would succeed —
  the planner refuses, it never silently materializes a full gather.
- the executor (:func:`reshard`, :func:`reshard_raw`,
  :func:`place_replica`) — runs a plan with static
  ``lax.slice_in_dim`` / ``lax.dynamic_update_slice_in_dim`` steps over
  the pad-to-max physical layout. Every index is known at plan time,
  so the same-device-set path is jit-safe (sharding constraints under
  trace, ``device_put`` when concrete); the cross-device-set path
  (shrink/grow, host replicas) transfers one chunk at a time.
- accounting — the whole move runs under a ``collective.reshard`` span
  with per-step ``collective.reshard.step`` events, bytes split
  ici/dcn when the mesh spans slices. The
  :func:`~pylops_mpi_tpu.resilience.faults.maybe_kill_reshard` seam
  fires between steps so chaos tests can kill a worker mid-plan.

The in-place elastic recovery path (``resilience/elastic.py``) is the
motivating consumer: a survivor holds the banked solver carry as host
replicas and replans it onto the shrunk mesh with
:func:`place_replica` — no checkpoint I/O on the recovery path.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Optional, Sequence, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh

from ..diagnostics import trace as _trace
from .mesh import replicated_sharding
from .partition import Partition, local_split, shard_offsets, unpad_index_map
from . import topology as _topo
from .collectives import _count_collective

__all__ = [
    "Layout",
    "ReshardStep",
    "ReshardPlan",
    "ReshardError",
    "reshard_budget",
    "plan_reshard",
    "reshard",
    "reshard_raw",
    "place_replica",
    "RESHARD_BUDGET_ENV",
]

RESHARD_BUDGET_ENV = "PYLOPS_MPI_TPU_RESHARD_BUDGET"

class _Unset:
    """Sentinel for "caller passed nothing" (``None`` means unbounded).

    A class with a stable repr — a bare ``object()`` would leak its
    memory address into the generated API signature and make
    ``docs/generate_api.py`` output non-deterministic."""

    def __repr__(self) -> str:
        return "<env>"


_UNSET = _Unset()


def reshard_budget() -> Optional[int]:
    """Scratch budget in bytes from ``PYLOPS_MPI_TPU_RESHARD_BUDGET``
    (plain int, or with a ``k``/``m``/``g`` binary suffix), or ``None``
    (unbounded — single-chunk plans) when unset/empty. Malformed values
    raise: a typo'd budget must not silently become "unbounded"."""
    raw = os.environ.get(RESHARD_BUDGET_ENV, "").strip().lower()
    if not raw:
        return None
    mult = 1
    if raw[-1] in "kmg":
        mult = {"k": 1 << 10, "m": 1 << 20, "g": 1 << 30}[raw[-1]]
        raw = raw[:-1]
    try:
        val = int(float(raw) * mult)
    except ValueError:
        raise ValueError(
            f"{RESHARD_BUDGET_ENV}={raw!r}: expected bytes as an integer "
            "with optional k/m/g suffix, e.g. '8m'") from None
    if val <= 0:
        raise ValueError(f"{RESHARD_BUDGET_ENV} must be positive, got {val}")
    return val


class ReshardError(ValueError):
    """The planner refuses a move: the budget cannot fit even one row
    of scratch. Carries ``min_budget`` — the smallest budget (bytes)
    under which the same move would succeed."""

    def __init__(self, msg: str, min_budget: int):
        super().__init__(msg)
        self.min_budget = int(min_budget)


@dataclass(frozen=True)
class Layout:
    """One side of a move: partition policy, shard axis, and the
    logical per-shard row counts along that axis (empty for
    replicated partitions)."""
    partition: Partition
    axis: int = 0
    sizes: Tuple[int, ...] = ()
    n_shards: int = 1

    @classmethod
    def scatter(cls, sizes: Sequence[int], axis: int = 0) -> "Layout":
        sizes = tuple(int(s) for s in sizes)
        return cls(Partition.SCATTER, int(axis), sizes, len(sizes))

    @classmethod
    def replicated(cls, n_shards: int,
                   partition: Partition = Partition.BROADCAST) -> "Layout":
        return cls(partition, 0, (), int(n_shards))

    @property
    def is_scatter(self) -> bool:
        return self.partition == Partition.SCATTER


@dataclass(frozen=True)
class ReshardStep:
    """One planner step: ``kind`` is the collective family
    (``dynamic_slice`` carve/place steps move no bytes between
    devices; ``host_stage`` steps of a spilled plan move bytes over
    PCIe instead — ``nbytes_h2d``/``nbytes_d2h``, round 14),
    ``nbytes``/``nbytes_ici``/``nbytes_dcn`` the exchanged payload,
    ``scratch_bytes`` the live device temporary the step holds."""
    kind: str
    chunk: int
    lo: int
    hi: int
    nbytes: int = 0
    nbytes_ici: Optional[int] = None
    nbytes_dcn: Optional[int] = None
    scratch_bytes: int = 0
    nbytes_h2d: int = 0
    nbytes_d2h: int = 0


@dataclass(frozen=True)
class ReshardPlan:
    """Host-side decomposition of one Partition→Partition move.

    A **spilled** plan (round 14) stages every chunk through host RAM:
    its steps are all ``host_stage``, its cross-device payload is zero
    (the bytes move over PCIe, ``nbytes_h2d``/``nbytes_d2h``), and
    ``host_dst`` marks a destination that stays in host RAM because it
    would not fit the device budget (``dst_device_bytes`` is the
    per-device footprint the destination would need)."""
    global_shape: Tuple[int, ...]
    itemsize: int
    src: Layout
    dst: Layout
    move_axis: int
    kind: str                      # exchange family, or "local"
    chunks: int
    steps: Tuple[ReshardStep, ...]
    nbytes: int                    # total cross-device payload
    nbytes_ici: Optional[int]      # split set when the mesh spans slices
    nbytes_dcn: Optional[int]
    peak_scratch: int
    min_budget: int
    budget: Optional[int]
    spilled: bool = False
    host_dst: bool = False
    nbytes_h2d: int = 0
    nbytes_d2h: int = 0
    dst_device_bytes: int = 0

    def cost_model(self) -> int:
        """Modeled peak *device* scratch in bytes: the largest live
        step temporary. For a spilled plan this is one staging chunk —
        the double-buffered executor's prefetch lives in host RAM, and
        the overlap transient (at most two chunks in flight) is the
        documented approximation."""
        return max((s.scratch_bytes for s in self.steps), default=0)


def _ceil_sizes(dim: int, n: int) -> Tuple[int, ...]:
    """GSPMD's implicit split of a (possibly non-divisible) dimension:
    ceil-sized shards, a short (possibly empty) tail."""
    s = -(-dim // n) if n else 0
    return tuple(max(0, min(s, dim - i * s)) for i in range(n))


def _pair_bytes(total: int, src: Layout, dst: Layout,
                move_axis: int, global_shape: Tuple[int, ...],
                itemsize: int) -> np.ndarray:
    """``B[i, j]``: bytes source shard ``i`` must deliver to
    destination shard ``j``. Shards are identified with linearized mesh
    ranks; the diagonal (data already resident, assuming rank identity
    across the move) is zeroed by the caller."""
    if not src.is_scatter:
        # replicated (or host) source: every destination already holds
        # — or receives locally — its piece; no cross-device payload.
        return np.zeros((max(src.n_shards, 1), max(dst.n_shards, 1)))
    held = np.asarray(src.sizes, dtype=np.float64)
    held *= (total / max(global_shape[src.axis], 1))
    if not dst.is_scatter:
        # all-gather: shard i's holding reaches every other device.
        return np.repeat(held[:, None], max(dst.n_shards, 1), axis=1)
    if src.axis == dst.axis:
        so = np.asarray(shard_offsets(src.sizes), dtype=np.int64)
        do = np.asarray(shard_offsets(dst.sizes), dtype=np.int64)
        s_lo, s_hi = so, so + np.asarray(src.sizes, dtype=np.int64)
        d_lo, d_hi = do, do + np.asarray(dst.sizes, dtype=np.int64)
        ov = (np.minimum(s_hi[:, None], d_hi[None, :])
              - np.maximum(s_lo[:, None], d_lo[None, :]))
        row_bytes = total / max(global_shape[move_axis], 1)
        return np.maximum(ov, 0).astype(np.float64) * row_bytes
    # axis change: shard i holds rows r_i/R of every column; shard j
    # wants cols c_j/C of every row — the product measure.
    r = np.asarray(src.sizes, dtype=np.float64) / max(global_shape[src.axis], 1)
    c = np.asarray(dst.sizes, dtype=np.float64) / max(global_shape[dst.axis], 1)
    return total * r[:, None] * c[None, :]


def plan_reshard(global_shape: Sequence[int], itemsize: int,
                 src: Layout, dst: Layout, *,
                 budget=_UNSET, chunks: Optional[int] = None,
                 slice_ids: Optional[Sequence[int]] = None,
                 spill: Optional[str] = None, src_host: bool = False,
                 dst_host: Optional[bool] = None,
                 topo_key: Optional[str] = None) -> ReshardPlan:
    """Plan one move. ``budget`` defaults to :func:`reshard_budget`
    (``None`` = unbounded); ``chunks`` forces at least that many
    chunks; ``slice_ids`` (per linearized rank, from
    :func:`~pylops_mpi_tpu.parallel.topology.slice_map`) drives the
    ici/dcn byte split. Raises :class:`ReshardError` when the budget
    cannot fit one row of scratch.

    Round 14: ``spill`` (default: ``PYLOPS_MPI_TPU_SPILL``) routes an
    over-budget move through host RAM instead of refusing — under
    ``"auto"`` ONLY a move the device planner would refuse spills, so
    every succeeding plan stays bit-identical; ``"on"`` forces a
    host-staged plan; ``"off"`` keeps the round-13 refusal. A spilled
    plan needs only ONE live staging buffer, so its refusal floor is
    one chunk row (``min_budget = row_bytes``). ``src_host`` marks a
    host-resident source (no D2H half), ``dst_host`` pins the
    destination to host RAM (``None`` = automatic: host when the
    spilled destination's per-device footprint exceeds the budget),
    and ``topo_key`` (from
    :func:`~pylops_mpi_tpu.parallel.topology.topology_key`) is named
    in refusal messages so hybrid-mesh failures are attributable."""
    global_shape = tuple(int(s) for s in global_shape)
    itemsize = int(itemsize)
    if budget is _UNSET:
        budget = reshard_budget()
    if spill is None:
        from ..utils.deps import spill_mode
        spill = spill_mode()
    if spill not in ("auto", "on", "off"):
        raise ValueError(f"spill={spill!r}: expected one of "
                         "['auto', 'on', 'off']")
    total = int(np.prod(global_shape, dtype=np.int64)) * itemsize

    if dst.is_scatter:
        move_axis = dst.axis
    elif src.is_scatter:
        move_axis = src.axis
    else:
        move_axis = 0
    rows = global_shape[move_axis] if global_shape else 0

    if src.is_scatter and not dst.is_scatter:
        kind = "all_gather"
    elif src.is_scatter and dst.is_scatter:
        kind = "ppermute" if src.axis == dst.axis else "all_to_all"
    else:
        kind = "local"

    if total == 0 or rows == 0:
        return ReshardPlan(global_shape, itemsize, src, dst, move_axis,
                           kind, 1, (), 0, None, None, 0, 0, budget)

    B = _pair_bytes(total, src, dst, move_axis, global_shape, itemsize)
    np.fill_diagonal(B, 0.0)   # rank identity: the diagonal stays put
    comm = int(round(B.sum()))
    if comm == 0:
        kind = "local"

    nb_ici = nb_dcn = None
    if slice_ids is not None and comm:
        sm = [int(s) for s in slice_ids]

        def _sid(r):
            return sm[min(r, len(sm) - 1)]
        cross = np.asarray([[_sid(i) != _sid(j) for j in range(B.shape[1])]
                            for i in range(B.shape[0])])
        nb_dcn = int(round(B[cross].sum()))
        nb_ici = comm - nb_dcn

    row_bytes = max(1, total // rows)
    factor = 1 if comm == 0 else 2   # carved piece (+ its exchanged copy)
    min_budget = factor * row_bytes
    topo_note = f" (topology {topo_key})" if topo_key else ""
    spilled = spill == "on"
    c_budget = 1
    if budget is not None and not spilled:
        w_max = int(budget) // (factor * row_bytes)
        if w_max < 1:
            if spill == "auto":
                # the spill tier's reason to exist: a move the device
                # planner must refuse runs host-staged instead
                spilled = True
            else:
                raise ReshardError(
                    f"reshard: budget {int(budget)} B cannot fit one "
                    f"{row_bytes}-byte row of axis {move_axis} "
                    f"({'x'.join(map(str, global_shape))}, {kind} move needs "
                    f"{factor} live buffers); the minimum budget that would "
                    f"succeed is {min_budget} B — raise "
                    f"{RESHARD_BUDGET_ENV} to at least {min_budget}"
                    f"{topo_note}",
                    min_budget)
        else:
            c_budget = -(-rows // w_max)
    if spilled:
        return _plan_spilled(global_shape, itemsize, src, dst, move_axis,
                             kind, rows, row_bytes, budget, chunks,
                             src_host, dst_host, topo_note)

    n_chunks = min(rows, max(c_budget, int(chunks or 1)))
    width = -(-rows // n_chunks)
    n_chunks = -(-rows // width)    # drop empty tail chunks

    steps = []
    peak = 0
    comm_left = comm
    ici_left = nb_ici or 0
    dcn_left = nb_dcn or 0
    for c in range(n_chunks):
        lo = c * width
        hi = min(rows, lo + width)
        cb = (hi - lo) * row_bytes
        steps.append(ReshardStep("dynamic_slice", c, lo, hi,
                                 scratch_bytes=cb))
        peak = max(peak, cb)
        if comm:
            last = c == n_chunks - 1
            share = comm_left if last else int(comm * (hi - lo) / rows)
            si = ici_left if last else (
                int(nb_ici * (hi - lo) / rows) if nb_ici is not None else None)
            sd = dcn_left if last else (
                int(nb_dcn * (hi - lo) / rows) if nb_dcn is not None else None)
            comm_left -= share
            if nb_ici is not None:
                ici_left -= si
                dcn_left -= sd
            steps.append(ReshardStep(
                kind, c, lo, hi, nbytes=share,
                nbytes_ici=si if nb_ici is not None else None,
                nbytes_dcn=sd if nb_dcn is not None else None,
                scratch_bytes=2 * cb))
            peak = max(peak, 2 * cb)

    return ReshardPlan(global_shape, itemsize, src, dst, move_axis, kind,
                       n_chunks, tuple(steps), comm, nb_ici, nb_dcn,
                       peak, min_budget, budget)


def _plan_spilled(global_shape, itemsize, src: Layout, dst: Layout,
                  move_axis: int, kind: str, rows: int, row_bytes: int,
                  budget, chunks, src_host: bool,
                  dst_host: Optional[bool], topo_note: str) -> ReshardPlan:
    """Build an all-``host_stage`` plan: every chunk is staged through
    host RAM, so only ONE device buffer is ever live and the refusal
    floor drops to one chunk row. The bytes move over PCIe
    (``nbytes_h2d``/``nbytes_d2h`` per step); the logical collective
    family ``kind`` is kept for provenance."""
    if budget is not None and int(budget) < row_bytes:
        raise ReshardError(
            f"reshard: budget {int(budget)} B cannot fit one "
            f"{row_bytes}-byte row of axis {move_axis} "
            f"({'x'.join(map(str, global_shape))}, host-staged {kind} "
            f"move needs 1 live staging buffer); the minimum budget "
            f"that would succeed is {row_bytes} B — raise "
            f"{RESHARD_BUDGET_ENV} to at least {row_bytes}{topo_note}",
            row_bytes)
    w_max = rows if budget is None else max(1, int(budget) // row_bytes)
    c_budget = -(-rows // w_max)
    n_chunks = min(rows, max(c_budget, int(chunks or 1)))
    width = -(-rows // n_chunks)
    n_chunks = -(-rows // width)    # drop empty tail chunks
    if dst.is_scatter and dst.sizes:
        dst_rows = max(dst.sizes)
    else:
        dst_rows = rows             # replicated: every device holds all
    dst_device_bytes = dst_rows * row_bytes
    if dst_host is None:
        host_dst = budget is not None and dst_device_bytes > int(budget)
    else:
        host_dst = bool(dst_host)
    steps = []
    peak = h2d = d2h = 0
    for c in range(n_chunks):
        lo = c * width
        hi = min(rows, lo + width)
        cb = (hi - lo) * row_bytes
        s_d2h = 0 if src_host else cb
        s_h2d = 0 if host_dst else cb
        scratch = cb if (s_d2h or s_h2d) else 0
        steps.append(ReshardStep("host_stage", c, lo, hi,
                                 scratch_bytes=scratch,
                                 nbytes_h2d=s_h2d, nbytes_d2h=s_d2h))
        peak = max(peak, scratch)
        h2d += s_h2d
        d2h += s_d2h
    return ReshardPlan(global_shape, itemsize, src, dst, move_axis, kind,
                       n_chunks, tuple(steps), 0, None, None, peak,
                       row_bytes, budget, spilled=True, host_dst=host_dst,
                       nbytes_h2d=h2d, nbytes_d2h=d2h,
                       dst_device_bytes=dst_device_bytes)


# ------------------------------------------------------------- executor

def _is_tracer(x) -> bool:
    return isinstance(x, jax.core.Tracer)


def _same_devices(a: Mesh, b: Mesh) -> bool:
    if a is b:
        return True
    da = [d.id for d in np.asarray(a.devices).ravel()]
    db = [d.id for d in np.asarray(b.devices).ravel()]
    return da == db


def _carve(src, host_value, lo: int, hi: int, move_axis: int):
    """Logical rows ``[lo, hi)`` along ``move_axis`` as one array.
    Bounded: touches only the chunk plus (for padded sources) the
    chunk-sized unpad gather."""
    if host_value is not None:
        sl = [slice(None)] * host_value.ndim
        sl[move_axis] = slice(lo, hi)
        return host_value[tuple(sl)]
    phys = src._arr
    if src.partition != Partition.SCATTER:
        return lax.slice_in_dim(phys, lo, hi, axis=move_axis)
    if move_axis != src._axis:
        piece = lax.slice_in_dim(phys, lo, hi, axis=move_axis)
        if src._even:
            return piece
        idx = unpad_index_map(src._axis_sizes, src._s_phys)
        return jnp.take(piece, jnp.asarray(idx), axis=src._axis)
    offs = shard_offsets(src._axis_sizes)
    sp = src._s_phys
    parts = []
    for p, size_p in enumerate(src._axis_sizes):
        a = max(lo, offs[p])
        b = min(hi, offs[p] + size_p)
        if a >= b:
            continue
        start = p * sp + (a - offs[p])
        parts.append(lax.slice_in_dim(phys, start, start + (b - a),
                                      axis=move_axis))
    if not parts:
        shp = list(phys.shape)
        shp[move_axis] = 0
        return jnp.zeros(shp, dtype=phys.dtype)
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts,
                                                            axis=move_axis)


def _place_piece(out, piece, lo: int, hi: int, dst, move_axis: int):
    """Scatter logical rows ``[lo, hi)`` into ``dst``'s physical
    buffer ``out`` with static-index updates."""
    if piece.dtype != out.dtype:
        piece = piece.astype(out.dtype)
    # static starts go in as int32 scalars: a python int would promote
    # to s64 under x64 and trip the SPMD partitioner's s32 index math
    if dst._partition != Partition.SCATTER:
        return lax.dynamic_update_slice_in_dim(out, piece, np.int32(lo),
                                               axis=move_axis)
    offs = shard_offsets(dst._axis_sizes)
    sp = dst._s_phys
    for q, size_q in enumerate(dst._axis_sizes):
        a = max(lo, offs[q])
        b = min(hi, offs[q] + size_q)
        if a >= b:
            continue
        sub = lax.slice_in_dim(piece, a - lo, b - lo, axis=move_axis)
        out = lax.dynamic_update_slice_in_dim(
            out, sub, np.int32(q * sp + (a - offs[q])), axis=move_axis)
    return out


def _chunk_ranges(plan: ReshardPlan):
    seen = []
    for s in plan.steps:
        if s.kind == "dynamic_slice":
            seen.append((s.lo, s.hi))
    return seen


def _run_plan(plan: ReshardPlan, dst, *, src=None, host_value=None):
    """Execute ``plan`` into the fresh DistributedArray ``dst``
    (its constructor zero-filled the physical buffer, so pad rows are
    already in the canonical zero state). Returns the physical array."""
    from ..resilience import faults as _faults
    out = dst._arr
    move = plan.move_axis
    cross = src is not None and not _same_devices(src.mesh, dst._mesh)
    traced = src is not None and _is_tracer(src._arr)
    if cross and traced:
        raise ValueError("reshard: moving to a different device set is a "
                         "concrete transfer and cannot run under a trace")
    has_comm = plan.nbytes > 0
    step_i = 0
    for (lo, hi) in _chunk_ranges(plan):
        _faults.maybe_kill_reshard()
        st = plan.steps[step_i]
        _trace.event("collective.reshard.step", kind=st.kind, lo=lo, hi=hi,
                     nbytes=st.nbytes, scratch_bytes=st.scratch_bytes)
        piece = _carve(src, host_value, lo, hi, move)
        step_i += 1
        if has_comm:
            _faults.maybe_kill_reshard()
            st = plan.steps[step_i]
            _trace.event("collective.reshard.step", kind=st.kind, lo=lo,
                         hi=hi, nbytes=st.nbytes,
                         scratch_bytes=st.scratch_bytes)
            step_i += 1
        if host_value is not None or cross:
            piece = jax.device_put(piece, replicated_sharding(dst._mesh))
        out = _place_piece(out, piece, lo, hi, dst, move)
        if not _is_tracer(out):
            out = dst._place(out)   # re-pin so scratch stays chunk-bounded
            if jax.default_backend() != "tpu":
                # the CPU-sim collective rendezvous starves (and
                # deadlocks) when many compiled chunk programs are in
                # flight at once; TPU device-ordered execution needs no
                # per-chunk sync, so only the simulator pays it
                jax.block_until_ready(out)
    return dst._place(out)


def _layout_of(x) -> Layout:
    if x.partition == Partition.SCATTER:
        return Layout.scatter(x._axis_sizes, x.axis)
    return Layout.replicated(x.n_shards, x.partition)


def _dst_layout(global_shape, n_shards: int, partition: Partition,
                axis: int, local_shapes):
    """Destination :class:`Layout` plus the normalized ``(axis,
    local_shapes)`` WITHOUT constructing the array — the spilled
    host-destination path must not allocate the (oversized) device
    buffer just to read its metadata. Validation mirrors the
    :class:`~pylops_mpi_tpu.DistributedArray` constructor."""
    axis = int(axis)
    if axis < 0:
        axis += len(global_shape)
    if partition == Partition.SCATTER and not (0 <= axis < len(global_shape)):
        raise IndexError(f"axis {axis} out of range for shape {global_shape}")
    if local_shapes is None:
        lsh = local_split(global_shape, n_shards, partition, axis)
    else:
        lsh = tuple(tuple(int(v) for v in np.atleast_1d(s))
                    for s in local_shapes)
        if len(lsh) != n_shards:
            raise ValueError(f"need {n_shards} local shapes, got {len(lsh)}")
        if partition == Partition.SCATTER:
            tot = sum(s[axis] for s in lsh)
            if tot != global_shape[axis]:
                raise ValueError(
                    f"local shapes sum to {tot} != global dim "
                    f"{global_shape[axis]}")
    if partition == Partition.SCATTER:
        return Layout.scatter(tuple(s[axis] for s in lsh), axis), axis, lsh
    return Layout.replicated(n_shards, partition), axis, lsh


def _span_and_run(plan: ReshardPlan, dst, *, src=None, host_value=None,
                  host_out=None, overlap=None, op: str = "reshard"):
    tags = dict(cat="collective", op=op, kind=plan.kind,
                chunks=plan.chunks, shape=plan.global_shape,
                peak_scratch=plan.peak_scratch)
    if plan.spilled:
        from . import spill as _spill
        seq = _count_collective("reshard", nbytes_h2d=plan.nbytes_h2d,
                                nbytes_d2h=plan.nbytes_d2h)
        tags.update(spilled=True, h2d_bytes=plan.nbytes_h2d,
                    d2h_bytes=plan.nbytes_d2h, host_dst=plan.host_dst)
        with _trace.span("collective.reshard", seq=seq, **tags):
            return _spill.run_spilled(plan, dst=dst, host_out=host_out,
                                      src=src, host_value=host_value,
                                      overlap=overlap)
    if plan.nbytes_ici is not None:
        seq = _count_collective("reshard", nbytes_ici=plan.nbytes_ici,
                                nbytes_dcn=plan.nbytes_dcn)
        tags.update(ici_bytes=plan.nbytes_ici, dcn_bytes=plan.nbytes_dcn)
    else:
        fab = _topo.collective_fabric(dst._mesh, None)
        seq = _count_collective("reshard", plan.nbytes, fab)
        tags.update(nbytes=plan.nbytes)
    with _trace.span("collective.reshard", seq=seq, **tags):
        return _run_plan(plan, dst, src=src, host_value=host_value)


def reshard(x, *, mesh: Optional[Mesh] = None,
            partition: Optional[Partition] = None,
            axis: Optional[int] = None,
            local_shapes=None, budget=_UNSET,
            chunks: Optional[int] = None, spill: Optional[str] = None,
            overlap: Optional[str] = None,
            host_dst: Optional[bool] = None):
    """Move a :class:`~pylops_mpi_tpu.DistributedArray` (or a
    host-resident :class:`~pylops_mpi_tpu.parallel.spill.HostArray`)
    to a new layout — partition policy, shard axis, ragged split,
    and/or a different mesh (shrink/grow) — with peak scratch bounded
    by the budget. Same-device-set moves are jit-safe; cross-mesh
    moves transfer one chunk at a time and require concrete inputs.

    A mask only survives a move that keeps the shard count (mask
    colors are per-shard); the planner refuses otherwise, as it
    refuses a SCATTER target whose axis is shorter than the new shard
    count — both mirror the checkpoint elastic-restore refusals, so
    callers can fall back to the same checkpoint path.

    Round 14: ``spill``/``overlap``/``host_dst`` thread through to the
    host-staging tier (see :func:`plan_reshard` and
    :mod:`~pylops_mpi_tpu.parallel.spill`). A concrete over-budget
    move runs host-staged instead of refusing (mode ``auto``), and a
    destination too large for the device budget comes back as a
    :class:`~pylops_mpi_tpu.parallel.spill.HostArray`; traced moves
    never spill."""
    from ..distributedarray import DistributedArray
    from . import spill as _spill
    if isinstance(x, _spill.HostArray):
        return _spill.reshard_from_host(
            x, mesh=mesh, partition=partition, axis=axis,
            local_shapes=local_shapes, budget=budget, chunks=chunks,
            spill=spill, overlap=overlap, host_dst=host_dst)
    tgt_mesh = mesh if mesh is not None else x.mesh
    tgt_part = partition if partition is not None else x.partition
    tgt_axis = x.axis if axis is None else int(axis)
    n_new = int(tgt_mesh.devices.size)
    if (tgt_part == Partition.SCATTER and local_shapes is None
            and x.global_shape[tgt_axis] < n_new):
        if _same_devices(x.mesh, tgt_mesh):
            # zero-row shards on the SAME device set are established
            # redistribute semantics (a tiny axis spread thin); the
            # planner's step carving assumes non-empty shards, so this
            # corner keeps the legacy one-shot placement (jit-safe,
            # bit-identical to the pre-planner path)
            out = DistributedArray(x.global_shape, tgt_mesh, tgt_part,
                                   tgt_axis, local_shapes=None,
                                   mask=x.mask, dtype=x.dtype)
            out._arr = out._place(out._from_global(x._global()))
            return out
        raise ReshardError(
            f"reshard: SCATTER axis {tgt_axis} has "
            f"{x.global_shape[tgt_axis]} rows < {n_new} shards — the "
            "balanced split would leave at least one shard with zero "
            "rows; choose a different partition axis",
            0)
    if x.mask is not None and n_new != x.n_shards:
        raise ReshardError(
            f"reshard: array carries a mask (per-shard group colors) and "
            f"the move changes the shard count {x.n_shards} -> {n_new}; "
            "drop the mask or re-derive it for the new world first", 0)
    # destination metadata WITHOUT constructing the array: a spilled
    # host destination must never allocate the oversized device buffer
    dst_l, ax_n, lsh = _dst_layout(x.global_shape, n_new, tgt_part,
                                   tgt_axis, local_shapes)
    # no-op fast path: identical layout on the same devices
    if (_same_devices(x.mesh, tgt_mesh) and tgt_part == x.partition
            and (tgt_part != Partition.SCATTER
                 or (ax_n == x._axis
                     and dst_l.sizes == x._axis_sizes))):
        out = DistributedArray(x.global_shape, tgt_mesh, tgt_part, tgt_axis,
                               local_shapes=local_shapes, mask=x.mask,
                               dtype=x.dtype)
        out._arr = x._arr + 0
        return out
    plan = plan_reshard(x.global_shape, np.dtype(x.dtype).itemsize,
                        _layout_of(x), dst_l, budget=budget,
                        chunks=chunks, slice_ids=_topo.slice_map(tgt_mesh),
                        spill=("off" if _is_tracer(x._arr) else spill),
                        dst_host=host_dst,
                        topo_key=_topo.topology_key(tgt_mesh))
    if plan.spilled and plan.host_dst:
        host_out = np.empty(x.global_shape, dtype=x.dtype)
        _span_and_run(plan, None, src=x, host_out=host_out,
                      overlap=overlap)
        return _spill.HostArray(host_out, tgt_mesh, tgt_part, ax_n,
                                local_shapes=lsh, mask=x.mask)
    out = DistributedArray(x.global_shape, tgt_mesh, tgt_part, tgt_axis,
                           local_shapes=local_shapes, mask=x.mask,
                           dtype=x.dtype)
    out._arr = _span_and_run(plan, out, src=x, overlap=overlap)
    return out


def place_replica(value, mesh: Mesh,
                  partition: Partition = Partition.SCATTER, axis: int = 0,
                  local_shapes=None, mask=None, budget=_UNSET,
                  chunks: Optional[int] = None, dtype=None,
                  spill: Optional[str] = None,
                  overlap: Optional[str] = None):
    """Place a host-replicated logical value (a numpy array every
    surviving process holds, e.g. a banked solver-carry field) onto
    ``mesh`` as a fresh :class:`~pylops_mpi_tpu.DistributedArray`,
    streaming chunk-at-a-time so device scratch stays under the
    budget. This is the survivor-side primitive of in-place elastic
    recovery: no checkpoint I/O, just bounded host→device placement."""
    from ..distributedarray import DistributedArray
    value = np.asarray(value)
    out = DistributedArray(value.shape, mesh, partition, axis,
                           local_shapes=local_shapes, mask=mask,
                           dtype=dtype if dtype is not None else value.dtype)
    plan = plan_reshard(value.shape, out.dtype.itemsize,
                        Layout.replicated(1), _layout_of(out),
                        budget=budget, chunks=chunks,
                        slice_ids=_topo.slice_map(mesh),
                        spill=spill, src_host=True, dst_host=False,
                        topo_key=_topo.topology_key(mesh))
    out._arr = _span_and_run(plan, out, host_value=value, overlap=overlap,
                             op="place_replica")
    return out


def reshard_raw(x: jax.Array, mesh: Mesh, old_axis: int, new_axis: int, *,
                budget=_UNSET, chunks: Optional[int] = None) -> jax.Array:
    """Planner-backed resharding of a plain ``jax.Array`` from
    ``old_axis`` to ``new_axis`` — the non-divisible fallback of
    :func:`~pylops_mpi_tpu.parallel.collectives.all_to_all_resharding`.

    jax only commits even shardings, so the move runs pad → streamed
    exchange → crop (the round-3 pad-and-crop contract): both axes pad
    to mesh multiples, the exchange streams in plan-sized chunks —
    each a divisible tile through the bulk single-``all_to_all``
    kernel, so the collective scratch stays chunk-bounded per arXiv
    2112.01075 — and the result crops back to ``x.shape``. The plan's
    budget check still applies: an impossible budget raises
    :class:`ReshardError` naming the minimum that would succeed.
    Trace-safe (every step is a static slice / pad / collective)."""
    from .collectives import all_to_all_resharding
    from ..resilience import faults as _faults
    n_dev = int(mesh.devices.size)
    # spill="off": this path is trace-safe by contract — a host-staged
    # schedule (concrete device_get) can never run under a trace, so
    # an impossible budget keeps the round-13 refusal here
    plan = plan_reshard(
        x.shape, x.dtype.itemsize,
        Layout.scatter(_ceil_sizes(x.shape[old_axis], n_dev), old_axis),
        Layout.scatter(_ceil_sizes(x.shape[new_axis], n_dev), new_axis),
        budget=budget, chunks=chunks, slice_ids=_topo.slice_map(mesh),
        spill="off", topo_key=_topo.topology_key(mesh))
    if plan.nbytes_ici is not None:
        seq = _count_collective("reshard", nbytes_ici=plan.nbytes_ici,
                                nbytes_dcn=plan.nbytes_dcn)
    else:
        seq = _count_collective("reshard", plan.nbytes,
                                _topo.collective_fabric(mesh, None))
    new_dim = x.shape[new_axis]
    # every streamed tile must be a mesh multiple along new_axis; cap
    # the chunk count so padding never exceeds one tile of slack
    n_chunks = min(plan.chunks, max(1, -(-new_dim // n_dev)))
    tile = n_chunks * n_dev
    bo = -(-new_dim // tile)
    cw = n_dev * bo
    with _trace.span("collective.reshard", cat="collective", op="raw",
                     kind=plan.kind, chunks=n_chunks, shape=x.shape,
                     old_axis=old_axis, new_axis=new_axis,
                     peak_scratch=plan.peak_scratch, seq=seq):
        xp = _pad_axis_to(x, old_axis, n_dev * (-(-x.shape[old_axis] // n_dev)))
        xp = _pad_axis_to(xp, new_axis, tile * bo)
        parts = []
        for k in range(n_chunks):
            _faults.maybe_kill_reshard()
            _trace.event("collective.reshard.step", kind="all_to_all",
                         lo=k * cw, hi=(k + 1) * cw,
                         nbytes=plan.nbytes // n_chunks)
            ck = lax.slice_in_dim(xp, k * cw, (k + 1) * cw, axis=new_axis)
            parts.append(all_to_all_resharding(ck, mesh, old_axis,
                                               new_axis))
        out = parts[0] if len(parts) == 1 else jnp.concatenate(
            parts, axis=new_axis)
        out = lax.slice_in_dim(out, 0, x.shape[old_axis], axis=old_axis)
        return lax.slice_in_dim(out, 0, new_dim, axis=new_axis)


def _pad_axis_to(x, axis: int, target: int):
    if x.shape[axis] == target:
        return x
    padw = [(0, 0)] * x.ndim
    padw[axis] = (0, target - x.shape[axis])
    return jnp.pad(x, padw)
