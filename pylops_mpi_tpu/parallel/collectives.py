"""Explicit collective primitives over the mesh (shard_map layer).

TPU-native equivalent of the reference's L0/L1 communication stack
(``pylops_mpi/Distributed.py:24-349``, ``utils/_mpi.py``,
``utils/_nccl.py``): one backend — XLA collectives over ICI/DCN — instead
of the MPI/NCCL dual dispatch. The implicit path (GSPMD partitioning of
plain ``jnp`` ops on sharded arrays) covers most of the library; this
module holds only the hand-scheduled primitives the hot kernels consume:

- :func:`all_to_all_resharding` — the pencil transpose of the
  distributed FFTs (``ops/fft.py``) and ``redistribute``'s pattern;
- :func:`plane_all_to_all` — the same pencil transpose on an (re, im)
  REAL plane pair (one stacked collective), consumed by the planar
  complex-free FFT mode's shard_map kernels;
- :func:`ring_halo_extend` / :func:`cart_halo_extend` — in-kernel
  neighbour (ghost-cell) exchanges used by the stencil fast path
  (``ops/derivatives.py``) and the N-D Cartesian halo (``ops/halo.py``);
- the **pipelined layer** (round 8, ``PYLOPS_MPI_TPU_OVERLAP``):
  :func:`ring_pass` — the double-buffered ``ppermute`` ring behind the
  overlapped SUMMA schedules (``ops/matrixmult.py``) and the
  homogeneous-row stack reduction (``ops/stack.py``): P-1
  collective-permutes interleaved with P per-block compute steps, each
  transfer independent of the resident block's compute so the
  latency-hiding scheduler overlaps DMA with the MXU (arXiv
  2112.09017's decomposed-collective scheme);
  :func:`chunked_pencil_transpose` (+ ``_planes``) — the streamed
  pencil transpose of the distributed FFTs: K tiled ``all_to_all``
  chunks, each chased immediately by its local transforms, so the
  transpose streams instead of barriering (arXiv 2112.01075);
  :func:`ring_halo_ghosts` — the halo exchange's two ghost slabs
  WITHOUT the concatenation, so stencil kernels can issue the
  ``ppermute``\\ s first and compute the interior while they fly.

- the **topology-aware layer** (round 11,
  ``PYLOPS_MPI_TPU_HIERARCHICAL``): :func:`hier_pencil_transpose`
  (+ ``_planes``, chunked variants), :func:`hier_psum_scatter`,
  :func:`hier_all_gather`, and :func:`ring_pass`'s ``slice_size``
  schedule — two-level decompositions for hybrid (dcn × ici) meshes
  that keep the dense exchange on ICI and stage one smaller transfer
  over DCN, with per-fabric byte counters
  (``collective.*.bytes_ici``/``.bytes_dcn``). Fabric classification
  comes from :mod:`pylops_mpi_tpu.parallel.topology`.

Generic allreduce/allgather wrappers existed in round 1 but had no
production call sites (reductions lower to ``psum`` through GSPMD
already) and were removed rather than kept as padding.

Sub-communicator semantics (``MPI.Comm.Split`` / ``nccl_split``,
ref ``pylops_mpi/DistributedArray.py:74-100``, ``utils/_nccl.py:135-165``)
are expressed with segment reductions / ``axis_index_groups`` at the
call sites that need them (``DistributedArray._reduce``).
"""

from __future__ import annotations

import logging
import threading
from typing import Callable, Dict, Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P
from jax import shard_map
from ..diagnostics import metrics as _metrics
from ..diagnostics import trace as _trace

__all__ = [
    "all_to_all_resharding",
    "plane_all_to_all",
    "ring_halo_extend",
    "cart_halo_extend",
    "halo_slab",
    "ring_pass",
    "ring_halo_ghosts",
    "resolve_chunks",
    "chunked_pencil_transpose",
    "chunked_pencil_transpose_planes",
    "hier_pencil_transpose",
    "hier_pencil_transpose_planes",
    "hier_chunked_pencil_transpose",
    "hier_chunked_pencil_transpose_planes",
    "hier_psum_scatter",
    "hier_all_gather",
    "reduce_stall",
    "stall_signature",
]

_logger = logging.getLogger("pylops_mpi_tpu.collectives")


# ------------------------------------------------ reduction-latency seam
# The CPU-sim mesh has ~zero all-reduce latency, so the
# communication-avoiding solver tier (solvers/ca.py) has nothing to win
# against on CI: every reduction completes in the time of a local sum.
# reduce_stall() is the test/chaos seam that restores a pod-fabric
# latency profile — it chains an N-step SERIAL scalar recurrence (each
# step depends on the previous one, so XLA cannot parallelize or fold
# it) onto a reduction result, seeded FROM that result (so it cannot be
# hoisted as a loop invariant) and folded back in with a float ``*0``
# term (which XLA must keep: 0*x is not 0 for NaN/inf operands). Every
# consumer of the reduction therefore waits ~N serial FLOPs — a
# deterministic, platform-independent stand-in for wire latency. With
# the knob unset the input is returned untraced, keeping the solver
# programs bit-identical.

def reduce_stall(k, steps: Optional[int] = None):
    """Chain an ``N``-step serial dependency onto reduction result
    ``k`` (any float array) and return a value numerically equal to
    ``k``. ``steps=None`` reads ``PYLOPS_MPI_TPU_REDUCE_STALL``; 0
    returns ``k`` itself with nothing traced."""
    if steps is None:
        from ..utils import deps as _deps
        steps = _deps.reduce_stall_steps()
    if not steps:
        return k
    k = jnp.asarray(k)
    seed = (jnp.sum(k) * jnp.asarray(1e-30, k.dtype)).astype(jnp.float32)

    def _step(_, c):
        return c * jnp.float32(1.0000001) + jnp.float32(1e-9)

    z = lax.fori_loop(0, int(steps), _step, seed)
    return k + (z * jnp.float32(0.0)).astype(k.dtype)


def stall_signature() -> tuple:
    """Fused-solver cache-key fragment for the stall seam: ``()`` when
    off — so enabling the knob can never collide with (or perturb the
    keys of) the bit-identical default programs — else a one-entry
    tuple carrying the chain length."""
    from ..utils import deps as _deps
    n = _deps.reduce_stall_steps()
    return (("stall", n),) if n else ()

# ---------------------------------------------- per-op sequence numbers
# Every rank of an SPMD job reaches the collectives in the same
# deterministic program order, so a per-op-name call counter gives the
# cross-rank matching key the fleet aggregator needs: span (name, seq)
# on rank 0 is THE SAME collective as (name, seq) on rank 7
# (diagnostics/aggregate.py stamps skew_us/straggler_rank per match).
# Incremented unconditionally — flipping TRACE mid-run must not
# desynchronize the counters across ranks — but these wrappers run
# per *dispatch* (often once per compile), never per device step, so
# the cost is one lock + dict op off the hot path.
_SEQ_LOCK = threading.Lock()
_SEQ: Dict[str, int] = {}


def _collective_seq(name: str) -> int:
    with _SEQ_LOCK:
        n = _SEQ.get(name, 0)
        _SEQ[name] = n + 1
    return n


def _count_collective(name: str, nbytes: Optional[int] = None,
                      fabric: Optional[str] = None,
                      nbytes_ici: Optional[int] = None,
                      nbytes_dcn: Optional[int] = None,
                      nbytes_h2d: Optional[int] = None,
                      nbytes_d2h: Optional[int] = None) -> int:
    """Metrics + sequencing for one collective dispatch: bumps the
    per-op call (and, when an estimate exists, byte) counters in the
    metrics registry and returns this call's sequence number for the
    span tags. Round 11: ``fabric`` attributes single-fabric bytes to
    ``.bytes_ici``/``.bytes_dcn`` (``None`` — a flat mesh — keeps only
    the legacy ``.bytes`` counter); a two-level collective passes its
    per-phase shares via ``nbytes_ici``/``nbytes_dcn`` instead, which
    sum into the legacy counter. Round 14: a host-staged (spilled)
    move passes its transfer bytes via ``nbytes_h2d``/``nbytes_d2h``;
    those land in ``.bytes_h2d``/``.bytes_d2h`` only — host↔device
    copies are not inter-device payload."""
    _metrics.inc(f"collective.{name}.calls")
    if nbytes is not None:
        _metrics.collective_bytes(name, int(nbytes), fabric)
    if nbytes_ici:
        _metrics.collective_bytes(name, int(nbytes_ici), "ici")
    if nbytes_dcn:
        _metrics.collective_bytes(name, int(nbytes_dcn), "dcn")
    if nbytes_h2d:
        _metrics.collective_bytes(name, int(nbytes_h2d), "h2d")
    if nbytes_d2h:
        _metrics.collective_bytes(name, int(nbytes_d2h), "d2h")
    return _collective_seq(name)


def _est_bytes(x, scale: float = 1.0) -> Optional[int]:
    """Best-effort payload estimate for an array (works on tracers —
    shapes are static); ``None`` when the array doesn't expose one."""
    try:
        return int(x.size * x.dtype.itemsize * scale)
    except (AttributeError, TypeError):
        return None


def all_to_all_resharding(x: jax.Array, mesh: Mesh,
                          old_axis: int, new_axis: int) -> jax.Array:
    """Reshard from ``old_axis`` to ``new_axis`` — the all-to-all pattern
    behind ``DistributedArray.redistribute``
    (ref ``pylops_mpi/DistributedArray.py:463-522``) and the pencil-FFT
    transposes (``signalprocessing/FFTND.py:199-211``).

    The implicit path (``jax.device_put`` with the new sharding) lets XLA
    pick the schedule; this explicit version pins a single
    ``lax.all_to_all`` when both axes divide the mesh size. Round 13:
    non-dividing axes no longer raise — they route through the
    bounded-memory resharding planner
    (:func:`~pylops_mpi_tpu.parallel.reshard.reshard_raw`), which only
    refuses (``ReshardError``, naming the minimum budget that would
    succeed) when ``PYLOPS_MPI_TPU_RESHARD_BUDGET`` makes the move
    genuinely impossible.
    """
    axis_name = mesh.axis_names[0]
    n_dev = int(mesh.devices.size)
    if any(x.shape[ax] % n_dev
           for ax in dict.fromkeys((old_axis, new_axis))):
        from .reshard import reshard_raw
        return reshard_raw(x, mesh, old_axis, new_axis)
    in_spec = [None] * x.ndim
    in_spec[old_axis] = axis_name
    out_spec = [None] * x.ndim
    out_spec[new_axis] = axis_name

    def kernel(xs):
        return lax.all_to_all(xs, axis_name, split_axis=new_axis,
                              concat_axis=old_axis, tiled=True)

    ici_bytes = int(x.size * x.dtype.itemsize
                    * (n_dev - 1) / max(n_dev, 1))
    with _trace.span("collective.all_to_all_resharding", cat="collective",
                     shape=x.shape, dtype=x.dtype, old_axis=old_axis,
                     new_axis=new_axis, n_dev=n_dev, ici_bytes=ici_bytes,
                     seq=_count_collective("all_to_all_resharding",
                                           ici_bytes)):
        return shard_map(kernel, mesh=mesh, in_specs=P(*in_spec),
                         out_specs=P(*out_spec))(x)


def plane_all_to_all(br: jax.Array, bi: jax.Array, axis_name: str, *,
                     split_axis: int, concat_axis: int):
    """ONE tiled ``all_to_all`` carrying an (re, im) plane pair, for use
    *inside* a ``shard_map`` kernel — the pencil-transpose primitive of
    the planar (complex-free) distributed FFT mode (``ops/fft.py``).

    The planes are stacked on a NEW trailing axis before the exchange,
    so each frequency bin's (re, im) pair stays on the same shard
    through the split — splitting a fused re/im layout along the
    transposed axis would separate the pair members across devices and
    make the post-transpose per-bin arithmetic impossible. One
    collective instead of two halves the dispatch count; the payload
    is the two f32 planes,
    which for the half-spectrum of a real transform is ~half the bytes
    of the complex engine's full-spectrum c64 schedule.

    ``split_axis``/``concat_axis`` refer to the UNSTACKED plane axes
    (both must be < ``br.ndim``). Returns the transposed plane pair.
    """
    with _trace.span("collective.plane_all_to_all", cat="collective",
                     shape=br.shape, dtype=br.dtype,
                     split_axis=split_axis, concat_axis=concat_axis,
                     axis=axis_name,
                     seq=_count_collective("plane_all_to_all",
                                           _est_bytes(br, 2.0))):
        s = jnp.stack([br, bi], axis=-1)
        s = lax.all_to_all(s, axis_name, split_axis=split_axis,
                           concat_axis=concat_axis, tiled=True)
        return s[..., 0], s[..., 1]


def cart_halo_extend(block: jax.Array, axis_name: str,
                     grid: Sequence[int], ax: int, hm: int, hp: int,
                     valid_len, array_axis: int = None,
                     slice_map: Optional[Sequence[int]] = None) -> jax.Array:
    """One axis of a Cartesian-grid halo exchange, for use *inside* a
    ``shard_map`` kernel: extends ``block`` along array axis ``ax`` with
    ``hm`` ghost rows from the minus-neighbour and ``hp`` from the
    plus-neighbour of the flat mesh axis arranged as the row-major
    ``grid``. Boundary shards keep zero ghosts (unpaired ``ppermute``
    destinations are zero-filled), reproducing the reference's
    zero-padded edges (``pylops_mpi/basicoperators/Halo.py:320-360``).

    ``valid_len`` — the calling shard's count of logically-valid rows
    along ``ax`` (traced per-device scalar for ragged ceil-splits): the
    minus-ghost sent to the plus-neighbour is the *valid* tail
    ``[valid_len-hm, valid_len)``, not the padded tail. Calling this per
    axis in sequence relays corner values exactly like the reference's
    sequential ``Sendrecv`` chain.

    Sends only the boundary slabs — this is the neighbour exchange the
    implicit partitioner cannot be trusted to recover from a gather
    formulation, lowered to ``collective-permute`` on ICI.

    ``array_axis`` — the block dimension the ghosts extend, when it
    differs from the mesh-grid axis ``ax`` (default: the same index,
    the N-D Cartesian-halo convention where grid dims mirror array
    dims; ``DistributedArray.ghosted`` shards e.g. array axis 1 over a
    1-axis mesh grid).
    """
    a_ax = ax if array_axis is None else array_axis
    g_ax = int(grid[ax])
    if hm == 0 and hp == 0:
        return block
    # flat-rank stride between ax-neighbours in the row-major grid
    stride = int(np.prod([int(g) for g in grid[ax + 1:]]))
    n = int(np.prod([int(g) for g in grid]))
    coords = [np.unravel_index(r, tuple(int(g) for g in grid))[ax]
              for r in range(n)]
    # per-fabric ghost bytes (round 11): only when the caller resolved
    # a slice map for the flat rank order (hybrid meshes) — flat meshes
    # keep the legacy calls-only counter byte-for-byte. Attribution is
    # the per-device average over the grid's neighbour pairs, the same
    # formula the cost model uses (model vs trace must agree).
    nb_ici = nb_dcn = None
    if slice_map is not None and g_ax > 1:
        try:
            row = block.size // block.shape[a_ax] * block.dtype.itemsize
        except (AttributeError, TypeError, ZeroDivisionError):
            row = None
        if row is not None:
            nb_ici = nb_dcn = 0
            for h, pairs in (
                    (hm, [(r, r + stride) for r in range(n)
                          if coords[r] < g_ax - 1]),
                    (hp, [(r, r - stride) for r in range(n)
                          if coords[r] > 0])):
                if not h:
                    continue
                cross = sum(1 for s, t in pairs
                            if slice_map[s] != slice_map[t])
                nb_ici += row * h * (len(pairs) - cross)
                nb_dcn += row * h * cross
            # per-device average, divided once at the end — a per-term
            # floor would zero out the few DCN-crossing pairs entirely
            nb_ici = -(-nb_ici // n)
            nb_dcn = -(-nb_dcn // n)
    _trace.event("collective.cart_halo_extend", cat="collective",
                 shape=getattr(block, "shape", None),
                 dtype=getattr(block, "dtype", None), axis=axis_name,
                 grid=tuple(int(g) for g in grid), ax=ax, hm=hm, hp=hp,
                 **({"fabric": "split"} if nb_ici is not None else {}),
                 seq=_count_collective("cart_halo_extend",
                                       nbytes_ici=nb_ici,
                                       nbytes_dcn=nb_dcn))
    if g_ax == 1:
        padw = [(0, 0)] * block.ndim
        padw[a_ax] = (hm, hp)
        return jnp.pad(block, padw)
    parts = []
    if hm:
        # my valid tail -> plus-neighbour's front ghost
        start = jnp.maximum(valid_len - hm, 0)
        slab = lax.dynamic_slice_in_dim(block, start, hm, axis=a_ax)
        perm = [(r, r + stride) for r in range(n) if coords[r] < g_ax - 1]
        parts.append(lax.ppermute(slab, axis_name, perm))
    parts.append(block)
    if hp:
        # my front rows -> minus-neighbour's back ghost (front rows are
        # valid even for short ragged blocks)
        slab = lax.slice_in_dim(block, 0, hp, axis=a_ax)
        perm = [(r, r - stride) for r in range(n) if coords[r] > 0]
        parts.append(lax.ppermute(slab, axis_name, perm))
    return jnp.concatenate(parts, axis=a_ax)


def halo_slab(block, axis_name: str, n_shards: int, ax: int,
              front: int, back: int, valid, s_phys: int,
              ragged: bool, slice_map: Optional[Sequence[int]] = None):
    """Ragged-aware ghosted slab for use *inside* a ``shard_map``
    kernel: :func:`cart_halo_extend` along ``ax`` plus, for ragged
    (pad-to-max) blocks, relocation of the received back ghost to sit
    right after this shard's last VALID row (``front + valid``) instead
    of after the padded tail. The relocation is a *local*
    ``dynamic_update_slice`` inside the shard_map body — not the
    GSPMD-partitioned scatter that miscompiles on sharded operands
    (jax 0.9, see ``ops/local.py``'s scatter-free note). The caller
    must scrub pad-tail garbage to zero BEFORE calling (the ghost sent
    to the successor is this block's valid tail, but the pad rows
    themselves travel nowhere — scrubbing keeps the slab's unused rows
    zero). Shared by the explicit stencil kernels
    (``ops/derivatives.py``) and ``DistributedArray.ghosted``; ``ax``
    is the ARRAY axis, the mesh is always the 1-D ring."""
    slab = cart_halo_extend(block, axis_name, (n_shards,), 0, front,
                            back, valid, array_axis=ax,
                            slice_map=slice_map)
    if ragged and back:
        bk = lax.slice_in_dim(slab, front + s_phys, front + s_phys + back,
                              axis=ax)
        slab = lax.dynamic_update_slice_in_dim(slab, bk, front + valid,
                                               axis=ax)
    return slab


# --------------------------------------------------------------------------
# Pipelined layer (round 8): decomposed collectives that the
# latency-hiding scheduler can overlap with compute. Every primitive
# here is for use INSIDE a shard_map kernel; the bulk (non-overlapped)
# schedules stay untouched so PYLOPS_MPI_TPU_OVERLAP=off is
# bit-identical to the pre-round-8 programs.

def ring_pass(block, axis_name: str, n_shards: int, body: Callable,
              init=None, shift: int = 1, slice_size: Optional[int] = None,
              fabric: Optional[str] = None):
    """Double-buffered ring pipeline over one mesh axis: the resident
    buffer starts as this shard's ``block`` and rotates ``shift``
    positions per step, so after ``n_shards`` steps every shard has
    seen every block — the decomposition of an all-gather-then-compute
    into P interleaved (transfer, compute) steps (arXiv 2112.09017's
    ring SUMMA). At step ``s`` the resident buffer is the block
    originally owned by shard ``(i + s*shift) mod n``;
    ``body(acc, resident, owner, s)`` folds it into the accumulator.

    The next hop's ``ppermute`` is issued BEFORE the step's ``body``
    and consumed only at the next step, so transfer ``s+1`` carries no
    data dependence on compute ``s`` — the double buffering the TPU
    scheduler needs to hide the DMA behind the MXU. Exactly
    ``n_shards - 1`` collective-permutes are emitted, interleaved with
    ``n_shards`` ``body`` calls (the ``assert_ring_schedule`` pin,
    ``utils/hlo.py``).

    ``slice_size`` (round 11) switches to the HIERARCHICAL hop
    schedule for an axis whose rank order is slice-blocked (runs of
    ``slice_size`` ICI-connected ranks, ``topology.slice_run``): the
    inner ring rotates within the slice block and only every
    ``slice_size``-th hop jumps a slice, so a full lap crosses DCN
    ``n/slice_size - 1`` times instead of on (up to) every hop. Same
    hop count, same double buffering, every block still visited
    exactly once — but the visit ORDER differs from the flat ring, so
    non-commutative accumulations see a different (equally valid)
    reduction order. ``fabric``: single-fabric byte attribution for
    the flat schedule on a classified mesh (``None`` = legacy
    counter)."""
    n = int(n_shards)
    L = int(slice_size) if slice_size else 0
    if 1 < L < n and n % L == 0 and shift == 1 and n > 1:
        return _ring_pass_hier(block, axis_name, n, body, init, L)
    with _trace.span("collective.ring_pass", cat="collective",
                     shape=getattr(block, "shape", None),
                     dtype=getattr(block, "dtype", None), axis=axis_name,
                     n_shards=n, shift=shift, hops=n - 1,
                     **({"fabric": fabric} if fabric else {}),
                     seq=_count_collective(
                         "ring_pass", _est_bytes(block, n - 1),
                         fabric=fabric)):
        i = lax.axis_index(axis_name)
        perm = [(r, (r - shift) % n) for r in range(n)]
        acc = init
        resident = block
        for s in range(n):
            nxt = (lax.ppermute(resident, axis_name, perm)
                   if s < n - 1 else None)
            owner = (i + s * shift) % n if n > 1 else i
            acc = body(acc, resident, owner, s)
            resident = nxt
        return acc


def _ring_pass_hier(block, axis_name, n: int, body: Callable, init,
                    ici: int):
    """Two-level ring schedule over one slice-blocked axis (see
    :func:`ring_pass`): the axis's ``n`` ranks fall in ``n//ici``
    slice blocks of ``ici`` ranks each. Inner hops rotate the resident
    buffer within the block (pure ICI); after each full inner lap one
    outer hop shifts every resident one block down (the lap's single
    DCN crossing — ``n//ici - 1`` total vs the flat ring's worst case
    of one per hop). Device ``r = (d, l)``'s resident before body call
    ``t`` (with ``k = t // ici`` outer hops done) is the block of
    owner ``((d+k) % D, (l + t-k) % ici)``; over ``t = 0..n-1`` that
    enumerates every owner exactly once."""
    dn = n // ici
    blk_bytes = _est_bytes(block)
    with _trace.span("collective.ring_pass", cat="collective",
                     shape=getattr(block, "shape", None),
                     dtype=getattr(block, "dtype", None), axis=axis_name,
                     n_shards=n, shift=1, hops=n - 1, hierarchical=True,
                     slice_size=ici,
                     seq=_count_collective(
                         "ring_pass",
                         nbytes_ici=(blk_bytes * dn * (ici - 1)
                                     if blk_bytes else None),
                         nbytes_dcn=(blk_bytes * (dn - 1)
                                     if blk_bytes else None))):
        r = lax.axis_index(axis_name)
        d, l = r // ici, r % ici
        perm_inner = [(q, (q // ici) * ici + ((q % ici) - 1) % ici)
                      for q in range(n)]
        perm_outer = [(q, (q - ici) % n) for q in range(n)]
        acc = init
        resident = block
        for t in range(n):
            if t < n - 1:
                perm = perm_outer if (t + 1) % ici == 0 else perm_inner
                nxt = lax.ppermute(resident, axis_name, perm)
            else:
                nxt = None
            k = t // ici
            owner = ((d + k) % dn) * ici + (l + (t - k)) % ici
            acc = body(acc, resident, owner, t)
            resident = nxt
        return acc


def ring_halo_ghosts(block, axis_name: str, n_shards: int,
                     front: int, back: int, valid_len, ax: int = 0,
                     slice_map: Optional[Sequence[int]] = None):
    """The 1-D ring halo exchange's two ghost slabs, WITHOUT stitching
    them onto the block: ``(front_ghost, back_ghost)`` — the
    predecessor's ``front`` valid tail rows and the successor's
    ``back`` first rows along array axis ``ax``, zero-filled at the
    domain edges (unpaired ``ppermute`` destinations), exactly the
    slabs :func:`halo_slab` would concatenate.

    Returning the slabs unstitched is the overlap lever: the stencil
    kernels issue these ``ppermute``\\ s FIRST, compute the interior
    rows (which need no ghosts) while the transfers fly, and patch only
    the ``front``/``back`` boundary rows from the received slabs
    (``ops/derivatives.py`` overlap path). ``None`` is returned for a
    zero-width side."""
    n = int(n_shards)
    nb_ici = nb_dcn = None
    if slice_map is not None and n > 1:
        try:
            row = block.size // block.shape[ax] * block.dtype.itemsize
        except (AttributeError, TypeError, ZeroDivisionError):
            row = None
        if row is not None:
            nb_ici = nb_dcn = 0
            for h, pairs in (
                    (front, [(r, r + 1) for r in range(n - 1)]),
                    (back, [(r, r - 1) for r in range(1, n)])):
                if not h:
                    continue
                cross = sum(1 for s, t in pairs
                            if slice_map[s] != slice_map[t])
                nb_ici += row * h * (len(pairs) - cross)
                nb_dcn += row * h * cross
            # per-device average, divided once at the end — a per-term
            # floor would zero out the few DCN-crossing pairs entirely
            nb_ici = -(-nb_ici // n)
            nb_dcn = -(-nb_dcn // n)
    with _trace.span("collective.ring_halo_ghosts", cat="collective",
                     shape=getattr(block, "shape", None),
                     dtype=getattr(block, "dtype", None), axis=axis_name,
                     n_shards=n, front=front, back=back, ax=ax,
                     **({"fabric": "split"} if nb_ici is not None else {}),
                     seq=_count_collective("ring_halo_ghosts",
                                           nbytes_ici=nb_ici,
                                           nbytes_dcn=nb_dcn)):
        gf = gb = None
        if front:
            start = jnp.maximum(valid_len - front, 0)
            slab = lax.dynamic_slice_in_dim(block, start, front, axis=ax)
            gf = lax.ppermute(slab, axis_name,
                              [(r, r + 1) for r in range(n - 1)])
        if back:
            slab = lax.slice_in_dim(block, 0, back, axis=ax)
            gb = lax.ppermute(slab, axis_name,
                              [(r, r - 1) for r in range(1, n)])
        return gf, gb


def resolve_chunks(width: int, n_shards: int, chunks: int,
                   where: str = "pencil transpose") -> int:
    """Usable chunk count for streaming a length-``width`` axis through
    tiled all-to-alls over ``n_shards`` devices: every chunk must carry
    at least one row per shard, so the count caps at
    ``width // n_shards``. A request that doesn't fit falls back (to
    the cap, or to 1 = the bulk schedule) with a logged note instead of
    erroring — the chunked path must degrade, never break, on small
    axes."""
    chunks = int(chunks)
    if chunks <= 1 or n_shards <= 1:
        return 1
    cap = max(1, int(width) // int(n_shards))
    if chunks > cap:
        _logger.info(
            "%s: comm_chunks=%d does not fit an axis of length %d over "
            "%d shards; falling back to %d chunk(s)",
            where, chunks, width, n_shards, cap)
        # structured twin of the log line: lands in the trace JSONL
        # artifact instead of scrolling away on stdout
        _trace.event("collective.resolve_chunks_fallback",
                     cat="fallback", where=where, requested=chunks,
                     width=int(width), n_shards=int(n_shards),
                     resolved=cap)
        return cap
    return chunks


def _pad_axis_to(x, axis: int, target: int):
    if x.shape[axis] == target:
        return x
    padw = [(0, 0)] * x.ndim
    padw[axis] = (0, target - x.shape[axis])
    return jnp.pad(x, padw)


def chunked_pencil_transpose(b, axis_name: str, n_shards: int,
                             out_ax: int, chunks: int, mid: Callable):
    """Streamed double pencil transpose for use *inside* a shard_map
    kernel: split ``out_ax`` into ``chunks`` tiles (padded to a
    ``chunks * n_shards`` multiple) and push each tile through
    ``all_to_all(split=out_ax, concat=0) → mid(tile) →
    all_to_all(split=0, concat=out_ax)`` independently. ``mid`` is the
    per-tile local work — the axis-0 transform/shift/repack section of
    the pencil FFT — which carries no cross-tile dependence, so tile
    ``k``'s transfers overlap tile ``k±1``'s transforms instead of the
    whole transpose barriering before any axis-0 compute (arXiv
    2112.01075's chunked redistribution). Emits exactly ``chunks``
    all-to-alls per transpose (the HLO pin). Returns the
    ``out_ax``-concatenated result at the padded width — the caller
    crops, exactly as after the bulk transpose."""
    K = int(chunks)
    tile = K * int(n_shards)
    bo = -(-b.shape[out_ax] // tile)
    with _trace.span("collective.chunked_pencil_transpose",
                     cat="collective", shape=b.shape, dtype=b.dtype,
                     axis=axis_name, n_shards=int(n_shards),
                     out_ax=out_ax, chunks=K,
                     a2a_per_transpose=K * (2 if n_shards > 1 else 0),
                     seq=_count_collective("chunked_pencil_transpose",
                                           _est_bytes(b, 2.0))):
        b = _pad_axis_to(b, out_ax, tile * bo)
        cw = n_shards * bo  # chunk width, divisible by the mesh size
        outs = []
        for k in range(K):
            ck = lax.slice_in_dim(b, k * cw, (k + 1) * cw, axis=out_ax)
            if n_shards > 1:
                ck = lax.all_to_all(ck, axis_name, split_axis=out_ax,
                                    concat_axis=0, tiled=True)
            ck = mid(ck)
            if n_shards > 1:
                ck = lax.all_to_all(ck, axis_name, split_axis=0,
                                    concat_axis=out_ax, tiled=True)
            outs.append(ck)
        return jnp.concatenate(outs, axis=out_ax) if K > 1 else outs[0]


def chunked_pencil_transpose_planes(br, bi, axis_name: str,
                                    n_shards: int, out_ax: int,
                                    chunks: int, mid: Callable):
    """Planar (re, im plane-pair) :func:`chunked_pencil_transpose`:
    each tile's transposes are ONE stacked real all-to-all apiece
    (:func:`plane_all_to_all`), ``mid(br_tile, bi_tile)`` returns the
    transformed pair. Same chunking/padding/crop contract."""
    K = int(chunks)
    tile = K * int(n_shards)
    bo = -(-br.shape[out_ax] // tile)
    with _trace.span("collective.chunked_pencil_transpose_planes",
                     cat="collective", shape=br.shape, dtype=br.dtype,
                     axis=axis_name, n_shards=int(n_shards),
                     out_ax=out_ax, chunks=K, planar=True,
                     seq=_count_collective(
                         "chunked_pencil_transpose_planes",
                         _est_bytes(br, 4.0))):
        br = _pad_axis_to(br, out_ax, tile * bo)
        bi = _pad_axis_to(bi, out_ax, tile * bo)
        cw = n_shards * bo
        outs_r, outs_i = [], []
        for k in range(K):
            cr = lax.slice_in_dim(br, k * cw, (k + 1) * cw, axis=out_ax)
            ci = lax.slice_in_dim(bi, k * cw, (k + 1) * cw, axis=out_ax)
            if n_shards > 1:
                cr, ci = plane_all_to_all(cr, ci, axis_name,
                                          split_axis=out_ax,
                                          concat_axis=0)
            cr, ci = mid(cr, ci)
            if n_shards > 1:
                cr, ci = plane_all_to_all(cr, ci, axis_name, split_axis=0,
                                          concat_axis=out_ax)
            outs_r.append(cr)
            outs_i.append(ci)
        if K > 1:
            return (jnp.concatenate(outs_r, axis=out_ax),
                    jnp.concatenate(outs_i, axis=out_ax))
        return outs_r[0], outs_i[0]


# --------------------------------------------------------------------------
# Topology-aware layer (round 11, PYLOPS_MPI_TPU_HIERARCHICAL): two-level
# schedules for hybrid (dcn x ici) meshes. Every flat collective above
# treats its axis as one uniform fabric; on a multi-slice pod that routes
# the dense shuffle over ~10 GB/s DCN links exactly like the ~100 GB/s
# ICI ones. The primitives here decompose each exchange into an
# intra-slice phase on the ICI axis plus one staged inter-slice phase on
# the DCN axis (arXiv 2112.09017's hierarchy, with arXiv 2112.01075's
# decomposition vocabulary), and stamp per-fabric byte counters
# (collective.*.bytes_ici / .bytes_dcn) so the split is visible to the
# round-9 aggregator and the round-11 cost model. All are for use INSIDE
# a shard_map kernel over a mesh holding both named axes; the fabric
# assignment comes from pylops_mpi_tpu.parallel.topology at the call
# site. With PYLOPS_MPI_TPU_HIERARCHICAL=off nothing here is reached and
# the flat programs stay bit-identical (the HLO pin in the tests).

def _hier_reorder(b, ax: int, d: int, i: int, inverse: bool = False):
    """Local column-block permutation pairing the two-level exchange
    with the flat combined-axis block order: the flat
    ``all_to_all(b, (dcn, ici), ...)`` deals axis-``ax`` blocks to
    devices in dcn-major rank order ``r = d*I + i``, while the
    ici-then-dcn two-phase exchange consumes them ici-major — so view
    the axis as ``(d, i, w)`` and swap the two leading factors before
    the phases (``inverse=True`` undoes it after the reverse
    phases). Pure local data movement, no collective."""
    w = b.shape[ax] // (d * i)
    pre, post = b.shape[:ax], b.shape[ax + 1:]
    f0, f1 = (i, d) if inverse else (d, i)
    b = b.reshape(pre + (f0, f1, w) + post)
    b = jnp.swapaxes(b, ax, ax + 1)
    return b.reshape(pre + (d * i * w,) + post)


def _hier_transpose_raw(b, dcn_axis: str, ici_axis: str, n_dcn: int,
                        n_ici: int, out_ax: int, forward: bool):
    """Span-free body of :func:`hier_pencil_transpose` (shared with the
    chunked/planar wrappers, which carry their own spans)."""
    d, i = int(n_dcn), int(n_ici)
    if forward:
        b = _hier_reorder(b, out_ax, d, i)
        if i > 1:
            b = lax.all_to_all(b, ici_axis, split_axis=out_ax,
                               concat_axis=0, tiled=True)
        if d > 1:
            b = lax.all_to_all(b, dcn_axis, split_axis=out_ax,
                               concat_axis=0, tiled=True)
        return b
    if d > 1:
        b = lax.all_to_all(b, dcn_axis, split_axis=0,
                           concat_axis=out_ax, tiled=True)
    if i > 1:
        b = lax.all_to_all(b, ici_axis, split_axis=0,
                           concat_axis=out_ax, tiled=True)
    return _hier_reorder(b, out_ax, d, i, inverse=True)


def hier_pencil_transpose(b, dcn_axis: str, ici_axis: str, n_dcn: int,
                          n_ici: int, out_ax: int, forward: bool = True):
    """Two-level pencil transpose for use *inside* a shard_map kernel
    over a hybrid mesh — bit-identical in result to the flat
    ``lax.all_to_all(b, (dcn_axis, ici_axis), split_axis=out_ax,
    concat_axis=0, tiled=True)`` (``forward``) / its inverse
    (``forward=False``), but scheduled as a local reorder + an
    intra-slice all-to-all on the ICI axis + ONE inter-slice all-to-all
    on the DCN axis. Each device's DCN payload drops from the portable
    flat decomposition's rotating volume to the direct
    ``(D-1)/D`` share of its shard — the "keep the dense shuffle on
    ICI" schedule of arXiv 2112.09017; the two phases are the
    ici/dcn factorization of arXiv 2112.01075's reshard algebra."""
    d, i = int(n_dcn), int(n_ici)
    L = _est_bytes(b)
    with _trace.span("collective.hier_pencil_transpose", cat="collective",
                     shape=b.shape, dtype=b.dtype, dcn_axis=dcn_axis,
                     ici_axis=ici_axis, n_dcn=d, n_ici=i, out_ax=out_ax,
                     forward=forward, fabric="split",
                     seq=_count_collective(
                         "hier_pencil_transpose",
                         nbytes_ici=(L * (i - 1) // i) if L else None,
                         nbytes_dcn=(L * (d - 1) // d) if L else None)):
        return _hier_transpose_raw(b, dcn_axis, ici_axis, d, i, out_ax,
                                   forward)


def hier_pencil_transpose_planes(br, bi, dcn_axis: str, ici_axis: str,
                                 n_dcn: int, n_ici: int, out_ax: int,
                                 forward: bool = True):
    """Planar (re, im plane-pair) :func:`hier_pencil_transpose`: the
    pair is stacked on a new trailing axis (same rationale as
    :func:`plane_all_to_all` — the pair members must ride together
    through the split) so each phase is ONE stacked real collective."""
    d, i = int(n_dcn), int(n_ici)
    L = _est_bytes(br, 2.0)
    with _trace.span("collective.hier_pencil_transpose_planes",
                     cat="collective", shape=br.shape, dtype=br.dtype,
                     dcn_axis=dcn_axis, ici_axis=ici_axis, n_dcn=d,
                     n_ici=i, out_ax=out_ax, forward=forward,
                     planar=True, fabric="split",
                     seq=_count_collective(
                         "hier_pencil_transpose_planes",
                         nbytes_ici=(L * (i - 1) // i) if L else None,
                         nbytes_dcn=(L * (d - 1) // d) if L else None)):
        s = jnp.stack([br, bi], axis=-1)
        s = _hier_transpose_raw(s, dcn_axis, ici_axis, d, i, out_ax,
                                forward)
        return s[..., 0], s[..., 1]


def hier_chunked_pencil_transpose(b, dcn_axis: str, ici_axis: str,
                                  n_dcn: int, n_ici: int, out_ax: int,
                                  chunks: int, mid: Callable):
    """Streamed double pencil transpose over a hybrid mesh — the
    two-level counterpart of :func:`chunked_pencil_transpose`: each of
    the ``chunks`` tiles runs reorder → ICI all-to-all → staged DCN
    all-to-all → ``mid`` → the reverse phases. The DCN exchange is
    thereby CHUNKED as well as staged: tile ``k``'s slow inter-slice
    transfer overlaps tile ``k±1``'s local transforms and ICI
    shuffles. Same padding/crop contract as the flat chunked
    transpose."""
    d, i = int(n_dcn), int(n_ici)
    n_shards = d * i
    K = int(chunks)
    tile = K * n_shards
    bo = -(-b.shape[out_ax] // tile)
    L = _est_bytes(b, 2.0)
    with _trace.span("collective.hier_chunked_pencil_transpose",
                     cat="collective", shape=b.shape, dtype=b.dtype,
                     dcn_axis=dcn_axis, ici_axis=ici_axis, n_dcn=d,
                     n_ici=i, out_ax=out_ax, chunks=K, fabric="split",
                     seq=_count_collective(
                         "hier_chunked_pencil_transpose",
                         nbytes_ici=(L * (i - 1) // i) if L else None,
                         nbytes_dcn=(L * (d - 1) // d) if L else None)):
        b = _pad_axis_to(b, out_ax, tile * bo)
        cw = n_shards * bo
        outs = []
        for k in range(K):
            ck = lax.slice_in_dim(b, k * cw, (k + 1) * cw, axis=out_ax)
            ck = _hier_transpose_raw(ck, dcn_axis, ici_axis, d, i,
                                     out_ax, True)
            ck = mid(ck)
            ck = _hier_transpose_raw(ck, dcn_axis, ici_axis, d, i,
                                     out_ax, False)
            outs.append(ck)
        return jnp.concatenate(outs, axis=out_ax) if K > 1 else outs[0]


def hier_chunked_pencil_transpose_planes(br, bi, dcn_axis: str,
                                         ici_axis: str, n_dcn: int,
                                         n_ici: int, out_ax: int,
                                         chunks: int, mid: Callable):
    """Planar :func:`hier_chunked_pencil_transpose`: per tile, ONE
    stacked real collective per phase, ``mid(br_tile, bi_tile)``
    returns the transformed pair."""
    d, i = int(n_dcn), int(n_ici)
    n_shards = d * i
    K = int(chunks)
    tile = K * n_shards
    bo = -(-br.shape[out_ax] // tile)
    L = _est_bytes(br, 4.0)
    with _trace.span("collective.hier_chunked_pencil_transpose_planes",
                     cat="collective", shape=br.shape, dtype=br.dtype,
                     dcn_axis=dcn_axis, ici_axis=ici_axis, n_dcn=d,
                     n_ici=i, out_ax=out_ax, chunks=K, planar=True,
                     fabric="split",
                     seq=_count_collective(
                         "hier_chunked_pencil_transpose_planes",
                         nbytes_ici=(L * (i - 1) // i) if L else None,
                         nbytes_dcn=(L * (d - 1) // d) if L else None)):
        br = _pad_axis_to(br, out_ax, tile * bo)
        bi = _pad_axis_to(bi, out_ax, tile * bo)
        cw = n_shards * bo
        outs_r, outs_i = [], []
        for k in range(K):
            cr = lax.slice_in_dim(br, k * cw, (k + 1) * cw, axis=out_ax)
            ci = lax.slice_in_dim(bi, k * cw, (k + 1) * cw, axis=out_ax)
            s = jnp.stack([cr, ci], axis=-1)
            s = _hier_transpose_raw(s, dcn_axis, ici_axis, d, i,
                                    out_ax, True)
            cr, ci = mid(s[..., 0], s[..., 1])
            s = jnp.stack([cr, ci], axis=-1)
            s = _hier_transpose_raw(s, dcn_axis, ici_axis, d, i,
                                    out_ax, False)
            outs_r.append(s[..., 0])
            outs_i.append(s[..., 1])
        if K > 1:
            return (jnp.concatenate(outs_r, axis=out_ax),
                    jnp.concatenate(outs_i, axis=out_ax))
        return outs_r[0], outs_i[0]


def hier_psum_scatter(x, dcn_axis: str, ici_axis: str, n_dcn: int,
                      n_ici: int, dim: int = 0):
    """Two-level reduce-scatter for use *inside* a shard_map kernel
    over a hybrid mesh — value-equivalent (up to floating-point
    reduction order) to ``lax.psum_scatter(x, (dcn_axis, ici_axis),
    scatter_dimension=dim, tiled=True)``: a local reorder to ici-major
    block order, the inner reduce-scatter over the ICI ring (full
    payload, fast fabric), then the outer reduce-scatter over the DCN
    axis on the ALREADY 1/P_ici-sized partials — the slow fabric moves
    ``P_ici`` times fewer bytes than a flat decomposition would push
    through it. Requires ``x.shape[dim]`` divisible by
    ``n_dcn * n_ici``."""
    d, i = int(n_dcn), int(n_ici)
    L = _est_bytes(x)
    with _trace.span("collective.hier_psum_scatter", cat="collective",
                     shape=x.shape, dtype=x.dtype, dcn_axis=dcn_axis,
                     ici_axis=ici_axis, n_dcn=d, n_ici=i, dim=dim,
                     fabric="split",
                     seq=_count_collective(
                         "hier_psum_scatter",
                         nbytes_ici=(L * (i - 1) // i) if L else None,
                         nbytes_dcn=(L * (d - 1) // (d * i))
                         if L else None)):
        x = _hier_reorder(x, dim, d, i)
        if i > 1:
            x = lax.psum_scatter(x, ici_axis, scatter_dimension=dim,
                                 tiled=True)
        if d > 1:
            x = lax.psum_scatter(x, dcn_axis, scatter_dimension=dim,
                                 tiled=True)
        return x


def hier_all_gather(x, dcn_axis: str, ici_axis: str, n_dcn: int,
                    n_ici: int, dim: int = 0):
    """Two-level all-gather for use *inside* a shard_map kernel over a
    hybrid mesh — bit-identical in result to ``lax.all_gather(x,
    (dcn_axis, ici_axis), axis=dim, tiled=True)``: gather the slice's
    shards over the ICI axis first, then exchange the assembled
    per-slice superblocks over the DCN axis — ``P_ici`` times FEWER,
    larger DCN messages (one per slice pair instead of one per device
    pair), the latency shape DCN wants (arXiv 2112.09017's
    slice-leader staging)."""
    d, i = int(n_dcn), int(n_ici)
    L = _est_bytes(x)
    with _trace.span("collective.hier_all_gather", cat="collective",
                     shape=x.shape, dtype=x.dtype, dcn_axis=dcn_axis,
                     ici_axis=ici_axis, n_dcn=d, n_ici=i, dim=dim,
                     fabric="split",
                     seq=_count_collective(
                         "hier_all_gather",
                         nbytes_ici=(L * (i - 1)) if L else None,
                         nbytes_dcn=(L * i * (d - 1)) if L else None)):
        if i > 1:
            x = lax.all_gather(x, ici_axis, axis=dim, tiled=True)
        if d > 1:
            x = lax.all_gather(x, dcn_axis, axis=dim, tiled=True)
        return x


def ring_halo_extend(block, axis_name: str, n_shards: int,
                     front: int = 0, back: int = 0):
    """In-kernel ring ghost exchange over the 1-D mesh axis: extends the
    local ``block`` along array axis 0 with the predecessor's last
    ``front`` rows and the successor's first ``back`` rows, zero-filled
    at the domain edges — one ``ppermute`` hop per direction, boundary
    slabs only. The structural analog of ring attention's neighbour
    pass and the explicit form of the ghost-cell Send/Recv chain in
    ref ``pylops_mpi/DistributedArray.py:877-954``. The 1-D
    un-padded special case of :func:`cart_halo_extend` (which the
    production stencil/ghost kernels reach through
    :func:`halo_slab`)."""
    return cart_halo_extend(block, axis_name, (int(n_shards),), 0,
                            front, back, valid_len=block.shape[0])
