"""Distributed dense matrix multiplication (tensor parallelism).

Rebuild of ``pylops_mpi/basicoperators/MatrixMult.py`` — the reference's
two schemes:

- **block** (ref ``178-427``): A row-blocked, X/Y column-blocked over a
  √P×√P grid; forward does a row-communicator allgather, adjoint a
  row-communicator allreduce.
- **SUMMA** (ref ``430-765``): 2-D tiles, √P iterations of row/col
  broadcasts + local GEMM accumulate; the adjoint pipelines Aᴴ tiles
  with tagged p2p sends.

TPU-native: both become one ``einsum`` on the MXU under sharding
constraints. ``kind="block"`` shards A by rows on the 1-D mesh
(forward: zero comm; adjoint: one ``psum``). ``kind="summa"`` tiles A,
X and Y over a 2-D mesh and runs an explicit ``shard_map`` kernel —
all-gather A-tiles along grid columns, all-gather X-tiles along grid
rows, then a single local GEMM: the √P-step broadcast pipeline of the
reference collapses into one collective + one MXU-saturating GEMM
(the tagged-p2p adjoint pipeline, ref ``744-761``, becomes the mirrored
all-gather — SURVEY §7 hard-part resolved). ``kind="auto"`` lays the
same tiling down as sharding constraints and lets XLA's SPMD partitioner
derive the schedule.

Deliberate departure: the reference's flat model vector physically
replicates X across grid rows (its global length is ``K * Σ_ranks
M_loc ≈ K·M·√P``, ref ``306-316``); here model and data are the unique
``(K·M,)`` / ``(N·M,)`` vectors — same operator, no duplicated storage.

Grid helpers mirror ref ``MatrixMult.py:24-175``: ``active_grid_comm``
is the reference-faithful analog (largest square grid, surplus devices
idle); ``best_grid_2d`` is the preferred no-idle alternative (factors P
into the most-square grid); ``local_block_split`` gives tile ownership
slices, ``block_gather`` reassembles a tiled matrix.
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..distributedarray import DistributedArray, Partition, local_split
from ..linearoperator import MPILinearOperator
from ..parallel.mesh import default_mesh, make_mesh_2d, best_grid_2d

__all__ = ["MPIMatrixMult", "active_grid_comm", "local_block_split",
           "block_gather"]


def active_grid_comm(N: int, M: int, n_devices: Optional[int] = None,
                     axis_names: Tuple[str, str] = ("r", "c")):
    """Largest-square active process grid for a distributed matmul —
    one-controller analog of ref ``MatrixMult.py:24-79``
    (``active_grid_comm(base_comm, N, M)``).

    The reference assigns every MPI rank a position in a ``P'×P'``
    logical grid (``P' = isqrt(P)``), caps the active dimension by
    ``min(N, M)``, and returns a sub-communicator of the active ranks
    (inactive ranks idle). Here there are no per-rank return values:
    the same selection yields a 2-D :class:`jax.sharding.Mesh` over the
    active devices only.

    Returns ``(mesh, grid, active_ids, is_full)``: the active 2-D mesh,
    its ``(d, d)`` grid shape, the flat indices (into ``jax.devices()``)
    of the participating devices in row-major grid order, and whether
    every device participates. Prefer :func:`best_grid_2d` (which
    factors the device count so nothing idles) when grid squareness is
    not required.
    """
    devs = jax.devices()
    if n_devices is None:
        n_devices = len(devs)
    if n_devices > len(devs):
        raise ValueError(
            f"requested {n_devices} devices but only {len(devs)} available")
    p_prime = int(np.sqrt(n_devices))
    d = max(1, min(int(N), int(M), p_prime))
    # row-major positions of the active sub-grid within the P'x P' grid
    active_ids = [r * p_prime + c for r in range(d) for c in range(d)]
    mesh = Mesh(np.asarray([devs[i] for i in active_ids]).reshape(d, d),
                axis_names)
    return mesh, (d, d), active_ids, len(active_ids) == n_devices


def local_block_split(global_shape: Tuple[int, int], rank: int,
                      grid: Tuple[int, int]) -> Tuple[slice, slice]:
    """Tile ownership of a 2-D block layout
    (ref ``MatrixMult.py:82-129``): grid position (i, j) of ``rank`` owns
    ``ceil``-sized block (i, j)."""
    pr, pc = grid
    i, j = divmod(rank, pc)
    if not (0 <= i < pr and 0 <= j < pc):
        raise ValueError(f"rank {rank} outside grid {grid}")
    br = int(np.ceil(global_shape[0] / pr))
    bc = int(np.ceil(global_shape[1] / pc))
    return (slice(i * br, min((i + 1) * br, global_shape[0])),
            slice(j * bc, min((j + 1) * bc, global_shape[1])))


def block_gather(blocks, global_shape: Tuple[int, int],
                 grid: Tuple[int, int]) -> np.ndarray:
    """Reassemble a list of per-rank tiles (row-major rank order) into the
    dense matrix (ref ``block_gather``, ``MatrixMult.py:132-175``)."""
    out = np.zeros(global_shape, dtype=np.asarray(blocks[0]).dtype)
    for rank, blk in enumerate(blocks):
        rs, cs = local_block_split(global_shape, rank, grid)
        out[rs, cs] = np.asarray(blk)
    return out


def _pad_to(x: jax.Array, rows: int, cols: int) -> jax.Array:
    """Zero-pad ``x`` to ``(rows, cols)``; ``x`` itself when it already
    has that shape (``jnp.pad`` by zero widths would copy it)."""
    dr, dc = rows - x.shape[0], cols - x.shape[1]
    if dr == 0 and dc == 0:
        return x
    return jnp.pad(x, ((0, dr), (0, dc)))


def _unpad(x: jax.Array, rows: int, cols: int) -> jax.Array:
    """Leading ``(rows, cols)`` block of ``x``; ``x`` itself when that
    is all of it."""
    return x if x.shape == (rows, cols) else x[:rows, :cols]


def _ring_pays(cols_per_hop: int) -> bool:
    """Whether the ring form of SUMMA's adjoint beats its bulk form
    when one hop's GEMM gets ``cols_per_hop`` columns — the rule behind
    ``overlap="auto"`` on a TPU. The ring reads the whole resident tile
    once a hop, ``pc`` times a product; the bulk kernel reads it once.
    The stationary-A forward had a ring of the same shape (a ring
    reduce-scatter in place of ``psum_scatter``); it won no row below
    and is gone, so the forward's column is why it has none.

    Measured on the SAME tiles, ring / bulk in ms, best of 3 x 10 jitted
    calls, TPU v5e, f32 under ``highest`` (PERF.md section 6, PR 28; the
    rows at 128 and 512 repeated to 0.05 % in a second process). A
    65,536^2 on 2 x 2 (4.29 GB tiles) unless said:

    ====================  ======================  ======================
    columns a hop         forward                 adjoint
    ====================  ======================  ======================
    8                     11.83 / 6.18   bulk     11.76 / 5.99   bulk
    32                    12.64 / 6.72   bulk     12.11 / 6.58   bulk
    32, bf16 tiles        6.51 / 3.79    bulk     6.32 / 3.51    bulk
    32, 1.07 GB tiles     3.44 / 1.97    bulk     3.19 / 1.88    bulk
    16, 1 x 4 grid        23.84 / 6.85   bulk     23.55 / 10.10  bulk
    64                    13.54 / 10.90  bulk     13.23 / 10.90  bulk
    128                   20.09 / 21.34  (ring)   21.03 / 18.88  bulk
    256                   43.31 / 39.74  bulk     37.93 / 38.15  ring
    512                   82.88 / 80.74  bulk     74.98 / 76.17  ring
    512, bf16 tiles       43.20 / 42.85  bulk     41.14 / 42.62  ring
    512, 1.07 GB tiles    22.45 / 21.74  bulk     20.26 / 20.75  ring
    512, 1 x 4 grid       161.08 / 160.64  bulk   142.55 / 144.68  ring
    1024                  165.08 / 161.90  bulk   151.12 / 152.73  ring
    2048                  329.98 / 323.64  bulk   301.98 / 305.33  ring
    ====================  ======================  ======================

    Below 128 columns a hop's GEMM is bound by the tile's bytes, so
    the ring doubles the product; at 128 it still runs 10 % under the
    MXU rate of a 256-column one. From 256 the split costs nothing and
    the adjoint's ring hides its hop of Y (0.6-3.5 % of the product);
    the forward's ring reduce-scatter stays 0.3-9 % behind the bulk
    ``psum_scatter`` wherever the MXU binds. The tile's bytes, its
    itemsize and ``pc`` moved no row across. The forward at 128 is the
    one row against the rule: the bulk 256-column GEMM is slow there
    (25.8 TFLOP/s against 27.7 at 512), its neighbours at 64 and 256
    and the adjoint at 128 go the other way, and the hop it could hide
    is 16 MB — one shape's tiling, not chased.
    """
    return cols_per_hop >= 256


class _MatMulBase(MPILinearOperator):
    # subclasses whose adjoint never reads At set this False
    # (see _MPISummaMatrixMult: its kernels use the sharded Ap tiles)
    _uses_At = True
    # K model columns fold into the GEMM's existing column dimension
    # (M -> M*K) — same kernels, widened contraction, no per-column loop
    accepts_block = True

    def __init__(self, A, M: int, mesh=None, dtype=None, saveAt: bool = False,
                 compute_dtype=None):
        A = jnp.asarray(A, dtype=dtype)
        self.N, self.K = A.shape
        self.M = int(M)
        self.mesh = mesh if mesh is not None else default_mesh()
        self.saveAt = saveAt
        self.dims = (self.K, self.M)
        self.dimsd = (self.N, self.M)
        super().__init__(shape=(self.N * self.M, self.K * self.M),
                         dtype=dtype or A.dtype)
        # bf16 tile storage with f32 MXU accumulation (same lever as
        # MPIBlockDiag's compute_dtype): halves the HBM traffic of the
        # bandwidth-bound matvec on TPU. Real f32 operators only.
        if compute_dtype is not None and np.dtype(self.dtype) != np.float32:
            raise ValueError(
                "compute_dtype is only supported for real float32 "
                f"operators, dtype is {self.dtype}")
        if compute_dtype is None:  # env-policy default (f32 only)
            from ._precision import default_compute_dtype
            compute_dtype = default_compute_dtype(self.dtype)
        self.compute_dtype = compute_dtype
        self._place_A(A)
        # adjoint reuses conj(A) tiles on the fly unless saveAt
        # (ref MatrixMult.py:288-292); stored at compute_dtype so the
        # saveAt copy gets the same storage/cast savings. The SUMMA
        # variant's adjoint kernel works on its sharded Ap tiles and
        # never reads At — it sets _uses_At = False so no dead K×N
        # copy is allocated.
        self.At = None
        if saveAt and self._uses_At:
            At = jnp.conj(A).T
            self.At = At.astype(compute_dtype) if compute_dtype is not None \
                else At

    def _gemm(self, a, b):
        """Local GEMM honouring compute_dtype: the matrix operand ``a``
        is already STORED narrow (``_place_A``) and enters the GEMM
        narrow — that is the HBM/wire lever; the vector/tile operand
        ``b`` stays at its own dtype (never round the solver's vectors
        per iteration — ops/_precision.py module doc) and the product
        accumulates in f32."""
        if self.compute_dtype is None:
            return a @ b
        out = jnp.matmul(a, b, preferred_element_type=jnp.float32)
        return out.astype(self.dtype)

    def _place_A(self, A):
        """Store the matrix the kernels read, in the layout and dtype
        they read it in (``self.A``; the SUMMA variant stores tiles)."""
        self.A = A

    def _fold_in(self, x: DistributedArray, nrows: int):
        """Reshape the flat model/data vector into the 2-D GEMM operand.

        Plain ``(nrows*M,)`` input gives the usual ``(nrows, M)``; a
        block ``(nrows*M, K)`` input folds its K columns into the GEMM
        columns — ``(nrows, M*K)`` — so every schedule below moves K
        columns per step with zero structural change. Returns
        ``(operand, ncol)`` with ``ncol=None`` for the vector case.
        """
        if x.ndim == 2:
            ncol = int(x.global_shape[1])
            return (x.array.reshape(nrows, self.M, ncol)
                    .reshape(nrows, self.M * ncol)), ncol
        return x.array.reshape(nrows, self.M), None

    def _wrap_out(self, arr: jax.Array, x: DistributedArray,
                  nrows: int, ncol=None) -> DistributedArray:
        gshape = nrows * self.M if ncol is None else (nrows * self.M, ncol)
        y = DistributedArray(global_shape=gshape, mesh=x.mesh,
                             partition=Partition.SCATTER, axis=0,
                             mask=x.mask, dtype=arr.dtype)
        if ncol is None:
            y[:] = arr.ravel()
        else:
            y[:] = arr.reshape(nrows, self.M, ncol).reshape(-1, ncol)
        return y


class _MPIBlockMatrixMult(_MatMulBase):
    """1-D block variant (ref ``MatrixMult.py:178-427``): A row-sharded
    over the mesh; forward is comm-free, adjoint is one psum (emitted by
    the partitioner for the row-contraction)."""

    def _place_A(self, A):
        from ..parallel.mesh import axis_sharding
        if self.compute_dtype is not None:
            A = A.astype(self.compute_dtype)
        try:
            A = jax.device_put(A, axis_sharding(self.mesh, 2, 0))
        except ValueError:
            pass  # rows not divisible by P: let XLA choose placement
        self.A = A

    def _matvec(self, x: DistributedArray) -> DistributedArray:
        X, ncol = self._fold_in(x, self.K)
        Y = self._gemm(self.A, X)           # (N, M[*K]) row-sharded
        return self._wrap_out(Y, x, self.N, ncol)

    def _rmatvec(self, x: DistributedArray) -> DistributedArray:
        Y, ncol = self._fold_in(x, self.N)
        At = self.At if self.At is not None else jnp.conj(self.A).T
        X = self._gemm(At, Y)               # sharded-N contraction → psum
        return self._wrap_out(X, x, self.K, ncol)


class _MPISummaMatrixMult(_MatMulBase):
    """2-D SUMMA variant (ref ``MatrixMult.py:430-765``) as an explicit
    shard_map kernel over an (r, c) mesh.

    The operator holds ONE copy of the matrix: ``Ap``, zero-padded to
    grid multiples, tiled ``P("r", "c")`` over the grid, the registered
    pytree child — so the operator is a jit argument of every fused
    solver and no compiled program embeds the matrix. ``A`` is a
    property over the tiles. A device array that already has that
    sharding and needs no padding (and no ``compute_dtype`` cast) is
    taken as it is, not copied: a matrix too large for one chip is
    generated in that sharding and handed over.

    Two forward schedules, chosen by per-device communication volume at
    construction (``schedule="auto"``):

    - ``"gather"``: all-gather the A row-block along ``c`` and the X
      column along ``r``, one local GEMM — the direct collapse of the
      reference's √P broadcast pipeline. Optimal for square-ish X.
    - ``"stat_a"``: A never moves. All-gather the (small) X fully,
      GEMM against the owned A tile's k-block, reduce-scatter the
      partial product along ``c``. For skinny X (M ≪ K — every
      matvec-shaped apply, e.g. the flagship's M=64 against K=4096)
      this moves ~A-row/X-col fewer bytes per call (round-5: 6.7×
      fewer at the component-bench shape). The adjoint has always
      been stationary-A (gather Y, GEMM, psum).

    ``overlap`` (``PYLOPS_MPI_TPU_OVERLAP``) chooses between the bulk
    kernels and the ring-pipelined forms two of them have (round 8,
    arXiv 2112.09017): the bulk collective along ``c`` decomposes into
    ``pc - 1`` double-buffered ``ppermute`` hops interleaved with ``pc``
    per-block GEMMs
    (:func:`~pylops_mpi_tpu.parallel.collectives.ring_pass`), so each
    hop's ICI transfer can hide behind the resident block's MXU work:

    - gather/ring: A tiles rotate along ``c``; each step GEMMs the
      resident tile against its k-slice of the gathered X column.
    - adjoint/ring: Y tiles rotate along ``c``; each step's GEMM fills
      the owner's M-column chunk; the ``r`` psum is unchanged.

    The stationary-A forward has one kernel, the bulk one
    (``psum_scatter``): its ring reduce-scatter lost to it in every row
    the chip measured (:func:`_ring_pays`) and was removed.

    A word — ``overlap=True/False/"on"/"off"``, a pinned
    ``PYLOPS_MPI_TPU_OVERLAP=on|off``, a tuner plan — means the ring
    where a ring exists (the gather forward, the adjoint) or the bulk
    form, at every shape. ``auto`` (nothing said) is bulk off a TPU.
    On a TPU the attribute reads ``"auto"`` and the adjoint's choice is
    made per traced apply: its ring reads the whole resident tile once
    a HOP (``pc`` times a product where the bulk form reads it once),
    so it asks :func:`_ring_pays` with the columns one hop's GEMM gets
    — from the operand's own width, so a block input that widens M to
    M*K decides by M*K — and a skinny right-hand side takes the bulk
    form (the measured rows are in that docstring). gather/ring reads
    each tile once and rings under ``auto`` on a TPU as before (not
    measured). Each adjoint decision leaves a ``summa.ring_select``
    trace event (``kernel``, ``cols_per_hop``, ``tile_bytes``,
    ``ring``, ``source``: ``rule``/``kwarg``/``env``/``plan``); the
    forward leaves none. ``off`` keeps the bulk kernels bit-identical;
    the ring reorders the floating-point accumulation (per-block
    partial sums) and matches within dtype tolerance.

    ``hierarchical`` (``PYLOPS_MPI_TPU_HIERARCHICAL``, round 11): on a
    hybrid mesh the (r, c) grid inherits the base mesh's dcn-major
    device order, so an aligned grid (the 8-device default: r spans
    slices, c stays inside one) already keeps the hot ``c``-axis
    collectives on ICI — enabling ``hierarchical`` activates the
    fabric-aligned cost/byte attribution (``_hier``) and, when the
    ``c`` axis DOES span slices (e.g. a ``(1, P)`` grid), switches the
    ring kernels to the two-level hop schedule
    (:func:`~pylops_mpi_tpu.parallel.collectives.ring_pass` with
    ``slice_size``): inner hops rotate within a slice on ICI and only
    one hop per inner lap crosses DCN. ``off`` keeps every kernel
    bit-identical to the flat build.
    """

    _uses_At = False

    def __init__(self, A, M: int, mesh=None, dtype=None, saveAt: bool = False,
                 grid: Optional[Tuple[int, int]] = None, compute_dtype=None,
                 schedule: str = "auto", overlap=None, hierarchical=None):
        from ..utils.deps import overlap_enabled, hierarchical_enabled
        base = mesh if mesh is not None else default_mesh()
        ndev = int(base.devices.size)
        self.grid = grid if grid is not None else best_grid_2d(ndev)
        if schedule not in ("auto", "gather", "stat_a"):
            raise ValueError(f"schedule={schedule!r}: expected "
                             "'auto', 'gather' or 'stat_a'")
        # autotuner seam (round 10): fill ONLY the knobs left at their
        # sentinels (schedule="auto" / overlap=None / hierarchical=None)
        # from the plan — explicit kwargs AND explicit env pins
        # (PYLOPS_MPI_TPU_OVERLAP / _HIERARCHICAL = on|off) always beat
        # the tuner; PYLOPS_MPI_TPU_TUNE=off returns None here and
        # everything below is untouched
        from ..utils.deps import overlap_env_pinned, hierarchical_env_pinned
        want_overlap = overlap is None and not overlap_env_pinned()
        want_hier = hierarchical is None and not hierarchical_env_pinned()
        tplan = None
        if schedule == "auto" or want_overlap or want_hier:
            tplan = self._consult_plan(A, M, base, dtype,
                                       compute_dtype)
        # who chose between ring and bulk: a word (kwarg, env pin, tuner
        # plan) holds at every shape; with nothing said the adjoint
        # asks _ring_pays per traced apply
        if overlap is None:
            self._overlap_source = "rule" if want_overlap else "env"
        else:
            self._overlap_source = ("rule" if str(overlap).strip().lower()
                                    == "auto" else "kwarg")
        if want_overlap and tplan is not None \
                and tplan.get("overlap") in ("on", "off"):
            overlap = tplan.get("overlap")
            self._overlap_source = "plan"
        if want_hier and tplan is not None \
                and tplan.get("hierarchical") in ("auto", "on", "off"):
            hierarchical = tplan.get("hierarchical")
        # True/False: a word, or auto off a TPU (bulk). "auto": left
        # open on a TPU — the gather forward rings as it always has
        # there, the adjoint decides by the rule
        self.overlap = overlap_enabled(overlap)
        if self.overlap and self._overlap_source == "rule":
            self.overlap = "auto"
        self.mesh2 = Mesh(base.devices.reshape(self.grid), ("r", "c"))
        # fabric classification of the 2-D grid (round 11): `_hier`
        # turns on the per-fabric cost/byte attribution; `_ring_slice`
        # is non-None only when the ring axis 'c' spans slices in
        # contiguous blocks — the shape the two-level hop schedule
        # stages. Both stay False/None on flat meshes and under
        # hierarchical=off, keeping the kernels (and their HLO)
        # untouched.
        from ..parallel import topology as _topo
        self._hier = False
        self._ring_slice = None
        self._fab_c = None
        fr = _topo.axis_fabric(self.mesh2, "r")
        fc = _topo.axis_fabric(self.mesh2, "c")
        if "dcn" in (fr, fc):  # multi-slice device set (not plain flat)
            self._fab_c = fc
            if hierarchical_enabled(hierarchical):
                self._hier = True
                if fc == "dcn":
                    self._ring_slice = _topo.slice_run(self.mesh2, "c")
        super().__init__(A, M, mesh=base, dtype=dtype, saveAt=saveAt,
                         compute_dtype=compute_dtype)
        from ..diagnostics import trace
        # what the kernels hold per device, and whether building it
        # copied the caller's matrix: both ride on the selection event
        held = dict(tile_bytes=int(self.Ap.nbytes) // int(np.prod(self.grid)),
                    copied=int(self.Ap is not A))
        if schedule == "auto" and tplan is not None \
                and tplan.get("schedule") in ("gather", "stat_a"):
            schedule = tplan.get("schedule")
            trace.event("summa.schedule_select", cat="schedule",
                        schedule=schedule, grid=self.grid,
                        shape=(self.N, self.K, self.M),
                        source=tplan.provenance,
                        overlap=self.overlap, **held)
        elif schedule == "auto":
            # per-device elements received per forward apply — the
            # comm-volume model now lives in diagnostics/costmodel.py
            # (shared with the roofline/bench layer; previously
            # private to this auto-select)
            from ..diagnostics.costmodel import summa_comm_volume
            vols = summa_comm_volume(self.N, self.K, self.M, self.grid)
            schedule = ("stat_a" if vols["stat_a"] < vols["gather"]
                        else "gather")
            # structured twin of the (previously undocumented)
            # selection decision: lands in the trace JSONL artifact
            trace.event("summa.schedule_select", cat="schedule",
                        schedule=schedule, grid=self.grid,
                        shape=(self.N, self.K, self.M),
                        vol_gather=vols["gather"],
                        vol_stat_a=vols["stat_a"],
                        overlap=self.overlap, **held)
        self.schedule = schedule

    def _consult_plan(self, A, M, base, dtype, compute_dtype):
        """``tuning.get_plan`` for this construction (None when
        ``PYLOPS_MPI_TPU_TUNE=off``). Under mode ``auto`` the factory
        lets a cache miss be MEASURED in place: candidate operators
        are built with explicit schedule/overlap kwargs (which never
        re-enter the tuner) and one forward apply is timed per trial,
        all inside the ``tune`` stage budget."""
        from ..tuning import plan as _tuneplan
        shp = np.shape(A)
        if len(shp) != 2:
            return None
        N_, K_ = int(shp[0]), int(shp[1])

        def factory(params):
            from ..distributedarray import DistributedArray
            op = _MPISummaMatrixMult(
                A, M, mesh=base, dtype=dtype, saveAt=False,
                grid=self.grid, compute_dtype=compute_dtype,
                schedule=params["schedule"], overlap=params["overlap"],
                hierarchical=params.get("hierarchical"))
            x = np.zeros(K_ * int(M), dtype=op.dtype)
            dx = DistributedArray.to_dist(x, mesh=base)
            return lambda: jax.block_until_ready(op.matvec(dx).array)

        from ..utils.deps import batch_default
        return _tuneplan.get_plan(
            "matrixmult", shape=(N_, K_, int(M)),
            dtype=dtype if dtype is not None else getattr(A, "dtype", None),
            mesh=base, extra={"grid": tuple(int(g) for g in self.grid),
                              "batch": batch_default()},
            factory=factory)

    def _place_A(self, A):
        """Pad + tile A once, eagerly, and commit it to the 2-D mesh as
        ``self.Ap`` — the ONE copy of the matrix this operator holds,
        the registered pytree child every kernel reads. (Padding
        inside the traced apply would make XLA constant-fold a full
        copy of A at compile time.) Stored at ``compute_dtype`` when
        set — bf16 tiles also halve the all-gather bytes on the wire,
        not just HBM reads (``self.compute_dtype``, not the ctor arg:
        the env policy may have filled it in). An array that needs no
        padding, no cast and already has the tiles' sharding comes
        through every step below as itself (``device_put`` to the
        sharding an array has returns that array): same buffers,
        nothing copied."""
        pr, pc = self.grid
        # padded tile sizes (ref pads to grid multiples, MatrixMult.py:589-601)
        self.Np = pr * int(np.ceil(self.N / pr))
        self.Kp_r = pr * int(np.ceil(self.K / pr))
        self.Kp_c = pc * int(np.ceil(self.K / pc))
        self.Mp = pc * int(np.ceil(self.M / pc))
        Ap = _pad_to(A, self.Np, self.Kp_c)
        if self.compute_dtype is not None \
                and Ap.dtype != np.dtype(self.compute_dtype):
            Ap = Ap.astype(self.compute_dtype)
        self.Ap = jax.device_put(
            Ap, NamedSharding(self.mesh2, P("r", "c")))

    @property
    def A(self) -> jax.Array:
        """The logical ``(N, K)`` matrix as a view over the tiles
        (``todense``-style debugging, the autodiff rules): the tiles
        themselves when nothing was padded, else their leading block.
        At the tiles' dtype — ``compute_dtype`` when that is set."""
        return _unpad(self.Ap, self.N, self.K)

    def _gemm(self, a, b):
        # the local GEMM under a scope of its own: under
        # pmt.collective.ring_pass the GEMMs and the hops then separate
        # by innermost scope in a device trace
        from ..diagnostics import trace
        with trace.span("summa.gemm", cat="kernel"):
            return super()._gemm(a, b)

    def _kernel_fwd(self, Ablk, Xblk):
        # Ablk: (Np/pr, Kp_c/pc) tile; Xblk: (Kp_r... ) — gather full
        # row of A along 'c' and full column of X along 'r', one GEMM.
        # Under compute_dtype the A tiles are narrow on the wire AND in
        # HBM; X gathers at its own (wide) dtype — rounding the model
        # vector per apply is the recurrence contamination the
        # precision policy forbids (ops/_precision.py).
        Arow = lax.all_gather(Ablk, "c", axis=1, tiled=True)   # (Np/pr, Kp_c)
        Xcol = lax.all_gather(Xblk, "r", axis=0, tiled=True)   # (Kp_r, Mp/pc)
        return self._gemm(Arow[:, :self.K], Xcol[:self.K])

    def _kernel_fwd_stat_a(self, Ablk, Xblk):
        # stationary-A: gather the skinny X fully, GEMM the owned A
        # tile against its k-block, reduce-scatter partials along 'c'.
        # Zero bytes of A on the wire; padding is benign because X's
        # pad rows are zeros (they meet A's pad columns in the GEMM).
        # X gathers wide (see _kernel_fwd note).
        Xfull = lax.all_gather(Xblk, "r", axis=0, tiled=True)   # (Kp_r, Mp/pc)
        Xfull = lax.all_gather(Xfull, "c", axis=1, tiled=True)  # (Kp_r, Mp)
        if self.Kp_c > self.Kp_r:
            Xfull = jnp.pad(Xfull, ((0, self.Kp_c - self.Kp_r), (0, 0)))
        kb = self.Kp_c // self.grid[1]
        c = lax.axis_index("c")
        Xk = lax.dynamic_slice_in_dim(Xfull, c * kb, kb, axis=0)
        part = self._gemm(Ablk, Xk)                             # (Np/pr, Mp)
        return lax.psum_scatter(part, "c", scatter_dimension=1,
                                tiled=True)                     # (…, Mp/pc)

    # ------------------------------------------------ ring (overlap) kernels
    def _kernel_fwd_ring(self, Ablk, Xblk):
        # ring form of the two-sided gather schedule: X gathers along
        # 'r' as before (the small side), but the A row-gather along
        # 'c' becomes a pc-step ppermute ring — at each hop the GEMM on
        # the resident A tile (against its k-slice of X) overlaps the
        # DMA of the next neighbour tile. pc-1 permutes, pc dots,
        # pinned by tests via utils.hlo.assert_ring_schedule.
        from ..parallel.collectives import ring_pass
        pc = self.grid[1]
        Xcol = lax.all_gather(Xblk, "r", axis=0, tiled=True)  # (Kp_r, Mp/pc)
        if self.Kp_c > self.Kp_r:
            Xcol = jnp.pad(Xcol, ((0, self.Kp_c - self.Kp_r), (0, 0)))
        kb = self.Kp_c // pc

        def body(acc, Ares, owner, _s):
            # owner's tile covers k-rows [owner*kb, (owner+1)*kb) of
            # the Kp_c-padded contraction (pad rows of A/X are zeros,
            # so padding contributes nothing — the stat_a argument)
            Xk = lax.dynamic_slice_in_dim(Xcol, owner * kb, kb, axis=0)
            part = self._gemm(Ares, Xk)
            return part if acc is None else acc + part

        return ring_pass(Ablk, "c", pc, body, slice_size=self._ring_slice,
                         fabric=self._fab_c)

    def _kernel_adj_ring(self, Ablk, Yblk):
        # ring form of the adjoint: Y tiles rotate along 'c'; each hop
        # GEMMs the resident tile into its owner's M-column chunk
        # (collected in rotation order, un-rotated with one roll). The
        # 'r' psum of the K-block partials is unchanged.
        from ..parallel.collectives import ring_pass
        pc = self.grid[1]
        mb = Yblk.shape[1]  # = Mp_eff // pc; block inputs widen Mp
        c = lax.axis_index("c")
        At = jnp.conj(Ablk).T
        if self._ring_slice:
            # hierarchical hop order visits owners out of rotation
            # sequence, so the concatenate-then-roll trick below (which
            # assumes owners c, c+1, ...) cannot un-rotate it — place
            # each chunk at its owner's M-column directly instead
            odt = (self.dtype if self.compute_dtype is not None
                   else jnp.result_type(At.dtype, Yblk.dtype))

            def body(acc, Yres, owner, _s):
                part = self._gemm(At, Yres)         # (Kp_c/pc, Mp/pc)
                return lax.dynamic_update_slice_in_dim(
                    acc, part.astype(odt), owner * mb, axis=1)

            out = ring_pass(Yblk, "c", pc, body,
                            init=jnp.zeros((At.shape[0], mb * pc),
                                           dtype=odt),
                            slice_size=self._ring_slice,
                            fabric=self._fab_c)
            return lax.psum(out, "r")
        parts = []

        def body(acc, Yres, _owner, _s):
            parts.append(self._gemm(At, Yres))      # (Kp_c/pc, Mp/pc)
            return acc

        ring_pass(Yblk, "c", pc, body, fabric=self._fab_c)
        cat = jnp.concatenate(parts, axis=1)        # owners c, c+1, ...
        part = jnp.roll(cat, c * mb, axis=1) if pc > 1 else cat
        return lax.psum(part, "r")

    def _kernel_adj(self, Ablk, Yblk):
        # X = Aᴴ Y, contraction over N which is sharded on 'r': gather Y
        # tiles along 'c' (full M for this row-block), one local GEMM
        # against the owned A tile, then psum the partial K-block over
        # 'r'. The reference's tagged-p2p Aᴴ pipeline (ref
        # MatrixMult.py:744-761) becomes gather + reduce; Y gathers
        # wide (see _kernel_fwd note).
        Yrow = lax.all_gather(Yblk, "c", axis=1, tiled=True)   # (Np/pr, Mp)
        part = self._gemm(jnp.conj(Ablk).T, Yrow)              # (Kp_c/pc, Mp)
        return lax.psum(part, "r")

    def _rings(self, cols_per_hop: int) -> bool:
        """Ring or bulk for one traced apply of the adjoint, whose ring
        re-reads the resident tile every hop. A word decides as it
        always has; ``auto`` on a TPU asks :func:`_ring_pays`. Leaves
        one ``summa.ring_select`` event per trace (not per
        execution)."""
        pr, pc = self.grid
        ring = bool(self.overlap) and pc > 1
        if ring and self.overlap == "auto":
            ring = _ring_pays(cols_per_hop)
        from ..diagnostics import trace
        trace.event("summa.ring_select", cat="schedule", kernel="rmatvec",
                    cols_per_hop=cols_per_hop,
                    tile_bytes=int(self.Ap.nbytes) // (pr * pc),
                    ring=int(ring), source=self._overlap_source)
        return ring

    def _matvec(self, x: DistributedArray) -> DistributedArray:
        pr, pc = self.grid
        X, ncol = self._fold_in(x, self.K)
        Me = X.shape[1]                       # M, or M*K for block input
        Mp = pc * int(np.ceil(Me / pc))
        X = _pad_to(X, self.Kp_r, Mp)
        if self.schedule == "stat_a":
            # no ring form: _ring_pays' docstring has the rows that
            # say why
            kernel = self._kernel_fwd_stat_a
        else:
            # the gather ring rotates A tiles and reads each once: its
            # auto stays the backend's (not measured, PERF.md)
            kernel = (self._kernel_fwd_ring if self.overlap and pc > 1
                      else self._kernel_fwd)
        Y = shard_map(kernel, mesh=self.mesh2,
                      in_specs=(P("r", "c"), P("r", "c")),
                      out_specs=P("r", "c"), check_vma=False)(self.Ap, X)
        return self._wrap_out(_unpad(Y, self.N, Me), x, self.N, ncol)

    def _rmatvec(self, x: DistributedArray) -> DistributedArray:
        pc = self.grid[1]
        Y, ncol = self._fold_in(x, self.N)
        Me = Y.shape[1]
        Mp = pc * int(np.ceil(Me / pc))
        Y = _pad_to(Y, self.Np, Mp)
        kernel = (self._kernel_adj_ring if self._rings(Mp // pc)
                  else self._kernel_adj)
        X = shard_map(kernel, mesh=self.mesh2,
                      in_specs=(P("r", "c"), P("r", "c")),
                      out_specs=P("c", None), check_vma=False)(self.Ap, Y)
        return self._wrap_out(_unpad(X, self.K, Me), x, self.K, ncol)


class _MPIAutoMatrixMult(_MatMulBase):
    """Partitioner-derived schedule: 2-D tiling expressed only as
    sharding constraints on one einsum (SURVEY §3.4: 'let XLA derive
    SUMMA')."""

    def __init__(self, A, M: int, mesh=None, dtype=None, saveAt: bool = False,
                 grid: Optional[Tuple[int, int]] = None, compute_dtype=None):
        base = mesh if mesh is not None else default_mesh()
        self.grid = grid if grid is not None else best_grid_2d(int(base.devices.size))
        self.mesh2 = Mesh(base.devices.reshape(self.grid), ("r", "c"))
        super().__init__(A, M, mesh=base, dtype=dtype, saveAt=saveAt,
                         compute_dtype=compute_dtype)

    def _place_A(self, A):
        if self.compute_dtype is not None:
            A = A.astype(self.compute_dtype)
        try:
            A = jax.device_put(A, NamedSharding(self.mesh2, P("r", "c")))
        except ValueError:
            pass  # non-divisible tiles: leave placement to XLA
        self.A = A

    def _matvec(self, x: DistributedArray) -> DistributedArray:
        X, ncol = self._fold_in(x, self.K)
        Y = self._gemm(self.A, X)
        return self._wrap_out(Y, x, self.N, ncol)

    def _rmatvec(self, x: DistributedArray) -> DistributedArray:
        Y, ncol = self._fold_in(x, self.N)
        At = self.At if self.At is not None else jnp.conj(self.A).T
        X = self._gemm(At, Y)
        return self._wrap_out(X, x, self.K, ncol)


def MPIMatrixMult(A, M: int, saveAt: bool = False, mesh=None,
                  kind: str = "summa", dtype=None,
                  grid: Optional[Tuple[int, int]] = None,
                  compute_dtype=None,
                  schedule: str = "auto",
                  overlap=None, hierarchical=None) -> MPILinearOperator:
    """Factory (ref ``MatrixMult.py:768-872``): ``kind`` in
    {"block", "summa", "auto"}.

    Parameters mirror the reference, except ``A`` is the full global
    matrix (one controller) rather than this rank's block, and
    ``compute_dtype`` (e.g. ``jnp.bfloat16``) selects low-precision tile
    storage with f32 MXU accumulation — the TPU bandwidth lever, same as
    ``MPIBlockDiag(compute_dtype=...)``. ``schedule`` (summa only)
    picks the forward communication schedule: "gather" (all-gather A
    row + X col), "stat_a" (A stays put; gather X, reduce-scatter the
    partials — wins for skinny X), or "auto" (per-device byte count
    decides). ``overlap`` (summa only; ``True``/``False``/``"on"``/
    ``"off"``/``"auto"``, default the ``PYLOPS_MPI_TPU_OVERLAP`` env
    seam) runs the gather forward and the adjoint as a double-buffered
    ``ppermute`` ring that hides the ICI transfer of each block behind
    the GEMM on the resident one (the stationary-A forward has no ring
    and stays bulk) — ``off`` is bit-identical to the bulk schedules,
    ``on`` matches within dtype tolerance (the accumulation order
    changes). ``auto`` is bulk off a TPU; on a TPU the adjoint decides
    per apply from the columns one hop's GEMM gets (a skinny right-hand
    side stays bulk: the ring would re-read the resident tile every
    hop), the gather forward rings — see ``_MPISummaMatrixMult``. ``block`` and ``auto`` kinds
    ignore it (forward is comm-free / the partitioner owns the
    schedule). ``hierarchical`` (summa only;
    ``True``/``False``/``"auto"``, default the
    ``PYLOPS_MPI_TPU_HIERARCHICAL`` env seam) enables the
    topology-aware treatment on hybrid (multi-slice) meshes:
    fabric-aligned per-fabric cost/byte accounting, and the two-level
    ring hop schedule when the grid's ``c`` axis spans slices — see
    ``_MPISummaMatrixMult``. ``off`` (and any flat mesh) keeps the
    kernels bit-identical to the pre-hierarchical build.
    """
    if kind == "block":
        return _MPIBlockMatrixMult(A, M, mesh=mesh, dtype=dtype,
                                   saveAt=saveAt, compute_dtype=compute_dtype)
    if kind == "summa":
        return _MPISummaMatrixMult(A, M, mesh=mesh, dtype=dtype,
                                   saveAt=saveAt, grid=grid,
                                   compute_dtype=compute_dtype,
                                   schedule=schedule, overlap=overlap,
                                   hierarchical=hierarchical)
    if kind == "auto":
        return _MPIAutoMatrixMult(A, M, mesh=mesh, dtype=dtype,
                                  saveAt=saveAt, grid=grid,
                                  compute_dtype=compute_dtype)
    raise NotImplementedError("kind must be 'block', 'summa' or 'auto'")


# sharded matrix tiles travel into jit as pytree children
# (multi-process arrays must not be closed over — linearoperator.py).
# The same registration makes the tiles DIFFERENTIABLE leaves for the
# autodiff tier (adjoint rules / implicit solver VJPs). Which leaf
# carries the matrix and its gradient:
# - block / auto: ``A`` — and, when ``saveAt=True`` stored a separate
#   ``At``, ``At`` INDEPENDENTLY, because the rules cannot know the two
#   tiles alias one matrix. A training loop updating weights must
#   either keep ``saveAt=False`` (``At`` is None → a single source of
#   truth) or fold ``gA + gAt.conj().T``-style cotangent pairs itself
#   (docs/autodiff.md).
# - summa: ``Ap``, the padded ``(Np, Kp_c)`` tiles the kernels read and
#   the only copy of the matrix the operator holds (``A`` is a property
#   over them, ``At`` is never stored). The cotangent arrives in the
#   tiles' shape with zeros in the pad; ``g.A`` of an operator-shaped
#   cotangent — the same property — is the gradient in A's shape.
from ..linearoperator import register_operator_arrays  # noqa: E402
for _c in (_MPIBlockMatrixMult, _MPIAutoMatrixMult):
    register_operator_arrays(_c, "A", "At")
register_operator_arrays(_MPISummaMatrixMult, "Ap")
