"""Per-op cost models and roofline placement.

"Large Scale Distributed Linear Algebra With TPUs" attributes its
results with per-collective byte accounting and roofline placement,
and "Memory-efficient array redistribution" (arXiv 2112.01075) shows
redistribution cost is predictable enough to assert against. This
module makes both first-class instead of bench-script folklore:

- :class:`OpCost` — FLOPs, HBM bytes and ICI (inter-chip) bytes for
  ONE apply of an operator, per device;
- a registry (:func:`register_cost` / :func:`estimate`) with models
  for the production operator families (MatrixMult block/SUMMA,
  BlockDiag, V/HStack, the distributed FFTs' pencil transposes, the
  halo-exchange stencils) that recurses through the lazy composition
  wrappers (product/sum/scaled/adjoint);
- :func:`summa_comm_volume` — the per-device communication-volume
  model that ``ops/matrixmult.py``'s ``schedule="auto"`` previously
  kept private (it now calls this function), exposed so tests can
  hand-check it;
- the per-chip peak tables (dense-matmul TFLOP/s, HBM GB/s — public
  spec-sheet figures, held equal to the benchmark's
  ``chipbench/peaks.json`` by ``tests/test_diagnostics.py`` — plus an
  APPROXIMATE aggregate ICI GB/s per chip) and :func:`roofline`,
  which converts an :class:`OpCost` + peaks into a predicted time and
  a bound ("compute" / "hbm" / "ici").

Counting conventions (what the hand-count tests pin):

- FLOPs: a real GEMM ``(m, k) @ (k, n)`` costs ``2·m·k·n``; complex
  costs 4× that (4 real multiplies + accumulation, counted as
  ``8·m·k·n`` total). FFTs count the standard ``5·n·log2(n)`` per
  length-``n`` transform.
- HBM bytes: operand + result traffic assuming each buffer streams
  once per apply (matrices at their STORAGE dtype — the
  ``compute_dtype`` lever halves this — vectors at theirs). On-chip
  (VMEM) residency makes the true figure smaller; the model is an
  upper bound.
- ICI bytes: bytes RECEIVED per device per apply. An all-gather over
  ``P`` devices of a result of ``B`` bytes receives ``B·(P-1)/P``;
  a tiled all-to-all moves ``B·(P-1)/P`` of the local block; a psum
  (ring all-reduce) ``2·B·(P-1)/P``; a ppermute exactly its slab.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Callable, Dict, Optional, Tuple

import numpy as np

__all__ = ["OpCost", "estimate", "register_cost", "roofline",
           "summa_comm_volume", "summa_comm_volume_split",
           "pencil_transpose_cost",
           "peak_flops", "peak_hbm_gbps", "peak_ici_gbps",
           "peak_dcn_gbps", "allreduce_latency_s",
           "device_peaks", "PEAK_TFLOPS", "PEAK_HBM_GBPS",
           "PEAK_ICI_GBPS", "PEAK_DCN_GBPS", "ALLREDUCE_LATENCY_S"]


# ------------------------------------------------------------- peak tables
# Dense matmul peak per chip, TFLOP/s (bf16 inputs, f32 accumulation on
# the MXU) — public spec-sheet numbers; most-specific key first. The
# f32 peak under the package's `highest` matmul-precision pin is bf16/6
# (3 products x 2 operand splits).
PEAK_TFLOPS = [
    ("v6e", 918.0), ("v6 lite", 918.0), ("v6", 918.0),
    ("v5p", 459.0), ("v5e", 197.0), ("v5 lite", 197.0), ("v5", 459.0),
    ("v4", 275.0), ("v3", 123.0), ("v2", 45.0),
]

# HBM bandwidth peak per chip, GB/s — public spec-sheet numbers (the
# denominator every hbm_gbps claim is divided by; docs/design.md
# round-7 correction).
PEAK_HBM_GBPS = [
    ("v6e", 1640.0), ("v6 lite", 1640.0), ("v6", 1640.0),
    ("v5p", 2765.0), ("v5e", 819.0), ("v5 lite", 819.0), ("v5", 2765.0),
    ("v4", 1228.0), ("v3", 900.0), ("v2", 700.0),
]

# APPROXIMATE aggregate ICI bandwidth per chip, GB/s (sum over links,
# derived from published per-pod interconnect figures: v5p 4800 Gb/s,
# v5e 1600 Gb/s, v6e 3584 Gb/s, v4 2400 Gb/s; older chips rougher).
# Good for roofline PLACEMENT (is this apply compute-, HBM- or
# ICI-bound, within ~2x), not for bandwidth claims — unknown chips get
# NO ICI roofline rather than a wrong one.
PEAK_ICI_GBPS = [
    ("v6e", 448.0), ("v6 lite", 448.0), ("v6", 448.0),
    ("v5p", 600.0), ("v5e", 200.0), ("v5 lite", 200.0), ("v5", 600.0),
    ("v4", 300.0), ("v3", 280.0), ("v2", 160.0),
]

# APPROXIMATE per-chip DCN bandwidth, GB/s (round 11): the inter-slice
# fabric is the hosts' datacenter NICs shared by each host's local
# chips — roughly a 100-200 Gb/s NIC over 4 chips. Like the ICI table
# this is for roofline PLACEMENT and for the ~10-30x ICI:DCN ratio the
# hierarchical schedules exploit, not for bandwidth claims; unknown
# chips get NO DCN roofline. Single-slice deployments never produce
# dcn_bytes, so these entries are inert off multislice.
PEAK_DCN_GBPS = [
    ("v6e", 12.5), ("v6 lite", 12.5), ("v6", 12.5),
    ("v5p", 25.0), ("v5e", 6.25), ("v5 lite", 6.25), ("v5", 25.0),
    ("v4", 6.25), ("v3", 6.25), ("v2", 6.25),
]

# APPROXIMATE per-fabric all-reduce LATENCY, seconds (round 17): the
# α term of the α–β model, i.e. the floor one small (few-scalar)
# all-reduce pays regardless of payload. A Krylov iteration's dot
# products are exactly such reductions, so on DCN-connected pods the
# iteration time is `max(apply, n_reductions * α)` — this is the term
# the communication-avoiding tier (solvers/ca.py) exists to shrink,
# and the selection signal its `auto` mode reads. Like the bandwidth
# tables these are placement numbers (order-of-magnitude per fabric
# class), not measurements: ICI ~ microseconds, DCN ~ tens of
# microseconds per software-pipelined hop tree, `host` ~ the CPU-sim /
# single-host dispatch floor.
ALLREDUCE_LATENCY_S = {
    "ici": 2e-6,
    "dcn": 50e-6,
    "host": 20e-6,
}


def allreduce_latency_s(fabric: str) -> Optional[float]:
    """Per-fabric small-all-reduce latency floor (seconds); ``None``
    for unknown fabric names rather than a wrong constant."""
    return ALLREDUCE_LATENCY_S.get((fabric or "").strip().lower())


def _lookup(table, device_kind: str) -> Optional[float]:
    kind = (device_kind or "").lower()
    for key, val in table:
        if key in kind:
            return val
    return None


def peak_flops(device_kind: str, mode: str = "bf16") -> Optional[float]:
    """Per-chip dense-matmul peak (FLOP/s) for ``mode`` (``bf16`` or
    ``f32_highest`` — the latter is bf16/6 under the package's
    precision pin). ``None`` for unknown chips."""
    tf = _lookup(PEAK_TFLOPS, device_kind)
    if tf is None:
        return None
    peak = tf * 1e12
    return peak / 6.0 if mode.startswith("f32") else peak


def peak_hbm_gbps(device_kind: str) -> Optional[float]:
    """Per-chip HBM bandwidth peak, GB/s (None for unknown chips — an
    unknown chip gets NO roofline rather than a wrong one)."""
    return _lookup(PEAK_HBM_GBPS, device_kind)


def peak_ici_gbps(device_kind: str) -> Optional[float]:
    """APPROXIMATE aggregate per-chip ICI bandwidth, GB/s (see table
    note); None for unknown chips."""
    return _lookup(PEAK_ICI_GBPS, device_kind)


def peak_dcn_gbps(device_kind: str) -> Optional[float]:
    """APPROXIMATE per-chip DCN (inter-slice) bandwidth, GB/s (see
    table note); None for unknown chips."""
    return _lookup(PEAK_DCN_GBPS, device_kind)


def device_peaks(device=None, mode: str = "bf16") -> Dict:
    """Peak dict for :func:`roofline` from a live ``jax.Device``
    (default: ``jax.devices()[0]``): ``{"flops", "hbm_gbps",
    "ici_gbps", "device_kind", "platform"}`` with ``None`` entries off
    TPU / on unknown chips."""
    if device is None:
        import jax
        device = jax.devices()[0]
    kind = getattr(device, "device_kind", "") or ""
    platform = getattr(device, "platform", "")
    if platform != "tpu":
        return {"flops": None, "hbm_gbps": None, "ici_gbps": None,
                "dcn_gbps": None,
                "allreduce_latency_s": allreduce_latency_s("host"),
                "device_kind": kind, "platform": platform}
    return {"flops": peak_flops(kind, mode),
            "hbm_gbps": peak_hbm_gbps(kind),
            "ici_gbps": peak_ici_gbps(kind),
            "dcn_gbps": peak_dcn_gbps(kind),
            "allreduce_latency_s": allreduce_latency_s("ici"),
            "device_kind": kind, "platform": platform}


# ----------------------------------------------------------------- OpCost
@dataclass
class OpCost:
    """Cost of ONE operator apply, PER DEVICE: floating-point
    operations, HBM bytes streamed, ICI bytes received — and, on
    hybrid meshes (round 11), DCN bytes received, split out because
    the two fabrics differ by ~10-30x in bandwidth and a single
    "inter-chip bytes" number hides exactly what the hierarchical
    schedules optimize. ``ici_bytes`` stays the intra-slice share (NOT
    the total), so ``ici + dcn`` is total off-chip traffic; flat
    meshes keep ``dcn_bytes == 0`` and every pre-round-11 model reads
    unchanged. ``dcn_bytes`` sits after ``notes`` so existing
    positional constructors keep their meaning. ``notes`` carries
    model provenance (which registry entry, which schedule).

    ``reductions_per_iter`` (round 17, appended last for the same
    positional-compat reason): how many latency-bound small
    all-reduces the cost's unit of work issues — the count the
    roofline's α-term ``latency`` component multiplies by the
    per-fabric :data:`ALLREDUCE_LATENCY_S` constant. 0 (the default)
    keeps every pre-round-17 model and roofline unchanged."""

    flops: float = 0.0
    hbm_bytes: float = 0.0
    ici_bytes: float = 0.0
    notes: Tuple[str, ...] = field(default_factory=tuple)
    dcn_bytes: float = 0.0
    reductions_per_iter: float = 0.0

    def __add__(self, other: "OpCost") -> "OpCost":
        return OpCost(self.flops + other.flops,
                      self.hbm_bytes + other.hbm_bytes,
                      self.ici_bytes + other.ici_bytes,
                      self.notes + other.notes,
                      self.dcn_bytes + other.dcn_bytes,
                      self.reductions_per_iter
                      + other.reductions_per_iter)

    def scaled(self, k: float) -> "OpCost":
        return OpCost(self.flops * k, self.hbm_bytes * k,
                      self.ici_bytes * k, self.notes,
                      self.dcn_bytes * k, self.reductions_per_iter * k)

    def as_dict(self) -> Dict:
        return {"flops": self.flops, "hbm_bytes": self.hbm_bytes,
                "ici_bytes": self.ici_bytes,
                "dcn_bytes": self.dcn_bytes,
                "reductions_per_iter": self.reductions_per_iter,
                "notes": list(self.notes)}


def _itemsize(dt) -> int:
    if dt is None:
        return 4
    try:
        return np.dtype(dt).itemsize
    except TypeError:
        # jnp dtypes like bfloat16 that numpy doesn't know natively
        import jax.numpy as jnp
        return jnp.dtype(dt).itemsize


def _flop_factor(dt) -> float:
    """Complex GEMMs cost 4 real multiply-accumulate pairs per term."""
    try:
        return 4.0 if np.issubdtype(np.dtype(dt), np.complexfloating) \
            else 1.0
    except TypeError:
        return 1.0


# ------------------------------------------------------------- comm models
def summa_comm_volume(N: int, K: int, M: int,
                      grid: Tuple[int, int]) -> Dict[str, float]:
    """Per-device ELEMENT volume received per forward apply of the two
    SUMMA schedules, on padded tiles over a ``(pr, pc)`` grid — the
    model ``ops/matrixmult.py``'s ``schedule="auto"`` selects with
    (previously inlined there; ring/bulk variants move the same bytes,
    only the interleaving differs):

    - ``gather``: all-gather the A row-block along ``c`` + all-gather
      the X column along ``r``;
    - ``stat_a``: A never moves — all-gather X fully (both axes), then
      reduce-scatter the partial products along ``c``.

    Returns ``{"gather": ..., "stat_a": ..., "adjoint": ...}``
    (adjoint = the stationary-A Y-gather + r-psum schedule).
    """
    split = summa_comm_volume_split(N, K, M, grid)
    return {k: v["r"] + v["c"] for k, v in split.items()}


def summa_comm_volume_split(N: int, K: int, M: int,
                            grid: Tuple[int, int]
                            ) -> Dict[str, Dict[str, float]]:
    """:func:`summa_comm_volume` split BY GRID AXIS — per schedule,
    the per-device element volume received over the ``r`` (row) and
    ``c`` (column) axis collectives separately. This is the per-fabric
    attribution seam (round 11): on a hybrid mesh whose grid is
    fabric-aligned (rows = slices, so ``r`` collectives ride DCN and
    ``c`` collectives ride ICI — the layout ``ops/matrixmult.py`` pins
    when the hierarchical seam is on), each axis's volume IS that
    fabric's bytes. A topology-blind schedule gets the conservative
    charge instead: with no pinned axis→fabric assignment, every
    collective may ride the slow fabric, so the whole total is
    DCN-attributed."""
    pr, pc = int(grid[0]), int(grid[1])
    Np = pr * math.ceil(N / pr)
    Kp_r = pr * math.ceil(K / pr)
    Kp_c = pc * math.ceil(K / pc)
    Mp = pc * math.ceil(M / pc)
    gather = {"c": (Np // pr) * Kp_c * (pc - 1) / pc,
              "r": Kp_r * (Mp // pc) * (pr - 1) / pr}
    stat_a = {"r": Kp_r * (Mp // pc) * (pr - 1) / pr,
              "c": (Kp_r * Mp * (pc - 1) / pc
                    + (Np // pr) * Mp * (pc - 1) / pc)}
    # adjoint: gather Y row along 'c' ((Np/pr, Mp) result), then psum
    # the (Kp_c/pc, Mp) partial over 'r' (ring all-reduce ~ 2(pr-1)/pr)
    adjoint = {"c": (Np // pr) * Mp * (pc - 1) / pc,
               "r": (Kp_c // pc) * Mp * 2 * (pr - 1) / pr}
    return {"gather": gather, "stat_a": stat_a, "adjoint": adjoint}


def pencil_transpose_cost(shape: Tuple[int, ...], n_dev: int,
                          itemsize: int = 8,
                          n_transposes: int = 2,
                          fabric_shape: Optional[Tuple[int, int]] = None,
                          hierarchical: bool = False) -> OpCost:
    """Off-chip cost of the distributed FFT's pencil transpose(s):
    each tiled all-to-all of the full array moves ``(P-1)/P`` of the
    local block off-chip, regardless of chunking
    (``chunked_pencil_transpose`` streams the SAME bytes in K pieces).
    ``itemsize`` is the element size on the wire — 8 for c64, 2×4 for
    the planar (re, im) f32 plane pair (identical bytes for the full
    spectrum; ~half for a real transform's half-spectrum, which the
    caller accounts by passing the half-spectrum shape). HBM term: one
    read + one write of the local block per transpose.

    ``fabric_shape=(D, I)`` (round 11) splits the off-chip bytes per
    fabric on a D-slice hybrid mesh of I devices each:

    - ``hierarchical=True`` — the two-level schedule
      (:func:`~pylops_mpi_tpu.parallel.collectives.hier_pencil_transpose`):
      the intra-slice all-to-all moves ``(I-1)/I`` of the local block
      on ICI, the staged inter-slice exchange ``(D-1)/D`` on DCN.
    - ``hierarchical=False`` — the topology-blind baseline. A flat
      tuple-axis all-to-all on a hybrid mesh does NOT lower to a
      pointwise exchange: GSPMD's portable cross-slice decomposition
      gathers the array (the generic-reshard lowering ``ops/fft.py``
      documents for multi-axis meshes), so each device receives
      ``(I-1)`` local blocks over ICI and ``(P-I)`` over DCN — the
      D-fold DCN inflation the hierarchical schedule removes.

    ``fabric_shape=None`` (flat mesh) keeps the pre-round-11 model
    verbatim: all off-chip bytes in ``ici_bytes``, ``dcn_bytes == 0``.
    """
    n_total = float(np.prod(shape))
    local_bytes = n_total * itemsize / max(n_dev, 1)
    frac = (n_dev - 1) / n_dev if n_dev > 1 else 0.0
    ici = local_bytes * frac * n_transposes
    dcn = 0.0
    notes = (f"pencil_transpose x{n_transposes}",)
    if fabric_shape is not None:
        d, i = int(fabric_shape[0]), int(fabric_shape[1])
        if d > 1 and i >= 1 and d * i == n_dev:
            if hierarchical:
                ici = local_bytes * (i - 1) / i * n_transposes
                dcn = local_bytes * (d - 1) / d * n_transposes
                notes = (f"pencil_transpose x{n_transposes} "
                         f"hier[dcn{d}xici{i}]",)
            else:
                ici = local_bytes * (i - 1) * n_transposes
                dcn = local_bytes * (n_dev - i) * n_transposes
                notes = (f"pencil_transpose x{n_transposes} "
                         f"flat-on-hybrid[dcn{d}xici{i}:gather]",)
    return OpCost(flops=0.0,
                  hbm_bytes=2.0 * local_bytes * n_transposes,
                  ici_bytes=ici, notes=notes, dcn_bytes=dcn)


# ------------------------------------------------------------ the registry
_REGISTRY: Dict[type, Callable] = {}


def register_cost(cls, fn: Callable) -> None:
    """Register ``fn(op, direction) -> OpCost`` for operator class
    ``cls`` (``direction`` in {"forward", "adjoint"}). Subclasses
    resolve through the MRO, most-derived first."""
    _REGISTRY[cls] = fn


def estimate(op, direction: str = "forward") -> Optional[OpCost]:
    """Per-device cost of one ``direction`` apply of ``op``, or
    ``None`` when no model (or no composable sub-model) exists —
    callers must treat a missing model as "unknown", never as zero."""
    if direction not in ("forward", "adjoint"):
        raise ValueError(f"direction={direction!r}")
    _bind_builtin()
    for cls in type(op).__mro__:
        fn = _REGISTRY.get(cls)
        if fn is not None:
            return fn(op, direction)
    return None


def _n_dev(op) -> int:
    mesh = getattr(op, "mesh", None)
    if mesh is None:
        return 1
    return int(mesh.devices.size)


# --- models for the production families (registered at the bottom of
# the modules that define the classes would create import cycles; the
# registry binds lazily by class object at first `estimate` call
# instead, via the _builtin table of dotted names).

def _cost_sparse_matmul(op, direction: str) -> OpCost:
    """Sparse matmul tier: flops and matrix bytes scale with ``nnz``
    (value + two int32 indices per triplet), not ``N·M`` — the whole
    point of the tier. Adjoint charges the scatter's cross-shard
    combine (psum-shaped, same bytes as the ring schedule's P-1 hops
    of the x-block ring)."""
    P = _n_dev(op)
    it_v = _itemsize(op.dtype)
    it_w = _itemsize(getattr(op, "compute_dtype", None) or op.dtype)
    ff = _flop_factor(op.dtype)
    flops = 2.0 * ff * op.nnz / P
    trip = op.nnz * (it_w + 8.0) / P
    if direction == "forward":
        vec = (op.Ncol + op.N / P) * it_v
        return OpCost(flops, trip + vec, 0.0, ("sparse.forward",))
    vec = (op.N + op.Ncol / P) * it_v
    ici = op.Ncol * it_v * 2.0 * (P - 1) / P
    return OpCost(flops, trip + vec, ici,
                  (f"sparse.adjoint+{op.adjoint_mode}",))


def _cost_block_matmul(op, direction: str) -> OpCost:
    P = _n_dev(op)
    it_a = _itemsize(getattr(op, "compute_dtype", None) or op.dtype)
    it_v = _itemsize(op.dtype)
    ff = _flop_factor(op.dtype)
    flops = 2.0 * ff * op.N * op.K * op.M / P
    a_bytes = op.N * op.K * it_a / P
    if direction == "forward":
        vec = (op.K * op.M + op.N * op.M / P) * it_v
        return OpCost(flops, a_bytes + vec, 0.0, ("block.forward",))
    # adjoint: sharded-N contraction -> one psum of the (K, M) result
    vec = (op.N * op.M / P + op.K * op.M) * it_v
    ici = op.K * op.M * it_v * 2.0 * (P - 1) / P
    return OpCost(flops, a_bytes + vec, ici, ("block.adjoint+psum",))


def _summa_fabric_split(op, bytes_r: float,
                        bytes_c: float) -> Tuple[float, float, str]:
    """``(ici_bytes, dcn_bytes, note)`` attribution of SUMMA's
    per-grid-axis comm bytes (round 11). Flat mesh: everything is ICI
    (the pre-round-11 model). Hybrid mesh + fabric-aligned
    hierarchical schedule (``op._hier``): each grid axis is charged to
    the fabric it actually spans (rows = slices, so ``r`` rides DCN
    and ``c`` rides ICI for the aligned layout). Hybrid mesh +
    topology-blind schedule: conservative slow-fabric charge — with no
    pinned axis→fabric assignment every collective may cross DCN."""
    mesh2 = getattr(op, "mesh2", None)
    if mesh2 is None:
        return bytes_r + bytes_c, 0.0, ""
    from ..parallel import topology as _topo
    if not _topo.is_hybrid(mesh2):
        return bytes_r + bytes_c, 0.0, ""
    if not getattr(op, "_hier", False):
        return 0.0, bytes_r + bytes_c, "+fabric[blind:dcn]"
    fr = _topo.axis_fabric(mesh2, "r")
    fc = _topo.axis_fabric(mesh2, "c")
    ici = ((bytes_r if fr == "ici" else 0.0)
           + (bytes_c if fc == "ici" else 0.0))
    dcn = ((bytes_r if fr == "dcn" else 0.0)
           + (bytes_c if fc == "dcn" else 0.0))
    return ici, dcn, f"+fabric[r={fr},c={fc}]"


def _cost_summa_matmul(op, direction: str) -> OpCost:
    pr, pc = op.grid
    P = pr * pc
    it_a = _itemsize(getattr(op, "compute_dtype", None) or op.dtype)
    it_v = _itemsize(op.dtype)
    ff = _flop_factor(op.dtype)
    flops = 2.0 * ff * op.Np * op.Kp_c * op.Mp / P
    a_bytes = op.Np * op.Kp_c * it_a / P
    split = summa_comm_volume_split(op.N, op.K, op.M, op.grid)
    if direction == "forward":
        sched = getattr(op, "schedule", "gather")
        sp = split.get(sched, split["gather"])
        # A moves narrow (gather schedule's c-axis term), X moves wide;
        # approximate with the A-row term at it_a and the rest at it_v
        if sched == "gather":
            a_term = (op.Np // pr) * op.Kp_c * (pc - 1) / pc
            bytes_c = a_term * it_a + (sp["c"] - a_term) * it_v
        else:
            bytes_c = sp["c"] * it_v
        bytes_r = sp["r"] * it_v
        ici, dcn, fnote = _summa_fabric_split(op, bytes_r, bytes_c)
        vec = (op.Kp_r * op.Mp / P + op.Np * op.Mp / P) * it_v
        return OpCost(flops, a_bytes + vec, ici,
                      (f"summa.forward[{sched}]{fnote}",), dcn)
    sp = split["adjoint"]
    ici, dcn, fnote = _summa_fabric_split(op, sp["r"] * it_v,
                                          sp["c"] * it_v)
    vec = (op.Np * op.Mp / P + op.Kp_c * op.Mp / pc) * it_v
    return OpCost(flops, a_bytes + vec, ici,
                  (f"summa.adjoint{fnote}",), dcn)


def _cost_blockdiag(op, direction: str) -> OpCost:
    P = _n_dev(op)
    batched = getattr(op, "_batched", None)
    it_a = _itemsize(getattr(op, "compute_dtype", None) or op.dtype)
    it_v = _itemsize(op.dtype)
    ff = _flop_factor(op.dtype)
    if batched is not None:
        nblk, m, n = batched.shape
        k = getattr(op, "_batched_k", 1)
        flops = 2.0 * ff * nblk * m * n * k / P
        hbm = (nblk * m * n * it_a
               + (op.shape[0] + op.shape[1]) * it_v) / P
        return OpCost(flops, hbm, 0.0, ("blockdiag.batched",))
    flops = 2.0 * ff * float(np.sum(op.nops * op.mops)) / P
    hbm = (float(np.sum(op.nops * op.mops)) * it_a
           + (op.shape[0] + op.shape[1]) * it_v) / P
    return OpCost(flops, hbm, 0.0, ("blockdiag.per-block",))


def _cost_stack(op, direction: str) -> OpCost:
    # sum the children (each applied once per stack apply); the
    # homogeneous-row batched path adds the adjoint reduce-scatter,
    # which the children's own models do not know about — approximate
    # with the children total (a lower bound, noted).
    total = OpCost(notes=("stack.children-sum",))
    for child in getattr(op, "ops", ()):
        c = estimate(child, direction)
        if c is None:
            return None
        total = total + c
    return total


def _cost_wrapper(op, direction: str) -> OpCost:
    """Lazy composition wrappers: recurse into args. Adjoint/transpose
    swap direction; product sums its factors; scaled/conj forward."""
    from ..linearoperator import (
        _AdjointLinearOperator, _TransposedLinearOperator,
        _ProductLinearOperator, _SumLinearOperator,
        _ScaledLinearOperator, _ConjLinearOperator,
        _PowerLinearOperator, _CheckpointedLinearOperator)
    flip = {"forward": "adjoint", "adjoint": "forward"}
    if isinstance(op, (_AdjointLinearOperator, _TransposedLinearOperator)):
        return estimate(op.args[0], flip[direction])
    if isinstance(op, _ProductLinearOperator):
        a = estimate(op.args[0], direction)
        b = estimate(op.args[1], direction)
        return None if (a is None or b is None) else a + b
    if isinstance(op, _SumLinearOperator):
        a = estimate(op.args[0], direction)
        b = estimate(op.args[1], direction)
        return None if (a is None or b is None) else a + b
    if isinstance(op, (_ScaledLinearOperator, _ConjLinearOperator,
                       _CheckpointedLinearOperator)):
        return estimate(op.args[0], direction)
    if isinstance(op, _PowerLinearOperator):
        c = estimate(op.args[0], direction)
        return None if c is None else c.scaled(op._p)
    return None


def _cost_fft(op, direction: str) -> OpCost:
    """Distributed pencil FFT: per-axis ``5 n log2 n`` transform FLOPs
    over the local share + the pencil-transpose collectives. Uses the
    operator's logical dims and engine mode (planar plane pairs move
    2xf32 = the same 8 bytes/element as c64 for the full spectrum)."""
    dims = getattr(op, "dims", None)
    if not dims or any(d is None for d in dims):
        return None
    P = _n_dev(op)
    n_total = float(np.prod(dims))
    axes = getattr(op, "axes", tuple(range(len(dims))))
    flops = sum(5.0 * n_total * math.log2(max(2, dims[ax]))
                for ax in axes) / P
    n_t = max(0, len(axes) - 1)  # one transpose per non-local axis pair
    fab = None
    mesh = getattr(op, "mesh", None)
    if mesh is not None:
        from ..parallel import topology as _topo
        h = _topo.hybrid_axes(mesh)
        if h is not None:
            fab = (h[2], h[3])
    cost = pencil_transpose_cost(dims, P, itemsize=8, n_transposes=n_t,
                                 fabric_shape=fab,
                                 hierarchical=bool(
                                     getattr(op, "_hier", False)))
    return OpCost(flops, cost.hbm_bytes + 2 * n_total * 8 / P,
                  cost.ici_bytes, ("fft.pencil",) + cost.notes,
                  cost.dcn_bytes)


def _cost_derivative(op, direction: str) -> OpCost:
    """Stencil: taps x N flops, one read+write sweep, and the
    ring-halo ghost slabs (2 x w rows) on the ICI."""
    dims = getattr(op, "dims", None) or (op.shape[1],)
    P = _n_dev(op)
    n_total = float(np.prod(dims))
    it = _itemsize(op.dtype)
    taps = 3.0  # centered first/second difference
    row = n_total / max(1, dims[0])
    w = 1  # one ghost row per side (3-point stencils)
    ici = 2.0 * w * row * it if P > 1 else 0.0
    return OpCost(2.0 * taps * n_total / P, 2.0 * n_total * it / P, ici,
                  ("stencil.halo",))


# dotted-name -> model; resolved lazily so this module imports clean
# without pulling the operator stack
_BUILTIN = [
    ("pylops_mpi_tpu.ops.matrixmult:_MPIBlockMatrixMult",
     _cost_block_matmul),
    ("pylops_mpi_tpu.ops.matrixmult:_MPIAutoMatrixMult",
     _cost_block_matmul),
    ("pylops_mpi_tpu.ops.matrixmult:_MPISummaMatrixMult",
     _cost_summa_matmul),
    ("pylops_mpi_tpu.ops.sparse:MPISparseMatrixMult",
     _cost_sparse_matmul),
    ("pylops_mpi_tpu.ops.blockdiag:MPIBlockDiag", _cost_blockdiag),
    ("pylops_mpi_tpu.ops.stack:MPIVStack", _cost_stack),
    ("pylops_mpi_tpu.ops.stack:MPIHStack", _cost_stack),
    ("pylops_mpi_tpu.ops.fft:MPIFFTND", _cost_fft),
    ("pylops_mpi_tpu.ops.fft:MPIFFT2D", _cost_fft),
    ("pylops_mpi_tpu.ops.derivatives:MPIFirstDerivative",
     _cost_derivative),
    ("pylops_mpi_tpu.ops.derivatives:MPISecondDerivative",
     _cost_derivative),
    ("pylops_mpi_tpu.linearoperator:_AdjointLinearOperator",
     _cost_wrapper),
    ("pylops_mpi_tpu.linearoperator:_TransposedLinearOperator",
     _cost_wrapper),
    ("pylops_mpi_tpu.linearoperator:_ProductLinearOperator",
     _cost_wrapper),
    ("pylops_mpi_tpu.linearoperator:_SumLinearOperator", _cost_wrapper),
    ("pylops_mpi_tpu.linearoperator:_ScaledLinearOperator",
     _cost_wrapper),
    ("pylops_mpi_tpu.linearoperator:_ConjLinearOperator", _cost_wrapper),
    ("pylops_mpi_tpu.linearoperator:_PowerLinearOperator",
     _cost_wrapper),
    ("pylops_mpi_tpu.linearoperator:_CheckpointedLinearOperator",
     _cost_wrapper),
]
_builtin_bound = False


def _bind_builtin() -> None:
    global _builtin_bound
    if _builtin_bound:
        return
    import importlib
    for dotted, fn in _BUILTIN:
        modname, clsname = dotted.split(":")
        try:
            cls = getattr(importlib.import_module(modname), clsname)
        except Exception:
            continue
        _REGISTRY.setdefault(cls, fn)
    _builtin_bound = True


# ---------------------------------------------------------------- roofline
def roofline(cost: OpCost, peaks: Dict, n_dev: int = 1,
             measured_s: Optional[float] = None) -> Dict:
    """Place an :class:`OpCost` on the roofline: per-component times
    (``flops / peak_flops``, ``hbm_bytes / hbm_bw``, ``ici_bytes /
    ici_bw``, when the cost carries a hybrid-mesh split ``dcn_bytes /
    dcn_bw``, and when it declares ``reductions_per_iter`` an α-term
    ``latency`` component = reductions x the fabric's
    ``allreduce_latency_s``; the cost is PER DEVICE, the peaks PER
    CHIP, so ``n_dev``
    only scales aggregate reporting), predicted seconds = max of the
    available components (a perfectly-overlapped execution's lower
    bound), and ``bound`` = the component that dominates. Components
    whose peak is ``None``/0 are skipped — an unknown chip yields
    ``predicted_s=None`` rather than a wrong roofline.

    ``measured_s`` (optional): the measured per-apply seconds. When
    the implied HBM bandwidth EXCEEDS the chip's HBM peak, the
    working set cannot have streamed from HBM — it was VMEM-resident
    — so the result re-buckets: ``regime="vmem"``, the HBM component
    is dropped from the bound, and ``hbm_pct`` is never reported
    above 100 (the VERDICT round-5 misattribution: 1261 GB/s
    "measured" against an 819 GB/s v5e peak is a cache number, not an
    HBM number). Otherwise ``regime="hbm"`` with the honest
    ``hbm_pct``."""
    comps = {}
    if peaks.get("flops"):
        comps["compute"] = cost.flops / peaks["flops"]
    if peaks.get("hbm_gbps"):
        comps["hbm"] = cost.hbm_bytes / (peaks["hbm_gbps"] * 1e9)
    if peaks.get("ici_gbps") and cost.ici_bytes:
        comps["ici"] = cost.ici_bytes / (peaks["ici_gbps"] * 1e9)
    if peaks.get("dcn_gbps") and cost.dcn_bytes:
        comps["dcn"] = cost.dcn_bytes / (peaks["dcn_gbps"] * 1e9)
    # α-term (round 17): reductions pay a per-collective latency floor
    # that no bandwidth component captures — a Krylov iteration's few
    # scalar dots cost microseconds of wire time each, not bytes. Only
    # costs that declare reductions_per_iter opt in, so every earlier
    # roofline is unchanged.
    if peaks.get("allreduce_latency_s") and cost.reductions_per_iter:
        comps["latency"] = (cost.reductions_per_iter
                            * peaks["allreduce_latency_s"])
    if not comps:
        return {"predicted_s": None, "bound": None, "components_s": {},
                "cost": cost.as_dict(), "n_dev": n_dev}
    bound = max(comps, key=comps.get)
    out = {"predicted_s": comps[bound], "bound": bound,
           "components_s": {k: float(f"{v:.4g}")
                            for k, v in comps.items()},
           "cost": cost.as_dict(), "n_dev": n_dev}
    if measured_s and measured_s > 0 and peaks.get("hbm_gbps") \
            and cost.hbm_bytes:
        implied_gbps = cost.hbm_bytes / measured_s / 1e9
        if implied_gbps > peaks["hbm_gbps"]:
            out["regime"] = "vmem"
            out["implied_hbm_gbps"] = round(implied_gbps, 1)
            out["note"] = ("implied bandwidth exceeds the HBM peak: "
                           "working set is VMEM-resident; not an HBM "
                           "measurement")
            nonhbm = {k: v for k, v in comps.items() if k != "hbm"}
            if nonhbm:
                out["bound"] = max(nonhbm, key=nonhbm.get)
        else:
            out["regime"] = "hbm"
            out["hbm_pct"] = round(
                100.0 * implied_gbps / peaks["hbm_gbps"], 1)
    return out
