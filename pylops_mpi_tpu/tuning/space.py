"""Per-operator tuning spaces and their cost-model seeds.

Every discrete plan choice the operator stack exposes — SUMMA gather
vs stationary-A, ``overlap=on|off``, ``comm_chunks=K``, Pallas-vs-XLA
normal path — is declared here as a :class:`TuningSpace`: a named set
of axes with candidate values plus a cost function that SEEDS the
search order from the analytic model (``diagnostics/costmodel.py``).
The searcher (``search.py``) then refines the seed by measurement;
both arXiv 2112.09017 and arXiv 2112.01075 show the best
collective/schedule is topology- and shape-dependent, so the seed is
a ranking hint, never the verdict.

Design rules:

- **The cost-model pick must equal today's defaults** on every
  platform: the seed exists so ``PYLOPS_MPI_TPU_TUNE=on`` without a
  measured cache behaves exactly like the hand-set ``auto`` seams
  (overlap off on CPU sim / on on TPU, schedule by comm volume,
  fused normal path when available). Measurement is the only thing
  that can move a plan off the defaults.
- **Fixed axes** are recorded, not searched — e.g. the FFT engine
  (planar vs complex) is resolved by the global
  ``PYLOPS_MPI_TPU_FFT_MODE`` seam and pinned by complex-free HLO
  tests; the space declares it so the plan carries the full schedule
  provenance, but the tuner never flips it.
- New operators REGISTER a space here instead of growing new env
  knobs — the tuner, the offline CLI, the plan cache and the docs
  table all pick it up from this one declaration.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

__all__ = ["Axis", "TuningSpace", "space_for", "register_space",
           "candidates", "rank", "default_params", "SPACES"]


# per-collective dispatch overhead used by the seeds: the CPU sim pays
# real python/XLA dispatch per extra collective with nothing to hide
# behind; on TPU the latency-hiding scheduler overlaps the hops
_DISPATCH_S = {"cpu": 50e-6, "tpu": 5e-6}


@dataclass(frozen=True)
class Axis:
    """One tunable dimension: ``candidates`` in preference order
    (index 0 = today's default — ties in the cost seed keep this
    order, so an uninformative model degrades to current behavior).
    ``fixed`` axes are recorded in the plan but never searched."""

    name: str
    candidates: Tuple
    fixed: bool = False


@dataclass
class TuningSpace:
    """Declared plan space for one operator family.

    ``cost(context, params) -> Optional[float]`` predicts seconds for
    one apply under ``params`` (lower is better; ``None`` = no model,
    candidate keeps declaration order). ``enumerate_fn(context)``
    overrides the default cartesian product when candidates are
    conditional (e.g. ``comm_chunks`` only varies with overlap on).
    """

    op: str
    axes: Tuple[Axis, ...]
    cost: Optional[Callable[[Dict, Dict], Optional[float]]] = None
    enumerate_fn: Optional[Callable[[Dict], List[Dict]]] = None
    default_fn: Optional[Callable[[Dict], Dict]] = None
    note: str = ""

    def axis(self, name: str) -> Optional[Axis]:
        for ax in self.axes:
            if ax.name == name:
                return ax
        return None

    def validate(self, params: Dict) -> bool:
        """True when every (name, value) pair fits a declared axis —
        the gate a cached plan must pass before it is applied (a
        schema-valid cache can still carry a stale axis value after a
        code change; such entries are treated as misses)."""
        for k, v in params.items():
            ax = self.axis(k)
            if ax is None or v not in ax.candidates:
                return False
        return True


# ------------------------------------------------------------- cost seeds
def _peaks(context: Dict) -> Dict:
    """Roofline peaks for the seed: spec-sheet per-chip numbers on
    TPU; an assumed 30 GB/s stream bandwidth carved across virtual
    devices on the CPU sim (the point is ORDERING candidates, not
    absolute prediction)."""
    nd = max(1, int(context.get("n_dev") or 1))
    if context.get("platform") == "tpu":
        from ..diagnostics import costmodel
        chip = context.get("chip") or ""
        return {"flops": costmodel.peak_flops(chip, "f32_highest"),
                "hbm_gbps": costmodel.peak_hbm_gbps(chip),
                "ici_gbps": costmodel.peak_ici_gbps(chip),
                "dcn_gbps": costmodel.peak_dcn_gbps(chip)}
    # CPU sim: the DCN "bandwidth" only needs the ~9x ICI:DCN ratio
    # (parallel/topology.FABRIC_GBPS) so the hierarchical seed orders
    # schedules the way a real hybrid fabric would
    return {"flops": None, "hbm_gbps": 30.0 / nd, "ici_gbps": 30.0 / nd,
            "dcn_gbps": 30.0 / nd / 9.0}


def _fabric_of(context: Dict) -> Optional[Tuple[int, int]]:
    """``(n_slices, per_slice)`` parsed from the context's
    ``extra["topology"]`` key component (``dcn{D}xici{I}``, injected by
    ``plan.get_plan`` on hybrid meshes), or ``None`` on flat meshes —
    where every seed below reduces to its pre-round-11 formula."""
    t = str(context.get("extra", {}).get("topology") or "")
    if t.startswith("dcn") and "xici" in t:
        try:
            d, i = t[3:].split("xici")
            return int(d), int(i)
        except ValueError:
            return None
    return None


def _t_dcn(context: Dict, dcn_bytes: float) -> float:
    pk = _peaks(context)
    bw = pk.get("dcn_gbps")
    return dcn_bytes / (bw * 1e9) if (bw and dcn_bytes) else 0.0


def _dispatch_s(context: Dict) -> float:
    return _DISPATCH_S["tpu" if context.get("platform") == "tpu"
                       else "cpu"]


def _itemsize(context: Dict) -> int:
    try:
        return int(np.dtype(context.get("dtype") or "float32").itemsize)
    except TypeError:
        return 4


def _overlap_seed(context: Dict, params: Dict, ici_bytes: float,
                  steps: int, base_s: float = 0.0) -> float:
    """Shared seed for the binary bulk-vs-pipelined choice: on TPU the
    ring/chunked schedule hides ~half the ICI time behind compute; on
    the CPU sim there is nothing to hide and each extra hop costs a
    dispatch — reproducing exactly the ``overlap=auto`` policy
    (``utils/deps.py``) the seed must not diverge from."""
    pk = _peaks(context)
    t_ici = (ici_bytes / (pk["ici_gbps"] * 1e9)
             if pk.get("ici_gbps") and ici_bytes else 0.0)
    on = params.get("overlap") == "on"
    if not on:
        return base_s + t_ici
    hide = 0.5 if context.get("platform") == "tpu" else 0.0
    return base_s + (1.0 - hide) * t_ici \
        + max(0, steps) * _dispatch_s(context)


def _batch_of(context: Dict) -> int:
    """Block width of the solve the plan will serve (``extra["batch"]``,
    default 1). Seeds scale their per-apply work by it — K columns ride
    the same schedule — so batch=1 costs (and therefore batch=1 plans)
    are EXACTLY the pre-batching ones."""
    try:
        return max(1, int(context.get("extra", {}).get("batch") or 1))
    except (TypeError, ValueError):
        return 1


def _cost_matrixmult(context: Dict, params: Dict) -> Optional[float]:
    shape = context.get("shape")
    if not shape or len(shape) != 3:
        return None
    N, K, M = (int(s) for s in shape)
    M *= _batch_of(context)  # K RHS columns widen the model dimension
    grid = tuple(context.get("extra", {}).get("grid") or (1, 1))
    pr, pc = max(1, int(grid[0])), max(1, int(grid[1]))
    P = pr * pc
    it = _itemsize(context)
    from ..diagnostics.costmodel import summa_comm_volume_split
    split = summa_comm_volume_split(N, K, M, (pr, pc))
    sp = split.get(params.get("schedule", "gather"), split["gather"])
    fab = _fabric_of(context)
    if fab is None:
        ici_b, dcn_b = (sp["r"] + sp["c"]) * it, 0.0
    elif params.get("hierarchical") == "off":
        # topology-blind on a hybrid mesh: conservative slow-fabric
        # charge (mirrors costmodel._summa_fabric_split)
        ici_b, dcn_b = 0.0, (sp["r"] + sp["c"]) * it
    else:
        ici_b, dcn_b = sp["c"] * it, sp["r"] * it
    pk = _peaks(context)
    flops = 2.0 * N * K * M / P
    hbm = (N * K + K * M + N * M) * it / P
    t_comp = flops / pk["flops"] if pk.get("flops") else 0.0
    t_hbm = hbm / (pk["hbm_gbps"] * 1e9) if pk.get("hbm_gbps") else 0.0
    return _overlap_seed(context, params, ici_b, steps=pc - 1,
                         base_s=max(t_comp, t_hbm)) \
        + _t_dcn(context, dcn_b)


def _cost_fft(context: Dict, params: Dict) -> Optional[float]:
    shape = context.get("shape")
    if not shape:
        return None
    P = max(1, int(context.get("n_dev") or 1))
    it = _itemsize(context)
    n_total = float(np.prod([int(s) for s in shape]))
    from ..diagnostics.costmodel import pencil_transpose_cost
    c = pencil_transpose_cost(
        tuple(int(s) for s in shape), P, itemsize=it,
        fabric_shape=_fabric_of(context),
        hierarchical=params.get("hierarchical") != "off")
    pk = _peaks(context)
    flops = 5.0 * n_total * math.log2(max(2.0, n_total)) / P
    t_comp = flops / pk["flops"] if pk.get("flops") else 0.0
    t_hbm = (c.hbm_bytes / (pk["hbm_gbps"] * 1e9)
             if pk.get("hbm_gbps") else 0.0)
    t_dcn = _t_dcn(context, c.dcn_bytes)
    K = int(params.get("comm_chunks", 1))
    # each chunk adds one all-to-all dispatch pair per transpose; more
    # chunks hide more of the transfer behind the per-chunk transforms
    base = max(t_comp, t_hbm)
    if params.get("overlap") != "on" or K <= 1:
        pk_ici = pk.get("ici_gbps")
        return base + t_dcn \
            + (c.ici_bytes / (pk_ici * 1e9) if pk_ici else 0.0)
    hide = (0.5 * (1.0 - 1.0 / K)
            if context.get("platform") == "tpu" else 0.0)
    pk_ici = pk.get("ici_gbps")
    t_ici = c.ici_bytes / (pk_ici * 1e9) if pk_ici else 0.0
    return base + (1.0 - hide) * (t_ici + t_dcn) \
        + 2 * (K - 1) * _dispatch_s(context)


def _cost_blockdiag(context: Dict, params: Dict) -> Optional[float]:
    extra = context.get("extra", {})
    a_bytes = float(extra.get("a_bytes") or 0.0)
    if not a_bytes:
        return None
    P = max(1, int(context.get("n_dev") or 1))
    pk = _peaks(context)
    # the normal-equation apply is HBM-bound: the fused (Pallas)
    # path streams the block stack ONCE per (u, q) pair, the two-sweep
    # einsum pair twice — the whole reason the kernel exists
    sweeps = 1.0 if params.get("normal_path") == "fused" else 2.0
    # the block stack streams ONCE for all K columns (the batching
    # amortization); only the per-column vector traffic scales, which
    # the seed folds in as a small linear term so batch=1 is unchanged
    b = _batch_of(context)
    if not pk.get("hbm_gbps"):
        return sweeps
    return sweeps * a_bytes * (1.0 + 0.01 * (b - 1)) / P \
        / (pk["hbm_gbps"] * 1e9)


def _cost_stack(context: Dict, params: Dict) -> Optional[float]:
    shape = context.get("shape")
    if not shape:
        return None
    P = max(1, int(context.get("n_dev") or 1))
    it = _itemsize(context)
    out_len = int(shape[-1]) * _batch_of(context)
    ici = out_len * it * 2.0 * (P - 1) / max(1, P)  # adjoint psum
    return _overlap_seed(context, params, ici, steps=P - 1)


def _cost_halo_family(context: Dict, params: Dict) -> Optional[float]:
    shape = context.get("shape")
    if not shape:
        return None
    P = max(1, int(context.get("n_dev") or 1))
    it = _itemsize(context)
    row = float(np.prod([int(s) for s in shape])) / max(1, int(shape[0]))
    ici = 2.0 * row * it if P > 1 else 0.0  # two ghost slabs
    return _overlap_seed(context, params, ici, steps=2)


def _expand_hier(cands: List[Dict], context: Dict) -> List[Dict]:
    """Expand candidates along the ``hierarchical`` axis — ONLY when
    the context carries a hybrid-mesh topology key. Flat meshes have
    nothing to stage, so their candidate lists (and cache entries, and
    measurement budgets) stay exactly the pre-round-11 ones; on a
    hybrid mesh ``auto`` resolves to on, so searching (on, off) covers
    the whole behavior space without an aliased third trial."""
    if not _fabric_of(context):
        return cands
    return [dict(p, hierarchical=h) for p in cands
            for h in ("on", "off")]


def _enum_matrixmult(context: Dict) -> List[Dict]:
    base = [{"schedule": s, "overlap": o}
            for s in ("gather", "stat_a") for o in ("off", "on")]
    return _expand_hier(base, context)


def _enum_fft(context: Dict) -> List[Dict]:
    """Overlap off makes the chunk count moot — one canonical bulk
    candidate plus the chunked ladder, instead of a product full of
    aliases that would waste measurement trials."""
    from ..utils.deps import comm_chunks_default
    ladder = []
    seen = set()
    for k in (comm_chunks_default(), 2, 4, 8):
        if k > 1 and k not in seen:
            seen.add(k)
            ladder.append({"overlap": "on", "comm_chunks": int(k)})
    return _expand_hier([{"overlap": "off", "comm_chunks": 1}] + ladder,
                        context)


def _enum_blockdiag(context: Dict) -> List[Dict]:
    if context.get("extra", {}).get("fused_available"):
        return [{"normal_path": "fused"}, {"normal_path": "two_sweep"}]
    return [{"normal_path": "two_sweep"}]


# --------------------------------------------------------------- registry
SPACES: Dict[str, TuningSpace] = {}


def register_space(space: TuningSpace) -> None:
    """Register (or replace) the tuning space for one operator family
    — the extension point new kernels use instead of a new env knob."""
    SPACES[space.op] = space


def space_for(op: str) -> Optional[TuningSpace]:
    return SPACES.get(op)


def candidates(space: TuningSpace, context: Optional[Dict] = None) \
        -> List[Dict]:
    """Searchable candidate param dicts (fixed axes excluded), in
    declaration order — index 0 is today's default configuration."""
    context = context or {}
    if space.enumerate_fn is not None:
        return [dict(p) for p in space.enumerate_fn(context)]
    out: List[Dict] = [{}]
    for ax in space.axes:
        if ax.fixed:
            continue
        out = [dict(p, **{ax.name: c}) for p in out
               for c in ax.candidates]
    return out


def default_params(space: TuningSpace, context: Optional[Dict] = None) \
        -> Dict:
    """The candidate matching current (pre-tuner) behavior — the race
    baseline the acceptance bar compares against. ``default_fn`` wins
    when declared (matrixmult: ``schedule="auto"`` IS the comm-volume
    pick, not a fixed value); otherwise first in declaration order,
    with platform-dependent defaults resolved the way the env seams
    resolve them (``overlap=auto``: off on CPU sim, on on TPU)."""
    context = context or {}
    if space.default_fn is not None:
        return dict(space.default_fn(context))
    cands = candidates(space, context)
    dflt = dict(cands[0])
    if "overlap" in dflt and context.get("platform") == "tpu":
        # overlap=auto is ON on real TPU (utils/deps.py); pick the
        # first candidate carrying it
        for c in cands:
            if c.get("overlap") == "on":
                return dict(c)
    return dflt


def rank(space: TuningSpace, context: Dict) -> List[Dict]:
    """Candidates ordered by the cost seed (stable sort: ties keep
    declaration order, i.e. the default first)."""
    cands = candidates(space, context)
    if space.cost is None:
        return cands
    scored = []
    for i, p in enumerate(cands):
        try:
            c = space.cost(context, p)
        except Exception:
            c = None
        scored.append((c if c is not None else float("inf"), i, p))
    scored.sort(key=lambda t: (t[0], t[1]))
    return [p for _, _, p in scored]


def _default_matrixmult(context: Dict) -> Dict:
    """Today's ``schedule="auto"`` resolution: the comm-volume pick
    (ops/matrixmult.py) — what an untuned construction would run."""
    shape = context.get("shape") or (1, 1, 1)
    grid = tuple(context.get("extra", {}).get("grid") or (1, 1))
    from ..diagnostics.costmodel import summa_comm_volume
    vols = summa_comm_volume(int(shape[0]), int(shape[1]),
                             int(shape[2]), grid)
    return {"schedule": ("stat_a" if vols["stat_a"] < vols["gather"]
                         else "gather"),
            "overlap": ("on" if context.get("platform") == "tpu"
                        else "off")}


register_space(TuningSpace(
    op="matrixmult",
    axes=(Axis("schedule", ("gather", "stat_a")),
          Axis("overlap", ("off", "on")),
          Axis("hierarchical", ("auto", "on", "off")),
          Axis("comm_chunks", (1,), fixed=True),
          Axis("batch", (1, 2, 4, 8, 16, 32, 64), fixed=True)),
    cost=_cost_matrixmult,
    default_fn=_default_matrixmult,
    enumerate_fn=_enum_matrixmult,
    note="SUMMA forward schedule x ring overlap x (hybrid meshes only) "
         "hierarchical staging; chunking is carried by the ring step "
         "count, recorded for provenance only; batch is the solve's "
         "block width (keyed, never searched)"))

register_space(TuningSpace(
    op="fft",
    axes=(Axis("overlap", ("off", "on")),
          Axis("comm_chunks", (1, 2, 4, 8)),
          Axis("hierarchical", ("auto", "on", "off")),
          Axis("engine", ("resolved",), fixed=True)),
    cost=_cost_fft,
    enumerate_fn=_enum_fft,
    note="pencil-transpose chunking x (hybrid meshes only) two-level "
         "staging; the planar/complex engine is the global "
         "PYLOPS_MPI_TPU_FFT_MODE seam (complex-free HLO pins) "
         "— recorded in the plan, never flipped by the tuner"))

def _cost_sparse_tier(context: Dict, params: Dict) -> Optional[float]:
    """Dense-vs-sparse matmul tier seed: both tiers priced on the
    roofline (flops when a peak is known, always bytes). The sparse
    tier streams ``nnz`` triplets (value + two int32 indices); the
    dense tier streams the full ``N·M`` matrix — the crossover sits
    near ``nnz ≈ N·M·it/(it+8)`` (≈ N·M/3 at f32), so ≥90% sparsity
    picks sparse with a wide margin."""
    shape = context.get("shape") or (1, 1)
    N, M = int(shape[0]), int(shape[1])
    extra = context.get("extra") or {}
    nnz = int(extra.get("nnz") or N * M)
    it = int(extra.get("itemsize") or 4)
    nd = max(1, int(context.get("n_dev") or 1))
    pk = _peaks(context)
    bw = (pk.get("hbm_gbps") or 30.0) * 1e9
    if params.get("tier") == "sparse":
        bytes_ = nnz * (it + 8.0) / nd + (N + M) * it
        flops = 2.0 * nnz / nd
    else:
        bytes_ = N * M * float(it) / nd + (N + M) * it
        flops = 2.0 * N * M / nd
    t = bytes_ / bw
    if pk.get("flops"):
        t = max(t, flops / pk["flops"])
    return t


register_space(TuningSpace(
    op="sparse_matmult",
    axes=(Axis("tier", ("dense", "sparse")),),
    cost=_cost_sparse_tier,
    note="matmul storage tier: dense GEMM (MPIMatrixMult) vs nnz-"
         "scaled gather/segment-sum (MPISparseMatrixMult); nnz rides "
         "in the plan key's extra so the same logical shape can "
         "resolve differently per sparsity — tuning off always means "
         "dense (the bit-identity pin)"))

register_space(TuningSpace(
    op="blockdiag",
    axes=(Axis("normal_path", ("fused", "two_sweep")),
          Axis("tile", ("kernel_default",), fixed=True),
          Axis("batch", (1, 2, 4, 8, 16, 32, 64), fixed=True)),
    cost=_cost_blockdiag,
    enumerate_fn=_enum_blockdiag,
    note="fused (Pallas one-sweep) vs two-sweep normal "
         "equations; Pallas tile shape is fixed by the Mosaic 8x128 "
         "rule (ops/pallas_kernels.py), recorded for provenance"))

register_space(TuningSpace(
    op="stack",
    axes=(Axis("overlap", ("off", "on")),
          Axis("batch", (1, 2, 4, 8, 16, 32, 64), fixed=True)),
    cost=_cost_stack,
    note="batched adjoint reduction: partitioner psum vs explicit "
         "ring reduce-scatter"))

register_space(TuningSpace(
    op="derivative",
    axes=(Axis("overlap", ("off", "on")),),
    cost=_cost_halo_family,
    note="ghost strategy: bulk halo-extend vs interior/boundary split "
         "with in-flight ghost ppermutes"))

register_space(TuningSpace(
    op="halo",
    axes=(Axis("overlap", ("off", "on")),),
    cost=_cost_halo_family,
    note="repack from the pre-exchange block (select-merged) vs the "
         "post-exchange extended block"))

def _cost_ca(context: Dict, params: Dict) -> Optional[float]:
    """Latency-aware (α–β) seed for the communication-avoiding solver
    tier (solvers/ca.py): per-iteration time = operator-apply stream
    term (β, bytes/bandwidth) + all-reduce count x per-fabric latency
    floor (α, costmodel.ALLREDUCE_LATENCY_S). Classic CG pays 2
    sequential reductions; the pipelined engine pays ONE, issued
    before the apply so it hides behind it (max, not sum); s-step
    pays 1/s reductions but (2s-1)/s applies for the combined basis
    plus a conditioning-risk penalty growing with s."""
    from ..diagnostics.costmodel import allreduce_latency_s
    from ..solvers.ca import classic_reductions_per_iter
    mode = params.get("mode", "off")
    s = max(1, int(params.get("s", 1) or 1))
    fabric = ("dcn" if _fabric_of(context)
              else ("ici" if context.get("platform") == "tpu"
                    else "host"))
    lat = (allreduce_latency_s(fabric) or 0.0) + _dispatch_s(context)
    extra = context.get("extra", {})
    a_bytes = float(extra.get("a_bytes") or 0.0)
    pk = _peaks(context)
    nd = max(1, int(context.get("n_dev") or 1))
    t_apply = (a_bytes / nd / (pk["hbm_gbps"] * 1e9)
               if (a_bytes and pk.get("hbm_gbps")) else 0.0)
    solver = str(extra.get("solver") or "cg")
    try:
        red = float(classic_reductions_per_iter(solver))
    except KeyError:
        red = 2.0
    if mode == "off":
        return t_apply + red * lat
    if mode == "pipelined":
        # one reduction in flight behind the apply; the extra vector
        # recurrences add a small stream term
        return max(t_apply, lat) + 0.05 * t_apply
    # sstep: amortized latency, inflated basis work, breakdown risk
    return (t_apply * (2.0 * s - 1.0) / s + lat / s
            + 0.02 * (s - 1) * t_apply)


def _enum_ca(context: Dict) -> List[Dict]:
    """``s`` only varies under ``mode="sstep"`` — off/pipelined carry
    the canonical ``s=1`` so the candidate list (and the measurement
    budget) has no aliased trials."""
    return ([{"mode": "off", "s": 1}, {"mode": "pipelined", "s": 1}]
            + [{"mode": "sstep", "s": k} for k in (2, 4, 8)])


register_space(TuningSpace(
    op="ca",
    axes=(Axis("mode", ("off", "pipelined", "sstep")),
          Axis("s", (1, 2, 4, 8))),
    cost=_cost_ca,
    enumerate_fn=_enum_ca,
    note="communication-avoiding Krylov engine selection "
         "(solvers/ca.py): classic per-iteration reductions vs the "
         "single-stacked-reduction pipelined engine vs the s-step "
         "basis with one Gram reduction per s iterations; index 0 = "
         "off keeps the bit-identity default, PYLOPS_MPI_TPU_CA "
         "overrides any plan"))
