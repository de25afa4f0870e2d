"""Hierarchical-collectives tests (round 11,
``PYLOPS_MPI_TPU_HIERARCHICAL`` + ``PYLOPS_MPI_TPU_FABRIC``).

Four families of pins, per the hierarchical contract:
- **oracles** (ISSUE 11 satellite): operator results on
  ``make_mesh_hybrid(dcn_size=2)`` with 8 virtual devices are
  BIT-IDENTICAL to the flat 8-device mesh for SUMMA, the pencil FFTs,
  halo, derivatives, and fused CGLS. Baselines pin
  ``hierarchical="off"`` explicitly: with ``PYLOPS_MPI_TPU_FABRIC``
  exported, ``auto`` resolves ON even for flat-mesh operators.
- **off bit-identity**: ``PYLOPS_MPI_TPU_HIERARCHICAL=off`` lowers to
  EXACTLY the pre-round-11 HLO (text-identical modulo module names),
  even with a fabric declared.
- **per-fabric accounting**: the ≥3x DCN-byte reduction of the
  two-level schedules on a 2x4 hybrid mesh, counted by the cost model
  AND verified against the traced ``collective.*.bytes_dcn`` counters;
  flat meshes keep the legacy ``.bytes`` counter with NO per-fabric
  keys.
- **tuner seam**: plan keys gain ``topology_key()`` only on hybrid
  meshes (flat cache entries keep their keys verbatim), and a seeded
  hybrid-mesh cache entry flips the schedule while explicit kwargs and
  env pins still win.
"""


import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as PSpec

import pylops_mpi_tpu as pmt
from pylops_mpi_tpu import DistributedArray, MPIMatrixMult
from jax import shard_map
from pylops_mpi_tpu.parallel import collectives as C
from pylops_mpi_tpu.parallel.mesh import make_mesh, make_mesh_hybrid
from pylops_mpi_tpu.diagnostics import costmodel, metrics
from pylops_mpi_tpu.utils import hlo as H

P = len(jax.devices())

pytestmark = pytest.mark.skipif(P != 8, reason="hierarchical pins assume 8")

_STRIP = H.strip_provenance


@pytest.fixture
def fabric24(monkeypatch):
    """Declare the 8 virtual CPU devices to be 2 slices of 4."""
    monkeypatch.setenv("PYLOPS_MPI_TPU_FABRIC", "2x4")
    monkeypatch.delenv("PYLOPS_MPI_TPU_HIERARCHICAL", raising=False)


@pytest.fixture
def clean_metrics(monkeypatch):
    monkeypatch.setenv("PYLOPS_MPI_TPU_METRICS", "on")
    metrics.clear_metrics()
    yield
    metrics.clear_metrics()


def _counters():
    snap = metrics.snapshot()
    return snap.get("counters", snap)


# ------------------------------------------------------------ primitives
def test_ring_pass_hier_visits_every_block_once(fabric24, rng):
    """The two-level hop schedule still delivers every owner's block
    exactly once (owner labels correct at every step) — same invariant
    the flat ring pins in test_overlap, different visit order."""
    mesh = make_mesh()
    name = mesh.axis_names[0]
    x = jnp.asarray(rng.standard_normal((P, 3)))

    def f(xs):
        def kernel(xb):
            def body(acc, res, owner, s):
                part = res * (owner + 1)
                return part if acc is None else acc + part
            return C.ring_pass(xb, name, P, body, slice_size=4)
        return shard_map(kernel, mesh=mesh, in_specs=PSpec(name),
                         out_specs=PSpec(name), check_vma=False)(x)

    got = np.asarray(f(x)).reshape(P, 3)
    want = sum((o + 1) * np.asarray(x[o]) for o in range(P))
    np.testing.assert_allclose(got, np.tile(want, (P, 1)), rtol=1e-12)


def test_hier_psum_scatter_all_gather(fabric24, rng):
    """hier_psum_scatter matches the flat psum+slice oracle (same
    values, staged reduction); hier_all_gather is bit-identical."""
    mesh = make_mesh_hybrid(dcn_size=2)
    names = tuple(mesh.axis_names)
    x = jnp.asarray(rng.standard_normal((P, 16, 3)))

    def hier(xs):
        def kernel(xb):
            part = xb[0]  # (16, 3) per-device partial
            red = C.hier_psum_scatter(part, names[0], names[1], 2, 4)
            return C.hier_all_gather(red, names[0], names[1], 2, 4)[None]
        return shard_map(kernel, mesh=mesh, in_specs=PSpec(names),
                         out_specs=PSpec(names), check_vma=False)(xs)

    got = np.asarray(hier(x))[0]
    want = np.asarray(x).sum(axis=0)
    np.testing.assert_allclose(got, want, rtol=1e-12, atol=1e-12)


# ------------------------------------------------------------ oracles
@pytest.mark.slow  # CI test-hierarchical leg runs it every push
def test_summa_hybrid_bit_identical(fabric24, rng):
    """SUMMA on the hybrid mesh (fabric-aligned (2,4) grid, bulk and
    ring kernels) is bit-identical to the flat mesh, both schedules,
    forward and adjoint."""
    A = rng.standard_normal((24, 16))
    X = rng.standard_normal((16, 8))
    Y = rng.standard_normal((24, 8))
    mesh_f, mesh_h = make_mesh(), make_mesh_hybrid(dcn_size=2)
    for schedule in ("gather", "stat_a"):
        for overlap in ("off", "on"):
            off = MPIMatrixMult(A, 8, kind="summa", dtype=np.float64,
                                mesh=mesh_f, schedule=schedule,
                                overlap=overlap, hierarchical="off")
            hier = MPIMatrixMult(A, 8, kind="summa", dtype=np.float64,
                                 mesh=mesh_h, schedule=schedule,
                                 overlap=overlap, hierarchical="on")
            assert hier._hier
            dxf = DistributedArray.to_dist(X.ravel(), mesh=mesh_f)
            dxh = DistributedArray.to_dist(X.ravel(), mesh=mesh_h)
            assert np.array_equal(
                np.asarray(off.matvec(dxf).asarray()),
                np.asarray(hier.matvec(dxh).asarray())), (schedule, overlap)
            dyf = DistributedArray.to_dist(Y.ravel(), mesh=mesh_f)
            dyh = DistributedArray.to_dist(Y.ravel(), mesh=mesh_h)
            assert np.array_equal(
                np.asarray(off.rmatvec(dyf).asarray()),
                np.asarray(hier.rmatvec(dyh).asarray())), (schedule, overlap)


@pytest.mark.slow  # CI test-hierarchical leg runs it every push
def test_summa_hier_ring_slice_spanning_axis(fabric24, rng):
    """A (1, 8) grid puts the whole ring on a slice-spanning axis: the
    two-level hop schedule engages (``_ring_slice``), changing only
    the fp reduction order on the forward (adjoint placement is
    exact); off-vs-off stays bit-identical."""
    A = rng.standard_normal((24, 16))
    X = rng.standard_normal((16, 8))
    Y = rng.standard_normal((24, 8))
    mesh_f, mesh_h = make_mesh(), make_mesh_hybrid(dcn_size=2)
    off = MPIMatrixMult(A, 8, kind="summa", dtype=np.float64,
                        mesh=mesh_f, grid=(1, 8), schedule="gather",
                        overlap="on", hierarchical="off")
    hoff = MPIMatrixMult(A, 8, kind="summa", dtype=np.float64,
                         mesh=mesh_h, grid=(1, 8), schedule="gather",
                         overlap="on", hierarchical="off")
    hier = MPIMatrixMult(A, 8, kind="summa", dtype=np.float64,
                         mesh=mesh_h, grid=(1, 8), schedule="gather",
                         overlap="on", hierarchical="on")
    assert hier._ring_slice == 4 and hoff._ring_slice is None
    dxf = DistributedArray.to_dist(X.ravel(), mesh=mesh_f)
    dxh = DistributedArray.to_dist(X.ravel(), mesh=mesh_h)
    yf = np.asarray(off.matvec(dxf).asarray())
    assert np.array_equal(yf, np.asarray(hoff.matvec(dxh).asarray()))
    np.testing.assert_allclose(
        np.asarray(hier.matvec(dxh).asarray()).reshape(24, 8), A @ X,
        rtol=1e-10, atol=1e-12)
    dyh = DistributedArray.to_dist(Y.ravel(), mesh=mesh_h)
    dyf = DistributedArray.to_dist(Y.ravel(), mesh=mesh_f)
    # adjoint: owner-indexed placement, no accumulation -> exact
    assert np.array_equal(np.asarray(off.rmatvec(dyf).asarray()),
                          np.asarray(hier.rmatvec(dyh).asarray()))


@pytest.mark.parametrize(
    "engine",
    ["complex",
     pytest.param("planar", marks=pytest.mark.slow)])
@pytest.mark.parametrize(
    "chunks",
    [None,
     pytest.param(2, marks=pytest.mark.slow)])
def test_fft_hybrid_bit_identical(fabric24, monkeypatch, rng, engine,
                                  chunks):
    """Pencil FFT on the hybrid mesh (two-level transposes, bulk and
    chunked, both engines) is bit-identical to the flat mesh."""
    if engine == "planar":
        monkeypatch.setenv("PYLOPS_MPI_TPU_FFT_MODE", "planar")
    dims = (16, 8, 3)
    x = (rng.standard_normal(dims) + 1j * rng.standard_normal(dims)).ravel()
    mesh_f, mesh_h = make_mesh(), make_mesh_hybrid(dcn_size=2)
    kw = dict(comm_chunks=chunks, overlap="on" if chunks else "off")
    off = pmt.MPIFFTND(dims, axes=(0, 1), mesh=mesh_f,
                       hierarchical="off", **kw)
    hier = pmt.MPIFFTND(dims, axes=(0, 1), mesh=mesh_h,
                        hierarchical="on", **kw)
    dxf = DistributedArray.to_dist(x, mesh=mesh_f)
    dxh = DistributedArray.to_dist(x, mesh=mesh_h)
    yf = off.matvec(dxf)
    yh = hier.matvec(dxh)
    assert np.array_equal(np.asarray(yf.asarray()),
                          np.asarray(yh.asarray()))
    assert np.array_equal(np.asarray(off.rmatvec(yf).asarray()),
                          np.asarray(hier.rmatvec(yh).asarray()))


@pytest.mark.slow  # CI test-hierarchical leg runs it every push
def test_halo_hybrid_bit_identical(fabric24, rng):
    """Halo exchange is pure data movement: the hybrid-mesh kernels
    (tuple-axis ppermutes) are bit-identical to the flat ring."""
    from pylops_mpi_tpu.ops.halo import MPIHalo
    mesh_f, mesh_h = make_mesh(), make_mesh_hybrid(dcn_size=2)
    n = 3 * P
    x = rng.standard_normal(n)
    for halo in (1, 2):
        off = MPIHalo(dims=n, halo=halo, mesh=mesh_f, dtype=np.float64,
                      hierarchical="off")
        hier = MPIHalo(dims=n, halo=halo, mesh=mesh_h, dtype=np.float64,
                       hierarchical="on")
        dxf = DistributedArray.to_dist(x, mesh=mesh_f)
        dxh = DistributedArray.to_dist(x, mesh=mesh_h)
        yf, yh = off.matvec(dxf), hier.matvec(dxh)
        assert np.array_equal(np.asarray(yf.asarray()),
                              np.asarray(yh.asarray()))
        assert np.array_equal(np.asarray(off.rmatvec(yf).asarray()),
                              np.asarray(hier.rmatvec(yh).asarray()))
    # a multi-axis mesh WITHOUT the hierarchical route is still invalid
    with pytest.raises(ValueError, match="single-axis"):
        MPIHalo(dims=n, halo=1, mesh=mesh_h, dtype=np.float64,
                hierarchical="off")


@pytest.mark.slow  # CI test-hierarchical leg runs it every push
def test_derivative_hybrid_bit_identical(fabric24, rng):
    """Explicit stencils run on the hybrid mesh via the linearized-rank
    kernels, bit-identical to the flat mesh; hierarchical off falls
    back to the implicit GSPMD path (pre-round-11 behavior)."""
    from pylops_mpi_tpu.ops.derivatives import (MPIFirstDerivative,
                                                MPISecondDerivative)
    mesh_f, mesh_h = make_mesh(), make_mesh_hybrid(dcn_size=2)
    x = rng.standard_normal(3 * P * 5)
    for mk in (lambda m, h: MPIFirstDerivative((3 * P, 5), order=5,
                                               edge=True, mesh=m,
                                               hierarchical=h),
               lambda m, h: MPISecondDerivative((3 * P, 5), mesh=m,
                                                overlap="on",
                                                hierarchical=h)):
        off, hier = mk(mesh_f, "off"), mk(mesh_h, "on")
        dxf = DistributedArray.to_dist(x, mesh=mesh_f)
        dxh = DistributedArray.to_dist(x, mesh=mesh_h)
        yf, yh = off.matvec(dxf), hier.matvec(dxh)
        assert np.array_equal(np.asarray(yf.asarray()),
                              np.asarray(yh.asarray()))
        assert np.array_equal(np.asarray(off.rmatvec(yf).asarray()),
                              np.asarray(hier.rmatvec(yh).asarray()))
    assert mk(mesh_h, "off")._axes is None  # implicit fallback


def test_cgls_fused_hybrid_bit_identical(fabric24, rng):
    """Fused CGLS over a hybrid-mesh stencil operator reproduces the
    flat-mesh solve bit-for-bit (every iterate is built from the
    bit-identical matvec/rmatvec plus mesh-shape-independent psums)."""
    from pylops_mpi_tpu.ops.derivatives import MPISecondDerivative
    from pylops_mpi_tpu.solvers import cgls
    mesh_f, mesh_h = make_mesh(), make_mesh_hybrid(dcn_size=2)
    n = 3 * P * 4
    y = rng.standard_normal(n)
    xs = {}
    for tag, mesh, hier in (("flat", mesh_f, "off"), ("hyb", mesh_h, "on")):
        Op = MPISecondDerivative((3 * P, 4), mesh=mesh, hierarchical=hier)
        dy = DistributedArray.to_dist(y, mesh=mesh)
        x0 = DistributedArray.to_dist(np.zeros(n), mesh=mesh)
        x, *_ = cgls(Op, dy, x0, niter=20, tol=0.0, fused=True)
        xs[tag] = np.asarray(x.asarray())
    assert np.array_equal(xs["flat"], xs["hyb"])


# ------------------------------------------------------ off HLO identity
def test_hier_off_hlo_bit_identical(fabric24, monkeypatch, rng):
    """With a fabric declared AND ``PYLOPS_MPI_TPU_HIERARCHICAL=off``,
    flat-mesh operators lower to exactly the pre-round-11 HLO (the
    baseline built with both knobs unset)."""
    A = rng.standard_normal((24, 16))
    X = rng.standard_normal((16, 8))
    dx = DistributedArray.to_dist(X.ravel())

    def build():
        return MPIMatrixMult(A, 8, kind="summa", dtype=np.float64,
                             schedule="gather", overlap="on")

    monkeypatch.delenv("PYLOPS_MPI_TPU_FABRIC", raising=False)
    monkeypatch.delenv("PYLOPS_MPI_TPU_HIERARCHICAL", raising=False)
    base = H.compiled_hlo(jax.jit(build()._matvec), dx)
    monkeypatch.setenv("PYLOPS_MPI_TPU_FABRIC", "2x4")
    monkeypatch.setenv("PYLOPS_MPI_TPU_HIERARCHICAL", "off")
    off = H.compiled_hlo(jax.jit(build()._matvec), dx)
    assert _STRIP(off) == _STRIP(base)


def test_hier_off_hlo_bit_identical_derivative(fabric24, monkeypatch,
                                               rng):
    from pylops_mpi_tpu.ops.derivatives import MPIFirstDerivative
    x = DistributedArray.to_dist(rng.standard_normal(3 * P * 4))

    def build():
        return MPIFirstDerivative((3 * P, 4), dtype=np.float64)

    monkeypatch.delenv("PYLOPS_MPI_TPU_FABRIC", raising=False)
    monkeypatch.delenv("PYLOPS_MPI_TPU_HIERARCHICAL", raising=False)
    base = H.compiled_hlo(jax.jit(build()._matvec), x)
    monkeypatch.setenv("PYLOPS_MPI_TPU_FABRIC", "2x4")
    monkeypatch.setenv("PYLOPS_MPI_TPU_HIERARCHICAL", "off")
    off = H.compiled_hlo(jax.jit(build()._matvec), x)
    assert _STRIP(off) == _STRIP(base)


# ------------------------------------------------- per-fabric accounting
def test_pencil_dcn_reduction_model_vs_trace(fabric24, clean_metrics,
                                             rng):
    """Acceptance: DCN bytes per pencil transpose on the 2x4 hybrid
    mesh drop >= 3x vs the flat (topology-blind) schedule — the cost
    model says so, and its hierarchical-side prediction matches the
    traced ``collective.hier_pencil_transpose.bytes_dcn`` exactly."""
    dims = (16, 8, 4)
    itemsize = 16  # c128 under the suite's x64 config
    hier_cost = costmodel.pencil_transpose_cost(
        dims, P, itemsize=itemsize, n_transposes=1,
        fabric_shape=(2, 4), hierarchical=True)
    flat_cost = costmodel.pencil_transpose_cost(
        dims, P, itemsize=itemsize, n_transposes=1,
        fabric_shape=(2, 4), hierarchical=False)
    assert flat_cost.dcn_bytes / hier_cost.dcn_bytes >= 3.0
    # trace the hierarchical schedule: 2 transposes per forward apply
    mesh_h = make_mesh_hybrid(dcn_size=2)
    Op = pmt.MPIFFTND(dims, axes=(0, 1), mesh=mesh_h, hierarchical="on")
    x = (rng.standard_normal(dims) + 1j * rng.standard_normal(dims)).ravel()
    _ = Op.matvec(DistributedArray.to_dist(x, mesh=mesh_h))
    cnt = _counters()
    traced_dcn = cnt.get("collective.hier_pencil_transpose.bytes_dcn", 0)
    traced_ici = cnt.get("collective.hier_pencil_transpose.bytes_ici", 0)
    assert traced_dcn == 2 * hier_cost.dcn_bytes
    assert traced_ici == 2 * hier_cost.ici_bytes
    assert flat_cost.dcn_bytes / (traced_dcn / 2) >= 3.0


@pytest.mark.slow  # CI test-hierarchical leg runs it every push
def test_summa_dcn_reduction_model_vs_trace(fabric24, clean_metrics,
                                            rng):
    """Acceptance: DCN bytes per SUMMA ring step on the 2x4 hybrid
    mesh drop >= 3x. Model side: the topology-blind charge vs the
    fabric-aligned split. Trace side: the flat ring on a slice-spanning
    (1, 8) axis crosses DCN on 7 of 7 hops; the two-level hop schedule
    crosses once — both counted by ``collective.ring_pass.bytes_dcn``."""
    # cost model: blind-vs-aligned attribution on the (2, 4) grid
    split = costmodel.summa_comm_volume_split(32, 32, 32, (2, 4))
    g = split["gather"]
    blind_dcn = g["r"] + g["c"]  # no pinned axis->fabric assignment
    aligned_dcn = g["r"]         # rows = slices on the aligned layout
    assert blind_dcn / aligned_dcn >= 3.0
    # traced: one jitted forward of each (1, 8)-grid ring
    A = rng.standard_normal((24, 16))
    X = rng.standard_normal((16, 8))
    mesh_h = make_mesh_hybrid(dcn_size=2)
    dcn_per = {}
    for tag, hier in (("flat", "off"), ("hier", "on")):
        metrics.clear_metrics()
        Op = MPIMatrixMult(A, 8, kind="summa", dtype=np.float64,
                           mesh=mesh_h, grid=(1, 8), schedule="gather",
                           overlap="on", hierarchical=hier)
        _ = Op.matvec(DistributedArray.to_dist(X.ravel(), mesh=mesh_h))
        dcn_per[tag] = _counters().get("collective.ring_pass.bytes_dcn", 0)
    assert dcn_per["flat"] > 0 and dcn_per["hier"] > 0
    assert dcn_per["flat"] / dcn_per["hier"] >= 3.0


def test_flat_mesh_keeps_legacy_byte_counters(clean_metrics, monkeypatch,
                                              rng):
    """Satellite regression: with no fabric declared, a flat-mesh ring
    emits ONLY the legacy ``.bytes`` counter — no per-fabric keys."""
    monkeypatch.delenv("PYLOPS_MPI_TPU_FABRIC", raising=False)
    A = rng.standard_normal((24, 16))
    X = rng.standard_normal((16, 8))
    Op = MPIMatrixMult(A, 8, kind="summa", dtype=np.float64,
                       schedule="gather", overlap="on")
    _ = Op.matvec(DistributedArray.to_dist(X.ravel()))
    cnt = _counters()
    assert cnt.get("collective.ring_pass.bytes", 0) > 0
    assert "collective.ring_pass.bytes_ici" not in cnt
    assert "collective.ring_pass.bytes_dcn" not in cnt


def test_aggregator_stamps_fabric(fabric24):
    """PR 9 aggregator satellite: matched collectives carry the fabric
    tag their spans were stamped with."""
    from pylops_mpi_tpu.diagnostics.aggregate import merge_traces
    ev = lambda ts, seq, fab: {
        "name": "collective.ring_pass", "cat": "collective", "ph": "X",
        "ts": ts, "dur": 5.0, "pid": 0,
        "args": {"seq": seq, **({"fabric": fab} if fab else {})}}
    out = merge_traces({0: [ev(10.0, 0, "dcn"), ev(30.0, 1, None)],
                        1: [ev(12.0, 0, "dcn"), ev(31.0, 1, None)]})
    recs = {r["seq"]: r for r in out["collectives"]}
    assert recs[0]["fabric"] == "dcn"
    assert "fabric" not in recs[1]


# ------------------------------------------------------------ tuner seam
def test_plan_key_topology_component(fabric24):
    """Hybrid meshes stamp ``topology_key()`` into plan keys; flat
    meshes contribute NOTHING — pre-round-11 cache entries keep their
    keys byte-for-byte."""
    from pylops_mpi_tpu.tuning import plan as tplan
    base = tplan.plan_key("matrixmult", (24, 16, 8), np.float64, 8,
                          ("sp",), {"grid": (2, 4)})
    # empty topology == absent topology (the flat-key regression)
    assert tplan.plan_key("matrixmult", (24, 16, 8), np.float64, 8,
                          ("sp",), {"grid": (2, 4), "topology": ""}) == base
    hyb = tplan.plan_key("matrixmult", (24, 16, 8), np.float64, 8,
                         ("sp",), {"grid": (2, 4),
                                   "topology": "dcn2xici4"})
    assert hyb != base and "dcn2xici4" in hyb


def test_seeded_hybrid_plan_flips_hierarchical(fabric24, monkeypatch,
                                               rng):
    """A cached hybrid-mesh plan fills the ``hierarchical`` sentinel;
    explicit kwargs and env pins still win."""
    from pylops_mpi_tpu.tuning import plan as tplan
    from pylops_mpi_tpu.tuning import cache as tcache
    from pylops_mpi_tpu.utils.deps import batch_default
    monkeypatch.setenv("PYLOPS_MPI_TPU_TUNE", "on")
    monkeypatch.delenv("PYLOPS_MPI_TPU_TUNE_CACHE", raising=False)
    tcache.clear_memory()
    tplan.reset_applied()
    try:
        A = rng.standard_normal((24, 16))
        mesh_h = make_mesh_hybrid(dcn_size=2)
        key = tplan.plan_key("matrixmult", (24, 16, 8), np.float64, 8,
                             ("dcn", "sp"),
                             {"grid": (2, 4), "batch": batch_default(),
                              "topology": "dcn2xici4"})
        tcache.store(key, {"params": {"schedule": "gather",
                                      "overlap": "off",
                                      "hierarchical": "off"},
                           "provenance": "tuned"})
        # plan fills the sentinel: hierarchical comes back OFF even
        # though auto would resolve ON under the declared fabric
        op = MPIMatrixMult(A, 8, kind="summa", dtype=np.float64,
                           mesh=mesh_h)
        assert op.schedule == "gather" and not op._hier
        # explicit kwarg beats the plan
        op2 = MPIMatrixMult(A, 8, kind="summa", dtype=np.float64,
                            mesh=mesh_h, hierarchical="on")
        assert op2._hier
        # explicit env pin beats the plan too
        monkeypatch.setenv("PYLOPS_MPI_TPU_HIERARCHICAL", "on")
        op3 = MPIMatrixMult(A, 8, kind="summa", dtype=np.float64,
                            mesh=mesh_h)
        assert op3._hier
    finally:
        tcache.clear_memory()
        tplan.reset_applied()


def test_space_has_hierarchical_axis(fabric24):
    """The matrixmult/fft tuning spaces expose the schedule dimension
    (and validate old flat-mesh params that lack it)."""
    from pylops_mpi_tpu.tuning import space as tspace
    for op in ("matrixmult", "fft"):
        sp = tspace.space_for(op)
        assert sp is not None and sp.axis("hierarchical") is not None
    sp = tspace.space_for("matrixmult")
    # params recorded before round 11 (no hierarchical key) stay valid
    assert sp.validate({"schedule": "gather", "overlap": "off"})
    assert sp.validate({"schedule": "gather", "hierarchical": "on"})
