"""Bounded-memory resharding planner (ISSUE 13 tentpole).

Pins, per the round-13 contract:

- **cost model**: exact per-pair bytes, a ``min_budget`` floor, and a
  chunk count that keeps ``peak_scratch <= budget`` — asserted on the
  plan itself, then cross-checked against live results;
- **ragged everything**: N=45 regrids across 2/4/8-device worlds,
  masked arrays, SCATTER axes shorter than the target world;
- **bit-identity**: an A→B→A round trip returns the exact bits;
- **refusals name the cure**: an impossible budget raises
  :class:`ReshardError` carrying (and printing) the minimum budget
  that would succeed;
- **accounting**: ``collective.reshard`` spans with per-step events,
  bytes split ici/dcn under ``PYLOPS_MPI_TPU_FABRIC``, chunk counts in
  the round-5 tuning space.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from pylops_mpi_tpu import DistributedArray
from pylops_mpi_tpu.parallel import reshard as R
from pylops_mpi_tpu.parallel import collectives as C
from pylops_mpi_tpu.parallel import topology
from pylops_mpi_tpu.parallel.mesh import make_mesh, set_default_mesh
from pylops_mpi_tpu.parallel.partition import Partition, local_split
from pylops_mpi_tpu.diagnostics import trace

F64 = np.dtype(np.float64).itemsize


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    monkeypatch.delenv("PYLOPS_MPI_TPU_RESHARD_BUDGET", raising=False)
    # this file pins the DEVICE planner (chunk accounting, nbytes,
    # refusal messages); the spill-forced mirror of the same matrix
    # lives in test_spill.py, so a CI leg's SPILL=on must not leak in
    monkeypatch.delenv("PYLOPS_MPI_TPU_SPILL", raising=False)
    yield
    set_default_mesh(None)


def _sizes(n, world):
    return tuple(s[0] for s in local_split((n,), world,
                                           Partition.SCATTER, 0))


# ------------------------------------------------------------ cost model
def test_budget_env_parsing(monkeypatch):
    monkeypatch.setenv("PYLOPS_MPI_TPU_RESHARD_BUDGET", "8m")
    assert R.reshard_budget() == 8 << 20
    monkeypatch.setenv("PYLOPS_MPI_TPU_RESHARD_BUDGET", "512k")
    assert R.reshard_budget() == 512 << 10
    monkeypatch.setenv("PYLOPS_MPI_TPU_RESHARD_BUDGET", "2g")
    assert R.reshard_budget() == 2 << 30
    monkeypatch.setenv("PYLOPS_MPI_TPU_RESHARD_BUDGET", "4096")
    assert R.reshard_budget() == 4096
    monkeypatch.delenv("PYLOPS_MPI_TPU_RESHARD_BUDGET")
    assert R.reshard_budget() is None
    monkeypatch.setenv("PYLOPS_MPI_TPU_RESHARD_BUDGET", "lots")
    with pytest.raises(ValueError, match="k/m/g"):
        R.reshard_budget()
    monkeypatch.setenv("PYLOPS_MPI_TPU_RESHARD_BUDGET", "-3")
    with pytest.raises(ValueError, match="positive"):
        R.reshard_budget()


def test_plan_uneven_regrid_cost_model():
    """The 45-row 8→4 regrid that used to be impossible: exact totals,
    scratch bounded by the budget, step bytes summing to the plan."""
    src = R.Layout.scatter(_sizes(45, 8))
    dst = R.Layout.scatter(_sizes(45, 4))
    plan = R.plan_reshard((45,), F64, src, dst)
    assert plan.kind == "ppermute"  # same-axis interval exchange
    # interval overlap, rank-identity diagonal removed: shards 0..7 of
    # 45 rows = (6,6,6,6,6,6,6,3), dst = (12,12,11,10); bytes that
    # actually cross devices are everything landing off-diagonal
    assert plan.nbytes > 0 and plan.nbytes % F64 == 0
    assert plan.min_budget == 2 * (45 * F64 // 45)  # 2 live row-buffers
    assert plan.peak_scratch >= plan.min_budget
    assert sum(s.nbytes for s in plan.steps) == plan.nbytes

    tight = R.plan_reshard((45,), F64, src, dst, budget=plan.min_budget)
    assert tight.peak_scratch <= plan.min_budget
    assert tight.chunks >= plan.chunks


def test_plan_budget_refusal_names_minimum():
    src = R.Layout.scatter(_sizes(45, 8))
    dst = R.Layout.scatter(_sizes(45, 4))
    with pytest.raises(R.ReshardError, match="minimum budget") as ei:
        R.plan_reshard((45,), F64, src, dst, budget=1)
    need = ei.value.min_budget
    assert need > 1 and str(need) in str(ei.value)
    plan = R.plan_reshard((45,), F64, src, dst, budget=need)
    assert plan.peak_scratch <= need


@pytest.mark.parametrize("budget_rows", [2, 4, 45])
def test_plan_peak_scratch_monotone(budget_rows):
    """More budget → no more chunks; scratch always under budget."""
    src = R.Layout.scatter(_sizes(45, 8))
    dst = R.Layout.scatter(_sizes(45, 2))
    budget = budget_rows * F64
    plan = R.plan_reshard((45,), F64, src, dst, budget=budget)
    assert plan.peak_scratch <= budget
    assert plan.budget == budget


def test_plan_axis_change_product_measure():
    """2-D regrid axis 0→1 plans as all_to_all with the product-measure
    byte count (every off-diagonal pair exchanges r_i x c_j)."""
    src = R.Layout.scatter(_sizes(45, 8), axis=0)
    dst = R.Layout.scatter(_sizes(16, 8), axis=1)
    plan = R.plan_reshard((45, 16), F64, src, dst)
    assert plan.kind == "all_to_all"
    total = 45 * 16 * F64
    r = np.asarray(_sizes(45, 8), float) / 45
    c = np.asarray(_sizes(16, 8), float) / 16
    B = total * r[:, None] * c[None, :]
    np.fill_diagonal(B, 0.0)
    assert plan.nbytes == int(round(B.sum()))


def test_plan_fabric_split_sums_to_total(monkeypatch):
    """Under FABRIC=2x4 the mesh spans two slices: per-pair bytes are
    attributed ici (same slice) or dcn (cross slice) and the split sums
    back to the legacy total."""
    monkeypatch.setenv("PYLOPS_MPI_TPU_FABRIC", "2x4")
    mesh = make_mesh(8)
    sm = topology.slice_map(mesh)
    assert sm is not None
    src = R.Layout.scatter(_sizes(45, 8))
    dst = R.Layout.scatter(_sizes(45, 4))
    plan = R.plan_reshard((45,), F64, src, dst, slice_ids=sm)
    assert plan.nbytes_ici is not None and plan.nbytes_dcn is not None
    assert plan.nbytes_ici + plan.nbytes_dcn == plan.nbytes
    assert plan.nbytes_dcn > 0  # dst shard 1 straddles the slice seam


# --------------------------------------------------------- live reshards
@pytest.mark.parametrize("world", [2, 4, 8])
def test_reshard_ragged_shrink_worlds(world, ndev):
    """N=45 placed on the full mesh, replanned onto 2/4/8-device
    worlds: exact values, scratch bounded, trace span emitted."""
    if world > ndev:
        pytest.skip("needs more devices")
    v = np.arange(45.0)
    x = DistributedArray.to_dist(v, mesh=make_mesh(ndev))
    sub = make_mesh(world)
    budget = 16 * F64
    out = R.reshard(x, mesh=sub, budget=budget)
    assert out.mesh is sub and out.n_shards == world
    np.testing.assert_array_equal(out.asarray(), v)
    plan = R.plan_reshard((45,), F64,
                          R.Layout.scatter(_sizes(45, ndev)),
                          R.Layout.scatter(_sizes(45, world)),
                          budget=budget)
    assert plan.peak_scratch <= budget


def test_reshard_round_trip_bit_identical(ndev):
    """A→B→A returns the exact bits (f64 row moves, no arithmetic)."""
    if ndev < 8:
        pytest.skip("needs 8 devices")
    rng = np.random.default_rng(3)
    v = rng.standard_normal(45)
    a = DistributedArray.to_dist(v, mesh=make_mesh(8))
    b = R.reshard(a, mesh=make_mesh(4), budget=8 * F64)
    back = R.reshard(b, mesh=make_mesh(8), budget=8 * F64)
    assert back.local_shapes == a.local_shapes
    assert np.array_equal(np.asarray(back.asarray()), v)
    assert np.array_equal(np.asarray(back._arr), np.asarray(a._arr))


def test_reshard_axis_regrid_values(ndev, rng):
    v = rng.standard_normal((45, 2 * ndev))
    x = DistributedArray.to_dist(v, mesh=make_mesh(ndev))
    out = R.reshard(x, axis=1)
    assert out.axis == 1
    np.testing.assert_array_equal(out.asarray(), v)


def test_reshard_mask_rules(ndev):
    if ndev < 8:
        pytest.skip("needs 8 devices")
    mesh8, mesh4 = make_mesh(8), make_mesh(4)
    x = DistributedArray.to_dist(np.arange(16.0), mesh=mesh8,
                                 mask=[0, 0, 0, 0, 1, 1, 1, 1])
    # same shard count: the mask survives
    kept = R.reshard(x, mesh=mesh8, axis=0)
    assert kept.mask == x.mask
    # changed world: refuse (mask colors are per-shard)
    with pytest.raises(R.ReshardError, match="mask"):
        R.reshard(x, mesh=mesh4)


def test_reshard_short_axis_refuses_cross_mesh():
    small = make_mesh(2)
    x = DistributedArray.to_dist(np.arange(3.0), mesh=small)
    with pytest.raises(R.ReshardError, match="zero rows"):
        R.reshard(x, mesh=make_mesh(4))


def test_redistribute_short_axis_same_mesh_still_works(ndev, rng):
    """dim < n_shards on the SAME device set is legacy redistribute
    behavior (zero-row shards) — the planner must not regress it."""
    v = rng.standard_normal((2 * ndev, ndev - 2 if ndev > 2 else 1))
    x = DistributedArray.to_dist(v, mesh=make_mesh(ndev))
    out = x.redistribute(1)
    assert out.axis == 1
    np.testing.assert_array_equal(out.asarray(), v)


def test_place_replica_budgeted(ndev, rng):
    v = rng.standard_normal(45)
    mesh = make_mesh(ndev)
    out = R.place_replica(v, mesh, budget=8 * F64)
    assert out.n_shards == ndev
    np.testing.assert_array_equal(out.asarray(), v)


def test_reshard_trace_span_and_steps(ndev, monkeypatch):
    if ndev < 8:
        pytest.skip("needs 8 devices")
    monkeypatch.setenv("PYLOPS_MPI_TPU_TRACE", "full")
    trace.clear_events()
    x = DistributedArray.to_dist(np.arange(45.0), mesh=make_mesh(8))
    R.reshard(x, mesh=make_mesh(4), chunks=3)
    names = [e.get("name") for e in trace.get_events()]
    assert "collective.reshard" in names
    assert names.count("collective.reshard.step") >= 3
    trace.clear_events()


def test_jit_same_mesh_reshard(ndev, rng):
    """Same-device-set moves are jit-safe: a traced reshard of a
    ragged array round-trips exactly under jax.jit."""
    mesh = make_mesh(ndev)
    v = rng.standard_normal(45)
    x = DistributedArray.to_dist(v, mesh=mesh)

    def f(arr):
        xx = DistributedArray._wrap(arr, x)
        return R.reshard(xx, partition=Partition.BROADCAST)._arr

    got = jax.jit(f)(x._arr)
    np.testing.assert_array_equal(np.asarray(got), v)


def test_raw_non_divisible_traced(ndev, rng):
    """The planner-backed all_to_all fallback stays shard_map/jit
    compatible (pad-and-crop, static indices only)."""
    if ndev < 2:
        pytest.skip("needs 2+ devices")
    mesh = make_mesh(ndev)
    v = rng.standard_normal((ndev + 1, 2 * ndev))

    def f(xx):
        return C.all_to_all_resharding(jnp.asarray(xx), mesh,
                                       old_axis=0, new_axis=1)

    got = jax.jit(f)(v)
    np.testing.assert_array_equal(np.asarray(got), v)
