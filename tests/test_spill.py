"""Host-RAM spill tier (ISSUE 14 tentpole).

Pins, per the round-14 contract:

- **refusal → schedule**: a move the device planner refuses (budget
  below ``factor * row_bytes``) completes host-staged under
  ``PYLOPS_MPI_TPU_SPILL=auto``, bit-identical to the unbounded
  oracle; ``off`` keeps the round-13 refusal (message and
  ``min_budget``) bit-identical;
- **the floor moves, it does not vanish**: a spilled plan needs one
  live staging buffer, so ``min_budget`` drops to one chunk row —
  and a budget below THAT still refuses, naming the minimum;
- **spill-forced mirror** of the reshard matrix: N=45 round trips
  across 2/4/8-device worlds, BROADCAST↔SCATTER, hybrid meshes, all
  with ``spill="on"`` and ``cost_model() <= budget``;
- **host residency**: an over-budget destination comes back as a
  :class:`HostArray` (no device allocation), usable as a reshard
  source; ``to_host``/``to_device`` round-trip exactly;
- **accounting**: ``host_stage`` steps carry h2d/d2h bytes in trace
  events, the metrics registry lands them in ``bytes_h2d``/
  ``bytes_d2h`` (never the legacy ``.bytes``), and the totals
  cross-check against the plan;
- **refusals name the fabric** (satellite bugfix): on a hybrid mesh
  the refusal message names the ``topology_key``.
"""

import os

import numpy as np
import pytest
import jax

from pylops_mpi_tpu import DistributedArray
from pylops_mpi_tpu.parallel import reshard as R
from pylops_mpi_tpu.parallel import spill as S
from pylops_mpi_tpu.parallel import topology
from pylops_mpi_tpu.parallel.mesh import (make_mesh, make_mesh_hybrid,
                                          set_default_mesh)
from pylops_mpi_tpu.parallel.partition import Partition, local_split
from pylops_mpi_tpu.diagnostics import trace
from pylops_mpi_tpu.diagnostics import metrics

F64 = np.dtype(np.float64).itemsize


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    monkeypatch.delenv("PYLOPS_MPI_TPU_RESHARD_BUDGET", raising=False)
    monkeypatch.delenv("PYLOPS_MPI_TPU_SPILL", raising=False)
    monkeypatch.delenv("PYLOPS_MPI_TPU_FAULT_KILL_SPILL", raising=False)
    yield
    set_default_mesh(None)


def _sizes(n, world):
    return tuple(s[0] for s in local_split((n,), world,
                                           Partition.SCATTER, 0))


# --------------------------------------------------------- mode seam
def test_spill_mode_resolution(monkeypatch):
    from pylops_mpi_tpu.utils import deps
    monkeypatch.delenv("PYLOPS_MPI_TPU_SPILL", raising=False)
    assert deps.spill_mode() == "auto"
    monkeypatch.setenv("PYLOPS_MPI_TPU_SPILL", "on")
    assert deps.spill_mode() == "on"
    monkeypatch.setenv("PYLOPS_MPI_TPU_SPILL", "OFF")
    assert deps.spill_mode() == "off"
    monkeypatch.setenv("PYLOPS_MPI_TPU_SPILL", "bogus")
    assert deps.spill_mode() == "auto"   # warn-and-default, never crash


def test_plan_rejects_unknown_spill_kwarg():
    src = R.Layout.scatter(_sizes(45, 8))
    dst = R.Layout.scatter(_sizes(45, 4))
    with pytest.raises(ValueError, match="spill"):
        R.plan_reshard((45,), F64, src, dst, spill="sideways")


# ------------------------------------------------- planner semantics
def test_auto_spills_only_refused_plans():
    """The auto-mode invariant: any budget the device planner accepts
    produces a byte-for-byte identical plan whether spill is auto or
    off — the spill tier only exists past the refusal line."""
    src = R.Layout.scatter(_sizes(45, 8))
    dst = R.Layout.scatter(_sizes(45, 4))
    for budget in (None, 2 * F64, 16 * F64, 45 * 2 * F64):
        a = R.plan_reshard((45,), F64, src, dst, budget=budget,
                           spill="auto")
        b = R.plan_reshard((45,), F64, src, dst, budget=budget,
                           spill="off")
        assert a == b
        assert not a.spilled
    # one row under the device floor: off refuses, auto spills
    low = 2 * F64 - 1
    with pytest.raises(R.ReshardError) as ei:
        R.plan_reshard((45,), F64, src, dst, budget=low, spill="off")
    assert ei.value.min_budget == 2 * F64
    plan = R.plan_reshard((45,), F64, src, dst, budget=low, spill="auto")
    assert plan.spilled
    assert all(s.kind == "host_stage" for s in plan.steps)
    assert plan.kind == "ppermute"    # logical family survives
    assert plan.min_budget == F64     # the spilled floor: one row


def test_spilled_floor_still_refuses():
    """Even the host path stages one row at a time: a budget below
    ``row_bytes`` refuses under every mode, names the minimum, and
    carries it on the exception."""
    src = R.Layout.scatter(_sizes(45, 8))
    dst = R.Layout.scatter(_sizes(45, 4))
    for spill in ("auto", "on"):
        with pytest.raises(R.ReshardError, match="minimum budget") as ei:
            R.plan_reshard((45,), F64, src, dst, budget=F64 - 1,
                           spill=spill)
        assert ei.value.min_budget == F64
        assert str(F64) in str(ei.value)


def test_spilled_cost_model_under_budget():
    """``cost_model()`` (modeled peak device scratch) respects the
    budget on spilled plans, and the h2d/d2h totals equal the moved
    payload for a device→device staging."""
    src = R.Layout.scatter(_sizes(45, 8))
    dst = R.Layout.scatter(_sizes(45, 4))
    for rows_budget in (1, 2, 5, 16):
        budget = rows_budget * F64
        plan = R.plan_reshard((45,), F64, src, dst, budget=budget,
                              spill="on", dst_host=False)
        assert plan.spilled
        assert plan.cost_model() <= budget
        assert plan.peak_scratch <= budget
        assert plan.nbytes == 0          # nothing crosses the fabric
        assert plan.nbytes_h2d == 45 * F64
        assert plan.nbytes_d2h == 45 * F64


def test_spilled_host_dst_resolution():
    """``dst_host=None`` goes to host RAM exactly when the
    destination's per-device footprint exceeds the budget; a host
    destination has no H2D half, a host source no D2H half."""
    src = R.Layout.scatter(_sizes(45, 8))
    dst = R.Layout.scatter(_sizes(45, 4))   # largest dst shard: 12 rows
    on_dev = R.plan_reshard((45,), F64, src, dst, budget=12 * F64,
                            spill="on")
    assert not on_dev.host_dst and on_dev.nbytes_h2d == 45 * F64
    to_host = R.plan_reshard((45,), F64, src, dst, budget=11 * F64,
                             spill="on")
    assert to_host.host_dst and to_host.nbytes_h2d == 0
    assert to_host.dst_device_bytes == 12 * F64
    from_host = R.plan_reshard((45,), F64, R.Layout.replicated(1), dst,
                               budget=12 * F64, spill="on", src_host=True)
    assert from_host.nbytes_d2h == 0
    assert from_host.nbytes_h2d == 45 * F64


# ---------------------------------------- spill-forced mirror matrix
@pytest.mark.parametrize("world", [2, 4, 8])
def test_spill_round_trip_worlds(world, ndev):
    """The reshard matrix with host staging forced on: N=45 A→B→A
    across shrunk worlds returns the exact bits, scratch bounded."""
    if world > ndev:
        pytest.skip("needs more devices")
    rng = np.random.default_rng(7)
    v = rng.standard_normal(45)
    a = DistributedArray.to_dist(v, mesh=make_mesh(ndev))
    budget = 16 * F64
    b = R.reshard(a, mesh=make_mesh(world), budget=budget, spill="on",
                  host_dst=False)
    assert isinstance(b, DistributedArray) and b.n_shards == world
    back = R.reshard(b, mesh=make_mesh(ndev), budget=budget, spill="on",
                     host_dst=False)
    assert back.local_shapes == a.local_shapes
    assert np.array_equal(np.asarray(back.asarray()), v)
    assert np.array_equal(np.asarray(back._arr), np.asarray(a._arr))


def test_spill_broadcast_scatter_round_trip(ndev, rng):
    v = rng.standard_normal(45)
    x = DistributedArray.to_dist(v, mesh=make_mesh(ndev))
    bc = R.reshard(x, partition=Partition.BROADCAST, budget=45 * F64,
                   spill="on", host_dst=False)
    assert bc.partition == Partition.BROADCAST
    np.testing.assert_array_equal(np.asarray(bc.asarray()), v)
    sc = R.reshard(bc, partition=Partition.SCATTER, axis=0,
                   budget=16 * F64, spill="on", host_dst=False)
    assert sc.partition == Partition.SCATTER
    assert np.array_equal(np.asarray(sc.asarray()), v)
    assert np.array_equal(np.asarray(sc._arr), np.asarray(x._arr))


def test_spill_hybrid_mesh_round_trip(monkeypatch, ndev, rng):
    if ndev < 8:
        pytest.skip("needs 8 devices")
    monkeypatch.setenv("PYLOPS_MPI_TPU_FABRIC", "2x4")
    mesh = make_mesh_hybrid(dcn_size=2)
    v = rng.standard_normal(45)
    x = DistributedArray.to_dist(v, mesh=mesh)
    regrid = tuple(reversed(_sizes(45, 8)))   # ragged re-split
    out = R.reshard(x, axis=0, local_shapes=[(s,) for s in regrid],
                    budget=8 * F64, spill="on", host_dst=False,
                    chunks=5)
    assert out._axis_sizes == regrid
    np.testing.assert_array_equal(np.asarray(out.asarray()), v)


def test_spill_oversized_vs_oracle(ndev, rng):
    """The acceptance shape: an oversized-destination move that the
    device planner refuses completes via host staging, bit-identical
    to the unbounded oracle."""
    if ndev < 8:
        pytest.skip("needs 8 devices")
    M = rng.standard_normal((64, 8))
    x = DistributedArray.to_dist(M, mesh=make_mesh(8))
    budget = 8 * F64   # one 64-byte row; the all_gather needs two
    with pytest.raises(R.ReshardError, match="minimum budget"):
        R.reshard(x, partition=Partition.BROADCAST, budget=budget,
                  spill="off")
    oracle = R.reshard(x, partition=Partition.BROADCAST,
                       budget=None, spill="off")
    spilled = R.reshard(x, partition=Partition.BROADCAST, budget=budget)
    assert isinstance(spilled, S.HostArray)   # dst over budget → host
    np.testing.assert_array_equal(spilled.value,
                                  np.asarray(oracle.asarray()))
    np.testing.assert_array_equal(spilled.value, M)


# ------------------------------------------------------ host arrays
def test_host_array_metadata_and_validation(ndev):
    mesh = make_mesh(ndev)
    v = np.arange(45.0)
    h = S.HostArray(v, mesh)
    assert h.global_shape == (45,) and h.n_shards == ndev
    assert h._axis_sizes == _sizes(45, ndev)
    assert np.array_equal(np.asarray(h), v)
    with pytest.raises(ValueError, match="local shapes"):
        S.HostArray(v, mesh, local_shapes=[(45,)])
    with pytest.raises(ValueError, match="sum"):
        S.HostArray(v, mesh, local_shapes=[(45,)] * ndev)
    with pytest.raises(IndexError, match="axis"):
        S.HostArray(v, mesh, axis=3)
    with pytest.raises(ValueError, match="mask"):
        S.HostArray(v, mesh, mask=[0, 1])


def test_to_host_round_trip(ndev, rng):
    v = rng.standard_normal(45)
    x = DistributedArray.to_dist(v, mesh=make_mesh(ndev))
    h = x.to_host(budget=8 * F64)
    assert isinstance(h, S.HostArray)
    assert h.local_shapes == x.local_shapes and h.axis == x.axis
    np.testing.assert_array_equal(h.value, v)
    back = h.to_device(budget=8 * F64)
    assert isinstance(back, DistributedArray)
    assert back.local_shapes == x.local_shapes
    assert np.array_equal(np.asarray(back._arr), np.asarray(x._arr))


def test_to_host_refuses_traced(ndev, rng):
    import jax
    x = DistributedArray.to_dist(rng.standard_normal(16),
                                 mesh=make_mesh(ndev))

    def f(d):
        return S.to_host(d)

    with pytest.raises(Exception, match="trace"):
        from pylops_mpi_tpu.distributedarray import DistributedArray as DA
        jax.jit(lambda a: S.to_host(
            DA._wrap(a, x)).value)(x._arr)


def test_host_array_as_reshard_source(ndev, rng):
    """reshard() accepts a HostArray operand: host→device streams
    under the budget, host→host relayout aliases the value."""
    v = rng.standard_normal(45)
    mesh = make_mesh(ndev)
    h = S.HostArray(v, mesh)
    out = R.reshard(h, mesh=mesh, partition=Partition.SCATTER, axis=0,
                    budget=8 * F64)
    assert isinstance(out, DistributedArray)
    np.testing.assert_array_equal(np.asarray(out.asarray()), v)
    # host→host: metadata-only, same buffer
    h2 = R.reshard(h, partition=Partition.BROADCAST, budget=2 * F64,
                   spill="on", host_dst=True)
    assert isinstance(h2, S.HostArray)
    assert h2.value is h.value
    assert h2.partition == Partition.BROADCAST
    # mask rules mirror reshard: changed world refuses
    if ndev >= 8:
        hm = S.HostArray(v, make_mesh(8), mask=[0, 0, 1, 1, 0, 0, 1, 1])
        with pytest.raises(R.ReshardError, match="mask"):
            R.reshard(hm, mesh=make_mesh(4))


# ------------------------------------------------- overlap execution
@pytest.mark.parametrize("overlap", ["on", "off"])
def test_overlap_modes_bit_identical(overlap, ndev, rng):
    """Double-buffered and serialized execution produce the same
    bits — overlap is a latency lever, never a semantics lever."""
    v = rng.standard_normal((45, 3))
    x = DistributedArray.to_dist(v, mesh=make_mesh(ndev))
    h = S.to_host(x, budget=8 * 3 * F64, overlap=overlap)
    np.testing.assert_array_equal(h.value, v)
    back = R.reshard(h, budget=8 * 3 * F64, overlap=overlap)
    assert np.array_equal(np.asarray(back._arr), np.asarray(x._arr))


def test_overlap_kwarg_validation(ndev, rng):
    x = DistributedArray.to_dist(rng.standard_normal(16),
                                 mesh=make_mesh(ndev))
    with pytest.raises(ValueError, match="overlap"):
        S.to_host(x, overlap="sideways")


# --------------------------------------------------------- accounting
def test_spill_trace_and_metrics_accounting(ndev, monkeypatch):
    """host_stage step events carry the h2d/d2h bytes; the metrics
    registry lands them in bytes_h2d/bytes_d2h next to the ici/dcn
    split and NEVER in the legacy .bytes counter; totals cross-check
    against the plan."""
    if ndev < 8:
        pytest.skip("needs 8 devices")
    monkeypatch.setenv("PYLOPS_MPI_TPU_TRACE", "full")
    monkeypatch.setenv("PYLOPS_MPI_TPU_METRICS", "on")
    trace.clear_events()
    metrics.clear_metrics()
    v = np.arange(45.0)
    x = DistributedArray.to_dist(v, mesh=make_mesh(8))
    budget = 8 * F64
    out = R.reshard(x, mesh=make_mesh(4), budget=budget, spill="on",
                    host_dst=False)
    np.testing.assert_array_equal(np.asarray(out.asarray()), v)
    plan = R.plan_reshard((45,), F64, R.Layout.scatter(_sizes(45, 8)),
                          R.Layout.scatter(_sizes(45, 4)), budget=budget,
                          spill="on", dst_host=False)
    evs = [e.get("args", {}) for e in trace.get_events()
           if e.get("name") == "collective.reshard.step"]
    assert evs and all(a.get("kind") == "host_stage" for a in evs)
    assert sum(a.get("nbytes_d2h", 0) for a in evs) == plan.nbytes_d2h
    assert sum(a.get("nbytes_h2d", 0) for a in evs) == plan.nbytes_h2d
    spans = [e.get("args", {}) for e in trace.get_events()
             if e.get("name") == "collective.reshard"]
    assert any(a.get("spilled") for a in spans)
    snap = metrics.snapshot()["counters"]
    assert snap.get("collective.reshard.bytes_h2d") == plan.nbytes_h2d
    assert snap.get("collective.reshard.bytes_d2h") == plan.nbytes_d2h
    assert "collective.reshard.bytes" not in snap
    trace.clear_events()
    metrics.clear_metrics()


def test_hybrid_refusal_names_topology(monkeypatch, ndev):
    """Satellite bugfix: a planner refusal raised for a move on a
    hybrid mesh names the fabric layout (topology_key) so multi-slice
    failures are attributable from the message alone."""
    if ndev < 8:
        pytest.skip("needs 8 devices")
    monkeypatch.setenv("PYLOPS_MPI_TPU_FABRIC", "2x4")
    mesh = make_mesh_hybrid(dcn_size=2)
    assert topology.topology_key(mesh) == "dcn2xici4"
    x = DistributedArray.to_dist(np.arange(45.0), mesh=mesh)
    with pytest.raises(R.ReshardError, match="dcn2xici4"):
        R.reshard(x, partition=Partition.BROADCAST, budget=F64 - 1)
    with pytest.raises(R.ReshardError, match="dcn2xici4"):
        R.reshard(x, partition=Partition.BROADCAST, budget=2 * F64 - 1,
                  spill="off")


def test_off_mode_bit_identical_plan(ndev):
    """SPILL=off and an unset SPILL produce identical plans on every
    succeeding path (the HLO pin: nothing about a working move
    changes when the tier ships)."""
    src = R.Layout.scatter(_sizes(45, 8))
    dst = R.Layout.scatter(_sizes(45, 2))
    for budget in (None, 4 * F64, 90 * F64):
        assert (R.plan_reshard((45,), F64, src, dst, budget=budget)
                == R.plan_reshard((45,), F64, src, dst, budget=budget,
                                  spill="off"))


# ---------------------------------------------- elastic restore path
def test_elastic_restore_spills_over_budget_carry(ndev, monkeypatch, rng):
    """The motivating consumer: an elastic shrink whose banked carry
    does not fit the device budget restores via host staging — trace
    shows host_stage steps — and the restored values are exact."""
    if ndev < 8:
        pytest.skip("needs 8 devices")
    from pylops_mpi_tpu.resilience import elastic as E
    monkeypatch.setenv("PYLOPS_MPI_TPU_TRACE", "full")
    # a banked carry is a HOST source (one live buffer), so its device
    # floor already equals the spill floor — host staging must be
    # forced, auto has nothing to rescue
    monkeypatch.setenv("PYLOPS_MPI_TPU_SPILL", "on")
    v = rng.standard_normal(48)
    x = DistributedArray.to_dist(v, mesh=make_mesh(8))
    E.bank_carry("spill_t", {"x": x})
    # below one row even the host path refuses
    with pytest.raises(R.ReshardError, match="minimum budget"):
        E.restore_carry("spill_t", make_mesh(4), budget=F64 - 1)
    trace.clear_events()
    state = E.restore_carry("spill_t", make_mesh(4), budget=F64)
    np.testing.assert_array_equal(np.asarray(state["x"].asarray()), v)
    assert state["x"].n_shards == 4
    kinds = [e.get("args", {}).get("kind") for e in trace.get_events()
             if e.get("name") == "collective.reshard.step"]
    assert kinds and all(k == "host_stage" for k in kinds)
    trace.clear_events()


def test_checkpoint_elastic_restore_budgeted(tmp_path, ndev, monkeypatch,
                                             rng):
    """A checkpoint elastic restore under a set budget routes through
    the bounded planner (spilling when the budget demands it); unset
    keeps the legacy one-shot path."""
    if ndev < 8:
        pytest.skip("needs 8 devices")
    from pylops_mpi_tpu.utils import checkpoint as C
    v = rng.standard_normal(48)
    x = DistributedArray.to_dist(v, mesh=make_mesh(8))
    path = str(tmp_path / "ck")
    C.save_pytree(path, {"x": x})
    # legacy path: no budget env
    out = C.load_pytree(path, mesh=make_mesh(4))
    np.testing.assert_array_equal(np.asarray(out["x"].asarray()), v)
    # budgeted path: the restore routes through place_replica, and
    # SPILL=on forces its placement host-staged end to end
    monkeypatch.setenv("PYLOPS_MPI_TPU_RESHARD_BUDGET", str(F64))
    monkeypatch.setenv("PYLOPS_MPI_TPU_SPILL", "on")
    monkeypatch.setenv("PYLOPS_MPI_TPU_TRACE", "full")
    trace.clear_events()
    out = C.load_pytree(path, mesh=make_mesh(4))
    np.testing.assert_array_equal(np.asarray(out["x"].asarray()), v)
    assert out["x"].n_shards == 4
    kinds = [e.get("args", {}).get("kind") for e in trace.get_events()
             if e.get("name") == "collective.reshard.step"]
    assert "host_stage" in kinds
    trace.clear_events()


# ------------------------------------------------------- chaos seam
def test_kill_spill_seam_counts_without_env(ndev, rng):
    """The seam is a counter bump when the env is unset, and it fires
    once per staged chunk."""
    from pylops_mpi_tpu.resilience import faults
    faults.reset_spill_steps()
    v = rng.standard_normal(45)
    x = DistributedArray.to_dist(v, mesh=make_mesh(ndev))
    h = S.to_host(x, chunks=5)
    assert faults.spill_steps() >= 5
    np.testing.assert_array_equal(h.value, v)
    faults.reset_spill_steps()
