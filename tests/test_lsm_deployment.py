"""Kirchhoff least-squares migration as a deployment (PR 38): the system
(``pmt.models.MPILSM`` with defaults -> ``pmt.cgls``) against the
benchmark builder's plain reference — forward, adjoint and the
answer after the configuration's iterations on 1, 2 and 8 virtual devices; the operator against
a dense two-tap oracle; the shots' shares adding up to the whole line;
the tables as jit arguments, made on the device; the scopes, events and
counters the device trace and the log read; the bfloat16 control, a
misplaced shot and a solve that stops early each refused by the loop's
comparison; the plain reference held to the equations in NumPy and to
reading nothing of the program. Small, seeded, on the CPU."""

import importlib
import json
import os
import re
import sys

import numpy as np
import pytest
import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import pylops_mpi_tpu as pmt
from pylops_mpi_tpu import DistributedArray, Partition
from pylops_mpi_tpu.diagnostics import metrics, trace
from pylops_mpi_tpu.linearoperator import operator_is_jit_arg
from pylops_mpi_tpu.models import (KirchhoffDemigration, MPILSM,
                                   TravelTimeSpray)
from pylops_mpi_tpu.ops import pallas_kernels as pk
from pylops_mpi_tpu.solvers import basic
from pylops_mpi_tpu.utils import hlo
from chipbench.builders import lsm as B
from chipbench.loops import closed_vstack

M = importlib.import_module("pylops_mpi_tpu.models.lsm")

with open(os.path.join(ROOT, "chipbench", "configs",
                       "lsm_kirchhoff.json")) as _f:
    CFG = json.load(_f)
# the configuration's rehearsal sizes; WIDE: a shot a device of the
# widest test mesh, the shots moved together so that all eight stand
# over the small image (shots beyond it make near-copies of one
# another's equations, and ten iterations of float32 CGLS on such a toy
# then differ by 1e-4 to 1e-2 between any two orders of summation)
SIZES = dict(CFG["sizes"], **CFG["rehearse"])
WIDE = dict(SIZES, ns=8, dshot=48.0)
# the depth the traffic runs, and the depth up to which float32
# determines the answer (the loop's ``rel_tol`` is read there)
NITER = int(CFG["guarantees"]["niter"])
HOLD = int(CFG["guarantees"]["hold_niter"])
LIMITS = {k: float(CFG["guarantees"][k])
          for k in ("rel_tol", "resid_ratio", "repeat_tol")}


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.linalg.norm(a - b) / np.linalg.norm(b)


def _op(sizes, mesh=None, shots=None, dtype=np.float32):
    """Upstream's arguments and nothing else, as the cell's builder;
    ``shots``: a share of the line's sources."""
    g = B.geometry(sizes)
    src = g.sources if shots is None else g.sources[:, shots]
    return MPILSM(g.z, g.x, g.t, src, g.recs, g.vel, g.wav, g.wavc,
                  mesh=mesh, dtype=dtype)


def _vec(a, mesh, part):
    out = DistributedArray(global_shape=a.size, mesh=mesh, partition=part,
                           dtype=np.float32)
    out[:] = jnp.asarray(a, jnp.float32).ravel()
    return out


def _case(sizes):
    """The plain reference's side, from the survey's geometry alone
    (its own travel times: nothing of the program is read)."""
    times = B.point_times(sizes)
    m = B.make_reflectivity(sizes)(jax.random.key(1))
    mv, rmv = B.plain_system(sizes)
    with jax.default_matmul_precision("highest"):
        d = mv(times, m)
        u = jax.random.normal(jax.random.key(2), d.shape, jnp.float32)
        deep = B.plain_solve(sizes, NITER)
        return dict(sizes=sizes, times=times, m=m, d=d, u=u,
                    Au=rmv(times, u), deep=deep,
                    xref=B.plain_solve(sizes, HOLD).solve(times, d),
                    drop=float(deep(times, d)[1]))


@pytest.fixture(scope="module")
def case():
    return _case(WIDE)


@pytest.fixture(scope="module")
def small():
    return _case(SIZES)


# ------------------------------------------- program against reference
@pytest.mark.parametrize("ndev", [1, 2, 8])
@pytest.mark.parametrize("what", ["forward", "adjoint", "answer"])
def test_system_matches_the_plain_reference(case, small, ndev, what):
    if what == "answer" and ndev <= SIZES["ns"]:
        case = small        # the sizes the cell's rehearsal runs
    mesh = pmt.make_mesh(ndev)
    Op = _op(case["sizes"], mesh)
    if what == "forward":
        got = Op.matvec(_vec(case["m"], mesh, Partition.BROADCAST))
        assert got.partition == Partition.SCATTER
        assert _rel(got.asarray(), case["d"]) < 5e-6
    elif what == "adjoint":
        got = Op.rmatvec(_vec(case["u"], mesh, Partition.SCATTER))
        assert got.partition == Partition.BROADCAST
        assert _rel(got.asarray(), case["Au"]) < 5e-6
    else:
        # the loop's readings: the answer after HOLD iterations against
        # the reference's, the full-depth answer's residual over the
        # reference's own, the same call again
        y = _vec(case["d"], mesh, Partition.SCATTER)
        x0 = _vec(np.zeros(Op.shape[1]), mesh, Partition.BROADCAST)
        held = pmt.cgls(Op, y, x0=x0, niter=HOLD, tol=0.0)[0]
        x = pmt.cgls(Op, y, x0=x0, niter=NITER, tol=0.0)[0].asarray()
        again = pmt.cgls(Op, y, x0=x0, niter=NITER, tol=0.0)[0].asarray()
        drop = float(case["deep"].drop(case["times"], case["d"],
                                       jnp.asarray(x)))
        readings = {"rel_tol": _rel(held.asarray(), case["xref"]),
                    "resid_ratio": drop / case["drop"],
                    "repeat_tol": _rel(again, x)}
        assert closed_vstack.judge(readings, LIMITS) == [], readings


@pytest.mark.parametrize("which", ["small", "case"])
def test_the_bfloat16_control_is_refused_by_the_loops_judge(request, which):
    """The same plain solve with every sprayed and gathered product
    rounded to bfloat16, through the loop's own comparison."""
    case = request.getfixturevalue(which)
    wrong = B.plain_solve(case["sizes"], HOLD, **B.CONTROLS["bf16"]).solve(
        case["times"], case["d"])
    reading = _rel(wrong, case["xref"])
    assert closed_vstack.judge({"rel_tol": reading}, LIMITS) \
        == ["rel_tol"], reading
    assert reading > 3 * LIMITS["rel_tol"]


def test_a_solve_that_stops_early_is_refused_by_its_residual(small):
    """The full-depth limit: an answer after HOLD iterations offered as
    the answer after NITER does not fit the data as the reference's
    does (``resid_ratio``)."""
    mesh = pmt.make_mesh(1)
    Op = _op(small["sizes"], mesh)
    y = _vec(small["d"], mesh, Partition.SCATTER)
    x0 = _vec(np.zeros(Op.shape[1]), mesh, Partition.BROADCAST)
    early = pmt.cgls(Op, y, x0=x0, niter=HOLD, tol=0.0)[0].array
    ratio = float(small["deep"].drop(small["times"], small["d"], early)) \
        / small["drop"]
    assert closed_vstack.judge({"resid_ratio": ratio}, LIMITS) \
        == ["resid_ratio"], ratio


@pytest.mark.parametrize("cast", [None, "bfloat16"],
                         ids=["float32", "control"])
@pytest.mark.parametrize("nt", [256, 60], ids=["whole", "taps-fall-off"])
def test_the_banded_reference_is_the_plain_one(case, cast, nt):
    """The form the cell's reference solves run on the chip
    (``banded_spray``: compares over each run's short band) against the
    equations as they stand (``plain_spray``: scatter-add and
    indexing), entry for entry."""
    sizes = dict(WIDE, nt=nt)
    times = case["times"]
    width = B.band_width(sizes, times)
    assert width % 8 == 0 and 8 <= width <= 64
    mv, rmv = B.plain_spray(sizes, cast)
    bmv, brmv = B.banded_spray(sizes, width, cast)
    m = B.to_blocks(case["m"].reshape(WIDE["nz"], WIDE["nx"]), sizes)
    pairs = WIDE["ns"] * WIDE["nr"]
    z = jax.random.normal(jax.random.key(5), (pairs, nt), jnp.float32)
    if nt == 60:            # taps DO fall off the short trace
        i, _ = B.pair_tables(times, 0, nt)
        assert int((i < 0).sum()) > 0
    tol = 1e-6 if cast is None else 2e-4   # rounded products, other order
    assert _rel(bmv(times, m), mv(times, m)) < tol
    assert _rel(brmv(times, z), rmv(times, z)) < tol


def test_the_reference_is_the_equations_in_float64(rng):
    """The builder's plain system — its own travel times, ``floor`` and
    fraction a block of pairs, the wavelet as shifted sums — against the
    equations written as loops in NumPy; both forms of the indexed
    part; a pixel count that is no whole run (padding dropped)."""
    sizes = dict(SIZES, nz=20, nx=40, ns=2, nr=3, nt=128)
    g = B.geometry(sizes)
    zz, xx = np.meshgrid(g.z, g.x, indexing="ij")
    pix = np.stack([xx.ravel(), zz.ravel()], 1)

    def times(points):
        return np.sqrt(((points.T[:, None, :] - pix[None]) ** 2).sum(-1)) \
            / g.vel
    T = (times(g.sources)[:, None] + times(g.recs)[None]).reshape(
        6, -1) / sizes["dt"]
    i = np.floor(T).astype(int)
    D = _dense_two_tap(i, T - i, 128).reshape(6, 128, -1)
    D = np.einsum("ab,pbx->pax", _wavelet_matrix(g, 128), D).reshape(
        6 * 128, -1)
    m, d = rng.standard_normal(D.shape[1]), rng.standard_normal(D.shape[0])
    t64 = B.point_times(sizes, np.float64)
    for width in (None, B.band_width(sizes, t64)):
        mv, rmv = B.plain_system(sizes, width=width)
        np.testing.assert_allclose(np.asarray(mv(t64, jnp.asarray(m))),
                                   D @ m, rtol=1e-10, atol=1e-10)
        np.testing.assert_allclose(np.asarray(rmv(t64, jnp.asarray(d))),
                                   D.T @ d, rtol=1e-10, atol=1e-10)


def test_the_reference_reads_nothing_of_the_program():
    """The plain reference makes every answer, and the data, from the
    survey's geometry: no stored table, layout or attribute of the
    program's operator appears in the builder."""
    with open(B.__file__) as f:
        text = f.read()
    for word in ("itrav", ".weight", ".ops[", "_it", "_wt", "lohi",
                 "pylops_mpi_tpu.models", "pylops_mpi_tpu.ops"):
        assert word not in text.replace(
            "``pylops_mpi_tpu.ops``, ``.solvers`` or ``.models``", ""), word


def test_a_misplaced_shot_is_not_correct(small):
    """What sharing the program's tables would let through: a program
    whose second shot stands 8 m (two pixels) from where the survey
    puts it is refused by the loop's comparison against the reference's
    own geometry."""
    sizes, mesh = small["sizes"], pmt.make_mesh(1)
    g = B.geometry(sizes)
    src = g.sources.copy()
    src[0, 1] += 8.0
    Op = MPILSM(g.z, g.x, g.t, src, g.recs, g.vel, g.wav, g.wavc, mesh=mesh)
    y = _vec(small["d"], mesh, Partition.SCATTER)
    x0 = _vec(np.zeros(Op.shape[1]), mesh, Partition.BROADCAST)
    x = pmt.cgls(Op, y, x0=x0, niter=HOLD, tol=0.0)[0]
    reading = _rel(x.asarray(), small["xref"])
    assert closed_vstack.judge({"rel_tol": reading}, LIMITS) \
        == ["rel_tol"], reading


def test_dot_test_in_float64(rng):
    g = B.geometry(SIZES)
    K = KirchhoffDemigration(*g.args, dtype=np.float64)
    u = rng.standard_normal(K.shape[1])
    v = rng.standard_normal(K.shape[0])
    lhs = np.asarray(K.matvec(jnp.asarray(u))) @ v
    rhs = u @ np.asarray(K.rmatvec(jnp.asarray(v)))
    np.testing.assert_allclose(lhs, rhs, rtol=1e-11)


# ------------------------------------------------ the operator's taps
def _dense_two_tap(i, tau, nt):
    """``D[p, t, x]``: the equations, written as loops."""
    npairs, npix = i.shape
    D = np.zeros((npairs, nt, npix))
    for p in range(npairs):
        for x in range(npix):
            if 0 <= i[p, x] < nt - 1:
                D[p, i[p, x], x] += 1 - tau[p, x]
                D[p, i[p, x] + 1, x] += tau[p, x]
    return D.reshape(npairs * nt, npix)


@pytest.mark.parametrize("npix", [7, 1024, 2500])
def test_two_taps_against_a_dense_scatter_oracle(rng, npix):
    """Entries on both sides of ``0 <= i < nt - 1`` (below zero, the
    last sample, beyond the trace), tables in no order at all, a pixel
    count that is no whole tile."""
    npairs, nt = 3, 12
    i = rng.integers(-3, nt + 3, size=(npairs, npix))
    tau = rng.uniform(0, 1, size=(npairs, npix))
    op = TravelTimeSpray(i, None, nt, dtype=np.float64, frac=tau)
    D = _dense_two_tap(i, tau, nt)
    m, z = rng.standard_normal(npix), rng.standard_normal(npairs * nt)
    np.testing.assert_allclose(np.asarray(op.matvec(jnp.asarray(m))), D @ m,
                               rtol=1e-12, atol=1e-12)
    np.testing.assert_allclose(np.asarray(op.rmatvec(jnp.asarray(z))),
                               D.T @ z, rtol=1e-12, atol=1e-12)
    assert op.dropped == int(((i < 0) | (i >= nt - 1)).sum())


def _wavelet_matrix(g, nt):
    """``C[a, b]``: ``d[a] = sum_j w[j] y[a + c - j]``, zeros beyond."""
    C = np.zeros((nt, nt))
    for a in range(nt):
        for j in range(len(g.wav)):
            if 0 <= a + g.wavc - j < nt:
                C[a, a + g.wavc - j] = g.wav[j]
    return C


def test_the_demigration_against_the_equations_in_float64(rng):
    """``KirchhoffDemigration`` against travel times, floor, fraction
    and the wavelet written out in NumPy."""
    sizes = dict(SIZES, nz=20, nx=40, ns=2, nr=3, nt=128)
    g = B.geometry(sizes)
    K = KirchhoffDemigration(*g.args, dtype=np.float64)
    zz, xx = np.meshgrid(g.z, g.x, indexing="ij")
    pix = np.stack([xx.ravel(), zz.ravel()], 1)

    def times(points):
        return np.sqrt(((points.T[:, None, :] - pix[None]) ** 2).sum(-1)) \
            / g.vel
    T = (times(g.sources)[:, None] + times(g.recs)[None]).reshape(
        6, -1) / sizes["dt"]
    i = np.floor(T).astype(int)
    D = _dense_two_tap(i, T - i, 128).reshape(6, 128, -1)
    D = np.einsum("ab,pbx->pax", _wavelet_matrix(g, 128), D).reshape(
        6 * 128, -1)
    m, d = rng.standard_normal(D.shape[1]), rng.standard_normal(D.shape[0])
    np.testing.assert_allclose(np.asarray(K.matvec(jnp.asarray(m))), D @ m,
                               rtol=1e-10, atol=1e-10)
    np.testing.assert_allclose(np.asarray(K.rmatvec(jnp.asarray(d))),
                               D.T @ d, rtol=1e-10, atol=1e-10)


@pytest.mark.parametrize("adjoint", [0, 1])
def test_the_scatter_form_is_the_same_operator(rng, monkeypatch, adjoint):
    """Where the kernels are refused (here: by answering for
    ``kirchhoff_legal``) the trace-by-trace form gives the same."""
    npairs, npix, nt = 4, 1500, 40
    i = rng.integers(-2, nt + 2, size=(npairs, npix))
    tau = rng.uniform(0, 1, size=(npairs, npix)).astype(np.float32)
    op = TravelTimeSpray(i, None, nt, frac=tau)
    v = jnp.asarray(rng.standard_normal(op.shape[adjoint ^ 1]), jnp.float32)
    apply = op.rmatvec if adjoint else op.matvec
    want = apply(v)
    monkeypatch.setenv("PYLOPS_MPI_TPU_TRACE", "spans")
    monkeypatch.setattr(pk, "kirchhoff_legal", lambda *_: False)
    trace.clear_events()
    assert _rel(apply(v), want) < 1e-6
    ev = [e["args"] for e in trace.get_events()
          if e["name"] == "kirchhoff.path_select"]
    assert [(a["form"], a["why"], a["adjoint"]) for a in ev] \
        == [("scatter", "nt", adjoint)]


# --------------------------- the adjoint's lane gather (PR 39)
def _banded(rng, taps, nt, ntiles, long=None, pairs=2):
    """Indices ``(pairs, ntiles * 1024)`` whose every tile spans a band
    of at most 40 samples at a random place, tile 0's reaching the
    trace's last tap (``nt - taps``), 5 % of the entries dropped (-7),
    tile 1 all dropped (an empty tile) and tile ``long``'s band samples
    40 to 139: 100 wide, and in no window; and weights in ``[0, 1)``."""
    i = np.empty((pairs, ntiles * 1024), np.int64)
    ends = []
    for t in range(ntiles):
        width = 100 if t == long else int(rng.integers(1, 41))
        lo = 40 if t == long else nt - taps + 1 - width if t == 0 else int(
            rng.integers(0, nt - taps + 2 - width))
        i[:, t * 1024:(t + 1) * 1024] = lo + rng.integers(
            0, width, (pairs, 1024))
        ends.append((t * 1024, lo, lo + width - 1))
    i[rng.random(i.shape) < 0.05] = -7
    for x, lo, hi in ends:                      # the bands as drawn
        i[:, x], i[:, x + 1] = lo, hi
    i[:, 1024:2048] = -7
    return i, rng.uniform(0, 1, i.shape).astype(np.float32)


def _spray(i, w, nt, taps, dtype=np.float32):
    return (TravelTimeSpray(i, None, nt, dtype=dtype, frac=w) if taps == 2
            else TravelTimeSpray(i, w, nt, dtype=dtype))


def _windowed_tiles(i, nt, taps):
    """Non-empty pair-tiles, and those whose band fits a window."""
    t = i.reshape(i.shape[0], -1, 1024)
    ok = (t >= 0) & (t <= nt - taps)
    lo = np.where(ok, t, 1 << 30).min(-1)
    hi = np.where(ok, t, -1).max(-1)
    fits = (lo <= hi) & (hi + taps - 1 - lo // 64 * 64 <= 127)
    return int((lo <= hi).sum()), int(fits.sum())


@pytest.mark.parametrize("taps", [1, 2])
@pytest.mark.parametrize("ntiles, long", [(6, None), (6, 3), (70, 66)],
                         ids=["windowed", "one-long-band", "two-blocks"])
def test_the_lane_gather_is_the_band_loop_bit_for_bit(rng, monkeypatch,
                                                       taps, ntiles, long):
    """``pmt_kirchhoff_adj`` (interpreted) against a copy of the band
    loop it replaces (``chip_probe/kirchhoff_gather_probe.py``), bit for
    bit: one and two taps, float32, an index on the trace's last tap,
    dropped entries, an empty tile; one tile of a 100-sample band,
    which the band loop takes (``two-blocks``: the tables' second grid
    step, its first every tile gathered); the counter and the adjoint's
    event count only the tiles that fit a window."""
    from chip_probe.kirchhoff_gather_probe import band_loop_gather
    monkeypatch.setenv("PYLOPS_MPI_TPU_TRACE", "spans")
    monkeypatch.setenv("PYLOPS_MPI_TPU_METRICS", "on")
    metrics.clear_metrics()
    trace.clear_events()
    nt = 300
    i, w = _banded(rng, taps, nt, ntiles, long)
    op = _spray(i, w, nt, taps)
    nonempty, fits = _windowed_tiles(i, nt, taps)
    assert (op.tiles, op.windowed) == (nonempty, fits)
    assert fits == nonempty - (0 if long is None else 2)
    assert metrics.snapshot()["counters"][
        "kirchhoff.gather_tiles_windowed"] == fits
    z = jnp.asarray(rng.standard_normal((2, nt)), jnp.float32)
    got = np.asarray(op.rmatvec(z.ravel()))
    want = np.asarray(band_loop_gather(op._lohi, op.itrav, op.weight, z,
                                       taps))[:i.shape[1]]
    assert np.array_equal(got, want), np.abs(got - want).max()
    ev = [e["args"] for e in trace.get_events()
          if e["name"] == "kirchhoff.path_select"]
    assert [a["windowed"] for a in ev] == [fits / nonempty]


@pytest.mark.parametrize("taps", [1, 2])
def test_the_gather_is_the_spray_s_adjoint_across_both_ways(rng, taps):
    """The dot test in float64 on tables that send some tiles through
    the lane gather and one through the band loop."""
    nt = 300
    i, w = _banded(rng, taps, nt, 6, long=3)
    op = _spray(i, w.astype(np.float64), nt, taps, dtype=np.float64)
    assert 0 < op.windowed < op.tiles
    m = rng.standard_normal(op.shape[1])
    z = rng.standard_normal(op.shape[0])
    lhs = np.asarray(op.matvec(jnp.asarray(m))) @ z
    rhs = m @ np.asarray(op.rmatvec(jnp.asarray(z)))
    np.testing.assert_allclose(lhs, rhs, rtol=1e-12)


# ------------------------------------- the spray's pair groups
def _steps(lo, hi, taps):
    return np.maximum(hi + taps - 1 - lo + pk._KIR_UNROLL, 0) \
        // pk._KIR_UNROLL


def _shifted(rng, taps, nt, pairs):
    """``_banded``'s tables with each pair's live entries moved by ``p
    % 3`` samples (neighbouring traces: the bands of a group overlap
    and differ), entries moved past the trace's last tap dropped, and
    pair 1's tile 4 all dropped: a group with an empty row beside live
    ones; tile 1 stays empty in every pair, tile 3 holds a band of 100
    samples, in no window."""
    i, w = _banded(rng, taps, nt, 6, long=3, pairs=pairs)
    shift = (np.arange(pairs) % 3)[:, None]
    i = np.where(i >= 0, i + shift, i)
    i[i > nt - taps] = -7
    i[1, 4 * 1024:5 * 1024] = -7
    return i, w


@pytest.mark.parametrize("taps", [1, 2])
@pytest.mark.parametrize("pairs, group", [(8, 8), (12, 4), (6, 2), (3, 1)])
def test_the_pair_groups_are_the_one_pair_spray_bit_for_bit(
        rng, monkeypatch, taps, pairs, group):
    """``pmt_kirchhoff`` (interpreted) at the rule's ``G`` traces a grid
    step against a copy of the one-trace kernel it replaces
    (``chip_probe/kirchhoff_spray_probe.py``), bit for bit: one and two
    taps, pair counts that 8, only 4, only 2 and nothing divide, a group
    with an empty row, dropped entries, a band in no window; the
    forward's event and the counter read the rule's ``G`` and the
    tables' ``walk``."""
    from chip_probe.kirchhoff_spray_probe import one_pair_spray
    monkeypatch.setenv("PYLOPS_MPI_TPU_TRACE", "spans")
    monkeypatch.setenv("PYLOPS_MPI_TPU_METRICS", "on")
    metrics.clear_metrics()
    trace.clear_events()
    nt = 300
    i, w = _shifted(rng, taps, nt, pairs)
    op = _spray(i, w, nt, taps)
    assert op.group == pk.kirchhoff_group(pairs, nt, np.float32) == group
    assert metrics.snapshot()["counters"]["kirchhoff.spray_group"] == group
    m = jnp.asarray(rng.standard_normal(op.shape[1]), jnp.float32)
    got = np.asarray(op.matvec(m)).reshape(pairs, nt)
    want = np.asarray(one_pair_spray(
        op._lohi, op.itrav, op.weight,
        jnp.pad(m, (0, op.itrav.shape[1] * 1024 - m.size)), nt, taps))
    assert np.array_equal(got, want), np.abs(got - want).max()
    # the walk, from the tables as drawn
    t = i.reshape(pairs, -1, 1024)
    ok = t >= 0
    lo = np.where(ok, t, 1 << 30).min(-1)
    hi = np.where(ok, t, -1).max(-1)
    union = _steps(lo.reshape(-1, group, lo.shape[1]).min(1),
                   hi.reshape(-1, group, hi.shape[1]).max(1), taps)
    own = _steps(lo, hi, taps)
    assert (op.steps_walked, op.steps_banded) == (group * union.sum(),
                                                  own.sum())
    assert (op.steps_walked > op.steps_banded) == (group > 1)
    ev = [e["args"] for e in trace.get_events()
          if e["name"] == "kirchhoff.path_select"]
    assert [(a["adjoint"], a["group"], a["walk"]) for a in ev] == [
        (0, group, group * union.sum() / own.sum())]
    assert "windowed" not in ev[0]


def test_the_spray_group_follows_pairs_nt_and_dtype():
    """The rule: the largest of 8, 4, 2 that divides the pairs and whose
    accumulators fit the spray's VMEM share beside the reduction's
    temporaries, else 1; ``kirchhoff_legal`` as before."""
    g = pk.kirchhoff_group
    f32 = np.float32
    assert g(2048, 1024, f32) == 4                  # the cell: 8 needs 53 MiB
    assert g(2048, 512, f32) == 8
    assert (g(2052, 1024, f32), g(2050, 1024, f32), g(2049, 1024, f32)) \
        == (4, 2, 1)
    assert (g(2048, 2048, f32), g(2048, 2600, f32)) == (2, 1)
    assert (g(2048, 1024, np.float64), g(2048, 1024, jnp.bfloat16)) \
        == (2, 8)
    # a trace that fits alone takes the kernel, one trace a step
    assert pk.kirchhoff_legal(6012, f32) and g(2048, 6012, f32) == 1
    assert not pk.kirchhoff_legal(6013, f32)
    assert pk.kirchhoff_legal(1024, np.float64)
    assert not pk.kirchhoff_legal(1024, np.complex64)


# ------------------------------------------------- the shares add up
def test_the_four_shares_add_up_to_the_whole_line(case):
    """The deployment deals the line's shots over four chips: the
    shares' forwards one after another are the whole operator's, and
    their adjoint images sum to its adjoint."""
    mesh = pmt.make_mesh(1)
    m = _vec(case["m"], mesh, Partition.BROADCAST)
    nt, nr = WIDE["nt"], WIDE["nr"]
    fwd, img = [], 0.0
    for share in np.split(np.arange(WIDE["ns"]), 4):
        Op = _op(WIDE, mesh, shots=share)
        fwd.append(np.asarray(Op.matvec(m).asarray()))
        rows = slice(share[0] * nr * nt, (share[-1] + 1) * nr * nt)
        img = img + np.asarray(Op.rmatvec(_vec(
            np.asarray(case["u"])[rows], mesh, Partition.SCATTER)).asarray())
    assert _rel(np.concatenate(fwd), case["d"]) < 5e-6
    assert _rel(img, case["Au"]) < 5e-6


def test_fewer_sources_than_devices_is_refused():
    g = B.geometry(SIZES)                       # two shots
    with pytest.raises(ValueError, match="2 source.* 8 devices"):
        MPILSM(*g.args, mesh=pmt.make_mesh(8))


# ------------------------------------- tables: arguments, device-made
@pytest.fixture(scope="module")
def fused_hlo():
    mesh = pmt.make_mesh(1)
    Op = _op(SIZES, mesh)
    text = hlo.compiled_hlo(
        lambda op, y, x0: basic._cgls_fused(op, y, x0, 0.0, 0.0, niter=3),
        Op, _vec(np.ones(Op.shape[0]), mesh, Partition.SCATTER),
        _vec(np.zeros(Op.shape[1]), mesh, Partition.BROADCAST))
    return Op, text


def test_the_operator_is_a_jit_argument(fused_hlo):
    Op, _ = fused_hlo
    assert operator_is_jit_arg(Op)
    leaves = jax.tree_util.tree_leaves(Op)
    spray = Op.ops[0].A.B
    assert sum(int(a.nbytes) for a in leaves) == spray.table_bytes
    # a stack over an unregistered local operator still closes over it
    from pylops_mpi_tpu.ops.local import Diagonal
    other = pmt.MPIVStack([Diagonal(jnp.ones(4)), Diagonal(jnp.ones(4))],
                          mesh=pmt.make_mesh(1))
    assert not operator_is_jit_arg(other)


def test_the_solver_program_holds_no_table_sized_constant(fused_hlo):
    Op, text = fused_hlo
    table = Op.ops[0].A.B.itrav.size          # entries of one table
    sizes = [int(np.prod([int(d) for d in dims.split(",")]))
             for dims in re.findall(r"= \w+\[([\d,]+)\]\S* constant\(", text)]
    assert max(sizes, default=0) < table // 8, max(sizes)
    # and the tables ARE parameters of the entry computation
    assert re.search(r"s32\[%d,%d,8,128\]\S* parameter\(" % (
        SIZES["ns"] * SIZES["nr"], Op.ops[0].A.B.itrav.shape[1]), text)


def test_nothing_pair_sized_is_built_on_the_host(monkeypatch):
    """Building the operator hands JAX no host array of a pair-pixel
    count or more: the tables are made by one program on the device."""
    g = B.geometry(SIZES)
    pair_pixels = SIZES["ns"] * SIZES["nr"] * SIZES["nz"] * SIZES["nx"]
    seen = []
    real = jnp.asarray

    def spy(a, *args, **kw):
        if isinstance(a, np.ndarray):
            seen.append(a.size)
        return real(a, *args, **kw)
    monkeypatch.setattr(M.jnp, "asarray", spy)
    K = KirchhoffDemigration(*g.args)
    assert seen and max(seen) * 8 <= pair_pixels, seen
    spray = K.A.B
    assert spray.itrav.dtype == jnp.int32
    assert spray.weight.dtype == jnp.float32
    assert isinstance(spray.itrav, jax.Array)


# --------------------------------------- scopes, events and counters
@pytest.mark.parametrize("scope", [
    "pmt.local.TravelTimeSpray", "pmt.local.Conv1D",
    "pmt.MPIVStack.matvec", "pmt.MPIVStack.rmatvec", "pmt_kirchhoff"])
def test_scopes_in_the_fused_solver(fused_hlo, scope):
    """The names the device trace splits the solve by survive on the
    ops inside the fused ``while_loop``; the local operators sit inside
    the stack's scopes."""
    names = [ln for ln in fused_hlo[1].split("\n")
             if "op_name=" in ln and "/while/body/" in ln and scope in ln]
    assert names, scope
    if scope.startswith("pmt.local."):
        assert all("pmt.MPIVStack." in ln for ln in names)


def test_events_and_counters(monkeypatch):
    monkeypatch.setenv("PYLOPS_MPI_TPU_TRACE", "spans")
    monkeypatch.setenv("PYLOPS_MPI_TPU_METRICS", "on")
    trace.clear_events()
    metrics.clear_metrics()
    sizes = dict(SIZES, nt=40)         # a short trace: taps fall off it
    mesh = pmt.make_mesh(1)
    Op = _op(sizes, mesh)
    spray = Op.ops[0].A.B
    pairs, npix = sizes["ns"] * sizes["nr"], sizes["nz"] * sizes["nx"]
    ev = [e["args"] for e in trace.get_events() if e["name"] == "lsm.tables"]
    assert len(ev) == 1
    assert (ev[0]["pairs"], ev[0]["npix"], ev[0]["stored"]) \
        == (pairs, npix, "pair")
    assert ev[0]["table_bytes"] == spray.table_bytes >= 8 * pairs * npix
    assert ev[0]["built_on"] == "cpu"
    counters = metrics.snapshot()["counters"]
    assert counters["kirchhoff.pair_pixels"] == pairs * npix
    assert counters["kirchhoff.taps_dropped"] == spray.dropped > 0

    trace.clear_events()
    Op.matvec(_vec(np.ones(npix), mesh, Partition.BROADCAST))
    Op.rmatvec(_vec(np.ones(pairs * 40), mesh, Partition.SCATTER))
    ev = [e["args"] for e in trace.get_events()
          if e["name"] == "kirchhoff.path_select"]
    assert [a["adjoint"] for a in ev] == [0, 1]
    for a in ev:
        assert a["form"] == "pmt_kirchhoff" and "why" not in a
        assert (a["pairs"], a["nt"], a["tile"]) == (pairs, 40, 1024)
        assert a["npix"] == spray.shape[1] and 2 <= a["band"] <= 40


def test_the_configuration_is_what_these_tests_run():
    assert CFG["builder"] == "lsm" and CFG["reduced"] == ["ns", "niter"]
    assert CFG["sizes"]["ns"] * 4 == CFG["sizes"]["ns_deployment"]
    for k, v in B.DEFAULT_SIZES.items():
        assert CFG["sizes"][k] == v, k
    s = CFG["sizes"]
    assert 8 * s["ns"] * s["nr"] * s["nz"] * s["nx"] == 8589934592
