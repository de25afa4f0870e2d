"""The Kirchhoff line on several devices: a generic ``MPIVStack`` whose
blocks are registered and alike takes its SHARDED form — each shard's
tables made on, laid on and applied by its own device under
``shard_map``, the adjoint's partial images summed by one ``psum``
(``pmt.collective.stack_reduce``). The system against the benchmark's
plain reference sharded by shots (``chipbench/builders/lsm_line.py``) on
2, 4 and 8 devices, through the loop's three limits; the placement, in
place and shot by shot; the compiled solver's collectives; the
fallbacks and the ``stack.placement`` event. Small, seeded, on the CPU
with interpreted kernels."""

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
from pylops_mpi_tpu.diagnostics import trace
from pylops_mpi_tpu.linearoperator import operator_is_jit_arg
from pylops_mpi_tpu.models import KirchhoffDemigration, MPILSM
from pylops_mpi_tpu.ops.local import Conv1D, Diagonal, MatrixMult
from pylops_mpi_tpu.parallel.mesh import concat_sharded
from pylops_mpi_tpu.solvers import basic
from pylops_mpi_tpu.utils import hlo
from chipbench.builders import lsm as B
from chipbench.builders import lsm_line as BL
from chipbench.loops import closed_vstack

with open(os.path.join(ROOT, "chipbench", "configs",
                       "lsm_kirchhoff_line.json")) as _f:
    CFG = json.load(_f)
# the line's rehearsal sizes with eight shots over the small image: a
# shot a device on the widest mesh
SIZES = dict(CFG["sizes"], **CFG["rehearse"])
SIZES.update(ns=8, dshot=48.0)
NITER = int(CFG["guarantees"]["niter"])
HOLD = int(CFG["guarantees"]["hold_niter"])
LIMITS = {k: float(CFG["guarantees"][k])
          for k in ("rel_tol", "resid_ratio", "repeat_tol")}
NPIX = SIZES["nz"] * SIZES["nx"]


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.linalg.norm(a - b) / np.linalg.norm(b)


def _op(mesh, sizes=SIZES, shots=None):
    g = B.geometry(sizes)
    src = g.sources if shots is None else g.sources[:, shots]
    return MPILSM(g.z, g.x, g.t, src, g.recs, g.vel, g.wav, g.wavc,
                  mesh=mesh)


def _vec(a, mesh, part):
    out = DistributedArray(global_shape=a.size, mesh=mesh, partition=part,
                           dtype=np.float32)
    out[:] = jnp.asarray(a, jnp.float32).ravel()
    return out


@pytest.fixture(scope="module")
def line():
    """The benchmark's side on one device of every mesh the tests use:
    its own travel times, data modelled by its own plain forward."""
    times = B.point_times(SIZES)
    width = B.band_width(SIZES, times)
    m = B.make_reflectivity(SIZES)(jax.random.key(1))
    mv, rmv = B.plain_system(SIZES, width=width)
    with jax.default_matmul_precision("highest"):
        d = mv(times, m)
        u = jax.random.normal(jax.random.key(2), d.shape, jnp.float32)
        Au = rmv(times, u)
    return dict(times=times, width=width, m=m, d=d, u=u, Au=Au)


# ------------------------------------- the system against the reference
@pytest.mark.parametrize("ndev", [2, 4, 8])
@pytest.mark.parametrize("what", ["forward", "adjoint", "answer"])
def test_the_sharded_line_matches_the_sharded_reference(line, ndev, what):
    """``MPILSM`` on ``ndev`` devices (sharded form) against the plain
    reference sharded by shots over the same mesh
    (``lsm_line.line_system`` / ``line_solve``): forward ``SCATTER``
    out, adjoint ``BROADCAST`` out, and the answer held by the loop's
    own three limits."""
    mesh = pmt.make_mesh(ndev)
    Op = _op(mesh)
    assert Op.form == "sharded"
    times = BL.shard_times(line["times"], mesh)
    mv, rmv = map(jax.jit, BL.line_system(SIZES, mesh, line["width"]))
    with jax.default_matmul_precision("highest"):
        if what == "forward":
            got = Op.matvec(_vec(line["m"], mesh, Partition.BROADCAST))
            assert got.partition == Partition.SCATTER
            assert _rel(got.asarray(), line["d"]) < 5e-6
            assert _rel(mv(times, line["m"]), line["d"]) < 5e-6
            return
        if what == "adjoint":
            got = Op.rmatvec(_vec(line["u"], mesh, Partition.SCATTER))
            assert got.partition == Partition.BROADCAST
            assert _rel(got.asarray(), line["Au"]) < 5e-6
            assert _rel(rmv(times, line["u"]), line["Au"]) < 5e-6
            return
        d = mv(times, line["m"])
        xref = BL.line_solve(SIZES, mesh, HOLD, line["width"]).solve(times, d)
        deep = BL.line_solve(SIZES, mesh, NITER, line["width"])
        ref_drop = float(deep(times, d)[1])
    y = _vec(d, mesh, Partition.SCATTER)
    x0 = _vec(np.zeros(NPIX), mesh, Partition.BROADCAST)
    held = pmt.cgls(Op, y, x0=x0, niter=HOLD, tol=0.0)[0]
    x = pmt.cgls(Op, y, x0=x0, niter=NITER, tol=0.0)[0]
    again = pmt.cgls(Op, y, x0=x0, niter=NITER, tol=0.0)[0]
    readings = {"rel_tol": _rel(held.asarray(), xref),
                "resid_ratio": float(deep.drop(times, d, x.array))
                / ref_drop,
                "repeat_tol": _rel(again.asarray(), x.asarray())}
    assert closed_vstack.judge(readings, LIMITS) == [], readings


def test_several_blocks_a_device(line):
    """Eight one-shot blocks on four devices (two a device: each
    device's shard of the stacked tables concatenated there, the blocks
    applied one after the other) against the plain reference."""
    g = B.geometry(SIZES)
    blocks = [KirchhoffDemigration(g.z, g.x, g.t, g.sources[:, [s]], g.recs,
                                   g.vel, g.wav, g.wavc) for s in range(8)]
    mesh = pmt.make_mesh(4)
    Op = pmt.MPIVStack(blocks, mesh=mesh)
    assert Op.form == "sharded"
    assert _rel(Op.matvec(_vec(line["m"], mesh, Partition.BROADCAST))
                .asarray(), line["d"]) < 5e-6
    assert _rel(Op.rmatvec(_vec(line["u"], mesh, Partition.SCATTER))
                .asarray(), line["Au"]) < 5e-6


# --------------------------------------------------------- placement
def test_each_device_holds_its_own_shots_tables_in_place():
    """Every stacked table's shard on device ``c`` IS shard ``c``'s
    table — the same buffer, made there (no copy) — and holds the
    entries of that shard's shots: those a block built for them alone
    holds, and only those."""
    mesh = pmt.make_mesh(4)
    Op = _op(mesh)
    g = B.geometry(SIZES)
    devices = list(mesh.devices.flat)
    for i, stacked in enumerate(Op._sharded):
        by_dev = {s.device: s.data for s in stacked.addressable_shards}
        assert set(by_dev) == set(devices)
        for c, (dev, block) in enumerate(zip(devices, Op.ops)):
            own = jax.tree_util.tree_leaves(block)[i]
            assert own.devices() == {dev}
            assert by_dev[dev].unsafe_buffer_pointer() \
                == own.unsafe_buffer_pointer()
            alone = KirchhoffDemigration(
                g.z, g.x, g.t, g.sources[:, 2 * c:2 * c + 2], g.recs,
                g.vel, g.wav, g.wavc)
            assert np.array_equal(np.asarray(by_dev[dev]), np.asarray(
                jax.tree_util.tree_leaves(alone)[i]))


def test_nothing_pair_sized_crosses_the_host(monkeypatch):
    """On four devices too, building the line hands JAX no host array of
    a shard's pair-pixel count: every table is made by a program on its
    own device."""
    import importlib
    M = importlib.import_module("pylops_mpi_tpu.models.lsm")
    seen = []
    real = jnp.asarray

    def spy(a, *args, **kw):
        if isinstance(a, np.ndarray):
            seen.append(a.size)
        return real(a, *args, **kw)
    monkeypatch.setattr(M.jnp, "asarray", spy)
    _op(pmt.make_mesh(4))
    shard_pair_pixels = SIZES["ns"] // 4 * SIZES["nr"] * NPIX
    assert seen and max(seen) * 8 <= shard_pair_pixels, seen


def test_concat_sharded_copies_only_what_is_not_in_place():
    mesh = pmt.make_mesh(4)
    devs = list(mesh.devices.flat)
    parts = [jax.device_put(jnp.full((3, 2), c, jnp.float32), d)
             for c, d in enumerate(devs)]
    out = concat_sharded(parts, mesh)
    assert out.shape == (12, 2)
    for s in out.addressable_shards:
        c = devs.index(s.device)
        assert s.data.unsafe_buffer_pointer() \
            == parts[c].unsafe_buffer_pointer()
    # two parts a device, and 0-d parts: concatenated on the device
    out = concat_sharded([np.float32(c) for c in range(8)], mesh)
    assert out.shape == (8,)
    np.testing.assert_array_equal(np.asarray(out), np.arange(8))
    assert {s.device for s in out.addressable_shards} == set(devs)


# ----------------------------------------- the compiled fused solver
@pytest.fixture(scope="module")
def fused4():
    mesh = pmt.make_mesh(4)
    Op = _op(mesh, dict(SIZES, ns=4))
    text = hlo.compiled_hlo(
        lambda op, y, x0: basic._cgls_fused(op, y, x0, 0.0, 0.0, niter=3),
        Op, _vec(np.ones(Op.shape[0]), mesh, Partition.SCATTER),
        _vec(np.zeros(Op.shape[1]), mesh, Partition.BROADCAST))
    return Op, text


def _collectives(text, kind):
    """``(result shapes, op_name)`` of every ``kind`` instruction."""
    out = []
    for line in text.split("\n"):
        m = re.match(r"\s*(?:ROOT )?%[\w.\-]+ = (.*?) " + kind
                     + r"(?:-start)?\(", line)
        if m:
            name = re.search(r'op_name="([^"]*)"', line)
            out.append((re.findall(r"\w+\[([\d,]*)\]", m.group(1)),
                        name.group(1) if name else ""))
    return out


def test_the_loop_body_reduces_the_image_once_an_adjoint(fused4):
    """In the fused loop's body: ONE all-reduce of the image's size (the
    adjoint's, under ``pmt.collective.stack_reduce``; XLA may carry a
    scalar in the same instruction), scalar all-reduces besides, no
    all-gather or other collective; no table-sized constant."""
    Op, text = fused4
    in_body = re.compile(r"^jit\([^)]*\)/while/body/")
    body = [(shapes, name) for shapes, name in
            _collectives(text, "all-reduce") if in_body.match(name)]
    image = [n for shapes, n in body if str(NPIX) in shapes]
    assert len(image) == 1 and "pmt.collective.stack_reduce" in image[0]
    for shapes, _ in body:
        assert all(s in ("", str(NPIX)) for s in shapes), shapes
    for kind in ("all-gather", "reduce-scatter", "collective-permute",
                 "all-to-all"):
        assert _collectives(text, kind) == [], kind
    table = Op._sharded[0].size // 4
    consts = [int(np.prod([int(d) for d in dims.split(",")]))
              for dims in re.findall(r"= \w+\[([\d,]+)\]\S* constant\(", text)]
    assert max(consts, default=0) < table // 8


def test_the_reduce_scope_survives_in_the_fused_loop(fused4):
    names = [ln for ln in fused4[1].split("\n")
             if "pmt.collective.stack_reduce" in ln and "/while/body/" in ln]
    assert names
    assert all("pmt.MPIVStack.rmatvec" in ln for ln in names)
    # and it holds the psum alone: no kernel, no local operator under it
    assert not any("pmt.local." in ln.split("stack_reduce")[1]
                   for ln in names if "op_name=" in ln)


def test_the_sharded_operator_is_a_jit_argument_of_stacked_tables(fused4):
    Op, _ = fused4
    assert operator_is_jit_arg(Op)
    leaves = jax.tree_util.tree_leaves(Op)
    assert len(leaves) == len(Op._sharded) == 3
    assert all(a is b for a, b in zip(leaves, Op._sharded))
    assert sum(int(a.nbytes) for a in leaves) == sum(
        b.A.B.table_bytes for b in Op.ops)


# ------------------------------------------ the fallbacks, the event
def _placement(make):
    trace.clear_events()
    op = make()
    ev = [e["args"] for e in trace.get_events()
          if e["name"] == "stack.placement"]
    assert len(ev) == 1
    return op, ev[0]


@pytest.mark.parametrize("case, why", [
    ("one_device", "one_device"), ("ns_not_a_multiple", "ragged"),
    ("unregistered", "unregistered"), ("filters_differ", "mixed")])
def test_the_replicated_form_and_its_why(monkeypatch, line, case, why):
    """What keeps the replicated form, and the answer it still gives."""
    monkeypatch.setenv("PYLOPS_MPI_TPU_TRACE", "spans")
    if case == "one_device":
        mesh = pmt.make_mesh(1)
        op, ev = _placement(lambda: _op(mesh))
    elif case == "ns_not_a_multiple":                # 3, 3, 2 shots
        mesh = pmt.make_mesh(3)
        op, ev = _placement(lambda: _op(mesh))
    elif case == "unregistered":
        mesh = pmt.make_mesh(2)
        op, ev = _placement(lambda: pmt.MPIVStack(
            [Diagonal(jnp.ones(4)), Diagonal(jnp.ones(4))], mesh=mesh))
    else:
        mesh = pmt.make_mesh(2)
        op, ev = _placement(lambda: pmt.MPIVStack(
            [Conv1D(16, np.ones(3, np.float32), offset=1),
             Conv1D(16, np.arange(3, dtype=np.float32), offset=1)],
            mesh=mesh))
    assert (op.form, ev["form"], ev["why"]) == ("replicated",) * 2 + (why,)
    assert op._sharded is None and op._local is not None
    assert ev["blocks"] == len(op.ops) and ev["shards"] == mesh.devices.size
    if case in ("one_device", "ns_not_a_multiple"):
        got = op.matvec(_vec(line["m"], mesh, Partition.BROADCAST))
        assert _rel(got.asarray(), line["d"]) < 5e-6
    if case == "filters_differ":       # each block its own filter still
        x = _vec(np.arange(16, dtype=np.float32), mesh, Partition.BROADCAST)
        y = np.asarray(op.matvec(x).asarray())
        for b, blk in enumerate(op.ops):
            np.testing.assert_allclose(y[16 * b:16 * (b + 1)], np.asarray(
                blk.matvec(jnp.arange(16, dtype=jnp.float32))), rtol=1e-6)


def test_the_placement_event_of_each_form(monkeypatch):
    monkeypatch.setenv("PYLOPS_MPI_TPU_TRACE", "spans")
    mesh = pmt.make_mesh(4)
    op, ev = _placement(lambda: _op(mesh))
    held = sum(int(a.nbytes) for a in op._sharded)
    assert ev == {"form": "sharded", "blocks": 4, "shards": 4,
                  "bytes_a_shard": held // 4}
    mats = [np.ones((3, 5), np.float32) for _ in range(4)]
    op, ev = _placement(lambda: pmt.MPIVStack(
        [MatrixMult(m) for m in mats], mesh=mesh))
    assert (op.form, ev["form"], "why" in ev) == ("batched", "batched",
                                                    False)
    assert ev["bytes_a_shard"] == 4 * 3 * 5 * 4 // 4


def test_the_stack_reports_the_longest_band_and_the_totals(monkeypatch):
    """The blocks differ only in their tables' counts; the sharded
    stack's template reports the longest band and the totals
    (``TravelTimeSpray.shard_merge``), and an apply's event says so."""
    monkeypatch.setenv("PYLOPS_MPI_TPU_TRACE", "spans")
    mesh = pmt.make_mesh(4)
    op = _op(mesh)
    sprays = [b.A.B for b in op.ops]
    assert len({s.band for s in sprays}) > 1        # the shards do differ
    tmpl = jax.tree_util.tree_unflatten(op._template, op._sharded).A.B
    assert tmpl.band == max(s.band for s in sprays)
    assert (tmpl.tiles, tmpl.windowed, tmpl.dropped) == tuple(
        sum(getattr(s, k) for s in sprays)
        for k in ("tiles", "windowed", "dropped"))
    trace.clear_events()
    op.rmatvec(_vec(np.ones(op.shape[0]), mesh, Partition.SCATTER))
    ev = [e["args"] for e in trace.get_events()
          if e["name"] == "kirchhoff.path_select"]
    assert [(a["band"], a["pairs"]) for a in ev] == [
        (tmpl.band, SIZES["ns"] // 4 * SIZES["nr"])]
    assert all(s.band <= tmpl.band for s in sprays)


def test_the_stack_reports_the_spray_s_walk_over_its_shards(monkeypatch):
    """The spray's loop steps are merged as totals too: the forward's
    event gives the line's ``walk`` and the shards' common ``group``."""
    monkeypatch.setenv("PYLOPS_MPI_TPU_TRACE", "spans")
    mesh = pmt.make_mesh(4)
    op = _op(mesh)
    sprays = [b.A.B for b in op.ops]
    tmpl = jax.tree_util.tree_unflatten(op._template, op._sharded).A.B
    walked, banded = (sum(getattr(s, k) for s in sprays)
                      for k in ("steps_walked", "steps_banded"))
    assert (tmpl.steps_walked, tmpl.steps_banded) == (walked, banded)
    assert walked >= banded > 0
    trace.clear_events()
    op.matvec(_vec(np.ones(op.shape[1]), mesh, Partition.BROADCAST))
    ev = [e["args"] for e in trace.get_events()
          if e["name"] == "kirchhoff.path_select"]
    assert [(a["adjoint"], a["group"], a["walk"]) for a in ev] == [
        (0, sprays[0].group, walked / banded)]
