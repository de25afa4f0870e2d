"""3-D post-stack inversion (PR 32): the streaming ``Conv1D``, the 3-D
modelling operator and Laplacian against a plain NumPy reference, the
regularised stacked solve against textbook CGLS, the scopes the device
trace splits it by, and the sweep schedule each operator resolves to.
Small on the CPU; the one compile for a described v5e (the kernel at
the benchmark's width) lives in a fixture, per the on-chip-measurement
guide."""

import re

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import pylops_mpi_tpu as pmt
from pylops_mpi_tpu import DistributedArray, StackedDistributedArray
from pylops_mpi_tpu.models import (MPIPoststackLinearModelling,
                                   poststack_inversion,
                                   poststack_regularized, ricker)
from pylops_mpi_tpu.ops import pallas_kernels as pk
from pylops_mpi_tpu.ops.local import Conv1D, MatrixMult
from pylops_mpi_tpu.solvers import basic
from pylops_mpi_tpu.utils import hlo


# ------------------------------------------------- plain NumPy reference
def np_conv(x, h, offset, axis=-1):
    n = x.shape[axis]
    return np.apply_along_axis(
        lambda t: np.convolve(t, h)[offset:offset + n], axis, x)


def np_modelling(m, wav):
    d = np.empty_like(m)
    d[..., 1:-1] = 0.5 * (m[..., 2:] - m[..., :-2])
    d[..., 0] = m[..., 1] - m[..., 0]
    d[..., -1] = m[..., -1] - m[..., -2]
    return 0.5 * np_conv(d, wav, len(wav) // 2)


def np_laplacian(m):
    out = np.zeros_like(m)
    for ax in range(m.ndim):
        a, b, c = ([slice(None)] * m.ndim for _ in range(3))
        a[ax], b[ax], c[ax] = slice(0, -2), slice(1, -1), slice(2, None)
        out[tuple(b)] += m[tuple(a)] - 2 * m[tuple(b)] + m[tuple(c)]
    return out


def np_cgls(A, y, x0, niter):
    """Textbook CGLS from ``x0`` on a dense matrix."""
    x = x0.copy()
    s = y - A @ x
    r = A.T @ s
    c, q, k = r.copy(), A @ r, r @ r
    for _ in range(niter):
        a = k / (q @ q)
        x, s = x + a * c, s - a * q
        r = A.T @ s
        k, kold = r @ r, k
        c = r + (k / kold) * c
        q = A @ c
    return x


WAV = ricker(np.arange(41) * 0.004, 15)[0]          # 81 taps


# ----------------------------------------------------------- the Conv1D
CONV_CASES = {
    "odd81-centre-f32-300": ((5, 300), 1, 81, 40, np.float32),
    "odd41-centre-f32-1024": ((3, 1024), 1, 41, 20, np.float32),
    "even10-axis0-f32": ((200, 6), 0, 10, 3, np.float32),
    "odd9-3d-f64": ((3, 4, 64), 2, 9, 4, np.float64),
    "offset0-f32-512": ((7, 512), 1, 81, 0, np.float32),
    "offset-last-two-tiles": ((7, 512), 1, 200, 199, np.float32),
    "nt0-130-not-128s": ((4, 130), -1, 41, 20, np.float32),
    "middle-axis-f32": ((3, 260, 5), 1, 31, 15, np.float32),
    "one-tap": ((6, 256), 1, 1, 0, np.float32),
    "filter-longer-than-axis": ((4, 50), 1, 81, 40, np.float64),
    "two-taps-f64-1d": ((384,), 0, 2, 1, np.float64),
    "complex64-data-and-filter": ((4, 130), 1, 41, 20, np.complex64),
    "complex128-300-taps": ((3, 700), 1, 300, 20, np.complex128),
    "rows-not-a-block": ((1031, 128), 1, 5, 2, np.float32),
}


def _seeded(rng, shape, dt):
    v = rng.standard_normal(shape)
    if np.issubdtype(dt, np.complexfloating):
        v = v + 1j * rng.standard_normal(shape)
    return v.astype(dt)


@pytest.mark.parametrize("case", sorted(CONV_CASES))
def test_conv1d_matches_numpy_and_its_adjoint(rng, case):
    dims, axis, nh, off, dt = CONV_CASES[case]
    h, x, u = (_seeded(rng, s, dt) for s in ((nh,), dims, dims))
    op = Conv1D(dims, h, axis=axis, offset=off, dtype=dt)
    y = np.asarray(op.matvec(jnp.asarray(x.ravel()))).reshape(dims)
    xa = np.asarray(op.rmatvec(jnp.asarray(u.ravel()))).reshape(dims)
    assert y.dtype == dt and xa.dtype == dt
    wide = np.result_type(dt, np.float64)
    want = np_conv(x.astype(wide), h.astype(wide), off, axis % len(dims))
    tol = 1e-6 if np.finfo(dt).bits == 32 else 1e-13
    assert np.linalg.norm(y - want) <= tol * np.linalg.norm(want)
    # the dot test: <u, A x> = <A^H u, x>
    lhs, rhs = np.vdot(u, y), np.vdot(xa, x)
    assert abs(lhs - rhs) <= 20 * tol * (np.linalg.norm(u)
                                         * np.linalg.norm(y))


@pytest.mark.parametrize("rows,n,nh,off", [(20, 512, 81, 40),
                                           (9, 256, 81, 0),
                                           (16, 1024, 200, 100)])
def test_pmt_conv1d_interpreted_matches_numpy(rng, rows, n, nh, off):
    h = jnp.asarray(rng.standard_normal(nh).astype(np.float32))
    x = rng.standard_normal((rows, n)).astype(np.float32)
    T = Conv1D._blocks(h, off, pk.conv1d_tile(nh))
    y = np.asarray(pk.conv1d_toeplitz(jnp.asarray(x), T))
    want = np_conv(x.astype(np.float64), np.asarray(h, np.float64), off)
    assert np.linalg.norm(y - want) <= 3e-7 * np.linalg.norm(want)


@pytest.mark.parametrize("nh,L", [(1, 128), (2, 128), (41, 128), (81, 128),
                                  (129, 128), (130, 256), (257, 256),
                                  (600, 640)])
def test_conv1d_tile_holds_the_filter(nh, L):
    """One form, one shape rule: whole 128-lane groups that hold
    ``nh - 1`` samples, so three blocks cover the band."""
    assert pk.conv1d_tile(nh) == L
    h = jnp.arange(1.0, nh + 1.0)
    for off in (0, nh // 2, nh - 1):
        T = np.asarray(Conv1D._blocks(h, off, L))
        assert T.shape == (3 * L, L)
        # every tap reaches every output sample once
        assert np.allclose(T.sum(axis=0), float(h.sum()))


def test_conv1d_toeplitz_wants_whole_tiles():
    T = Conv1D._blocks(jnp.ones(5), 2, 128)
    with pytest.raises(ValueError, match="whole 128-sample tiles"):
        pk.conv1d_toeplitz(jnp.zeros((8, 200), jnp.float32), T)
    with pytest.raises(ValueError, match="real rows"):
        pk.conv1d_toeplitz(jnp.zeros((8, 256), jnp.complex64), T)


@pytest.mark.parametrize("n,pad", [(300, 84), (1024, 0)])
def test_conv1d_path_select_event(monkeypatch, n, pad):
    from pylops_mpi_tpu.diagnostics import trace
    monkeypatch.setenv("PYLOPS_MPI_TPU_TRACE", "spans")
    trace.clear_events()
    op = Conv1D((4, n), jnp.asarray(WAV, jnp.float32), axis=1, offset=40,
                dtype=np.float32)
    op.matvec(jnp.ones(4 * n, jnp.float32))
    ev = [e for e in trace.get_events() if e["name"] == "conv1d.path_select"]
    trace.clear_events()
    assert ev and ev[0]["args"]["taps"] == 81 and ev[0]["args"]["n"] == n
    assert ev[0]["args"]["form"] == "pmt_conv1d"
    assert ev[0]["args"]["pad"] == pad


def test_laplacian_path_select_event_once_a_traced_apply(monkeypatch):
    """The stacked system's regulariser says which form it took: one
    ``laplacian.path_select`` a traced apply, ``pmt_laplacian``."""
    from pylops_mpi_tpu.diagnostics import trace
    monkeypatch.setenv("PYLOPS_MPI_TPU_TRACE", "spans")
    _, _, _, x0 = _small_system()
    Lap = poststack_regularized(WAV.astype(np.float32), 256, (4, 4), 100.0,
                                mesh=pmt.make_mesh(1), dtype=np.float32)[2]
    trace.clear_events()
    f = jax.jit(lambda v: Lap.rmatvec(Lap.matvec(v)))
    f(x0), f(x0)                      # the second call traces nothing
    ev = [e["args"] for e in trace.get_events()
          if e["name"] == "laplacian.path_select"]
    trace.clear_events()
    assert [(e["form"], e["adjoint"]) for e in ev] == [
        ("pmt_laplacian", 0), ("pmt_laplacian", 1)]
    assert all(tuple(e["dims"]) == (4, 4, 256) and e["shards"] == 1
               and tuple(e["axes"]) == (0, 1, 2) for e in ev)


# -------------------------------- the kernel, compiled for a described v5e
@pytest.fixture(scope="module")
def v5e_devices():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                                  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return topo.devices


@pytest.fixture(scope="module")
def one_chip(v5e_devices):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(v5e_devices[0])


V5E_CASES = {
    # rows, n, taps, temporaries allowed in volumes
    "survey-41-taps": (192 * 1024, 1024, 41, 2.05),
    "survey-81-taps": (192 * 1024, 1024, 81, 2.05),
    "ragged-axis-1000": (192 * 1024, 1000, 41, 4.2),
    "769-taps-the-largest-tile": (4096, 1536, 769, 2.05),
}


@pytest.mark.parametrize("case", sorted(V5E_CASES))
def test_pmt_conv1d_compiles_for_v5e(one_chip, monkeypatch, case):
    """At the benchmark's shape (192 x 1,024 traces of 1,024 samples),
    on a ragged axis and at the largest tile: Mosaic accepts the
    kernel, and no array of more than the stated multiple of one volume
    is live in the apply or the adjoint — the input and the output; two
    more where the axis is padded to whole tiles."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    rows, n, nh, allowed = V5E_CASES[case]
    monkeypatch.setattr(pk, "_interpret", lambda: False)
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        # as on the chip, where x64 is off (Mosaic has no i64 indices)
        with jax.enable_x64(False):
            h = jnp.asarray(ricker(np.arange(nh // 2 + 1) * 0.004, 15)[0],
                            jnp.float32)
            op = Conv1D((rows, n), h, axis=1, offset=nh // 2,
                        dtype=np.float32)
            x = jax.ShapeDtypeStruct((rows * n,), jnp.float32,
                                     sharding=one_chip)
            compiled = [jax.jit(f).lower(x).compile()
                        for f in (op._matvec, op._rmatvec)]
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        cc.reset_cache()
    vol = 4 * rows * n
    for c in compiled:
        assert "pmt_conv1d" in c.as_text()
        # the flat vector and the (rows, n) tiles are different layouts
        # on the chip: a relayout each way is all that is left beside
        # the call (and the padded copies, where the axis is ragged)
        ma = c.memory_analysis()
        assert ma.temp_size_in_bytes <= allowed * vol, (case, ma)


@pytest.mark.parametrize("adjoint", [False, True],
                         ids=["forward", "adjoint"])
@pytest.mark.parametrize("chips", [1, 4])
def test_pmt_laplacian_compiles_for_v5e(v5e_devices, monkeypatch, chips,
                                        adjoint):
    """At the benchmark's shape a chip (192 planes of 1,024 x 1,024
    float32), on one chip and on the four of the survey: Mosaic accepts
    the kernel inside ``MPILaplacian``'s apply, the rule takes it, every
    device's program holds ONE call, and beside it only the relayouts
    between the flat vector and the cube are left — no volume-sized
    pad, no gather; across chips the ghost planes travel as
    collective-permutes."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    dims = (192 * chips, 1024, 1024)
    V = int(np.prod(dims))
    monkeypatch.setattr(pk, "_interpret", lambda: False)
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        with jax.enable_x64(False):
            mesh = Mesh(np.array(v5e_devices[:chips]), ("sp",))
            Lap = pmt.MPILaplacian(dims, axes=(0, 1, 2), weights=(1, 1, 1),
                                   sampling=(1, 1, 1), mesh=mesh,
                                   dtype=np.float32)
            # a described device holds no array: the vector is abstract
            x = DistributedArray.tree_unflatten(
                (mesh, pmt.Partition.SCATTER, 0, (V,), pmt.local_split(
                    (V,), chips, pmt.Partition.SCATTER, 0), None),
                [jax.ShapeDtypeStruct((V,), jnp.float32,
                                      sharding=NamedSharding(mesh, P("sp")))])
            assert Lap._kernel_refusal(x) is None
            f = Lap.rmatvec if adjoint else Lap.matvec
            c = jax.jit(lambda v: f(v)).lower(x).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        cc.reset_cache()
    text = c.as_text()
    assert len(re.findall(r"custom-call\(", text)) == 1
    assert "pmt_laplacian" in text and "all-gather" not in text
    if chips > 1:
        assert "collective-permute-start" in text
    assert not re.findall(r"f32\[192,1024,\d+\]\S* pad\(", text)
    assert c.memory_analysis().temp_size_in_bytes <= 2.05 * 4 * V / chips


def _mdc_solver_for_v5e(v5e_devices, kernel_as: str, normal=False):
    """Compile ``mdd_obc.cgls_nv16``'s fused solver for one described
    chip at the cell's full size (PR 34; kept in THIS file because it
    holds the one topology fixture of the suite): the operator built
    inside the traced function from an abstract kernel — ``planes``:
    float32 ``(2, 64, 4096, 4096)``, what the cell hands over;
    ``complex``: complex64 ``(64, 4096, 4096)``. ``normal``: the
    one-sweep schedule, the kernels compiled as on a TPU — the program
    the cell runs (``cgls(normal=None)`` asks the chain,
    which answers yes on a TPU)."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    nf, n, nt, nv = 64, 4096, 1023, 16
    solve = basic._cgls_fused_normal if normal else basic._cgls_fused
    real = pk._interpret
    if normal:
        pk._interpret = lambda: False
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        with jax.enable_x64(False):
            mesh = Mesh(np.array(v5e_devices[:1]), ("sp",))
            G = jax.ShapeDtypeStruct(
                (2, nf, n, n), jnp.float32,
                sharding=NamedSharding(mesh, P(None, "sp"))) \
                if kernel_as == "planes" else jax.ShapeDtypeStruct(
                    (nf, n, n), jnp.complex64,
                    sharding=NamedSharding(mesh, P("sp")))
            V = nt * n * nv
            vec = lambda: DistributedArray.tree_unflatten(
                (mesh, pmt.Partition.BROADCAST, 0, (V,), pmt.local_split(
                    (V,), 1, pmt.Partition.BROADCAST, 0), None),
                [jax.ShapeDtypeStruct((V,), jnp.float32,
                                      sharding=NamedSharding(mesh, P()))])
            fn = jax.jit(lambda g, y, x0: solve(
                pmt.MPIMDC(g, nt=nt, nv=nv, dt=0.004, dr=12.5,
                           twosided=True, mesh=mesh),
                y, x0, jnp.float32(0), jnp.float32(0), niter=30))
            return fn.lower(G, vec(), vec()).compile()
    finally:
        pk._interpret = real
        jax.config.update("jax_enable_compilation_cache", True)
        cc.reset_cache()


@pytest.fixture(scope="module")
def mdc_v5e(v5e_devices):
    return _mdc_solver_for_v5e(v5e_devices, "planes")


@pytest.fixture(scope="module")
def mdc_one_sweep_v5e(v5e_devices):
    return _mdc_solver_for_v5e(v5e_devices, "planes", normal=True)


def test_mdc_one_sweep_solver_reads_the_kernel_once(mdc_one_sweep_v5e,
                                                    mdc_v5e):
    """The cell's one-sweep program at full size: the loop's body holds the
    plane-pair kernel ``pmt_normal_planes`` under
    ``pmt.MPIFredholm1.normal_matvec`` and NO plane ``einsum`` (a fusion
    that reads the ``(2, 64, 4096, 4096)`` planes: the classic body has
    two, its set-up two more; the set-up here reads the planes once
    through the same kernel); the four truncated DFT products are still under
    ``pmt.local.FFT``; arguments are the kernel and two vectors, and the
    compiler's peak is the classic program's to a MiB (10,469,256,704
    bytes against 10,469,255,680: no vector-sized buffer is added)."""
    c = mdc_one_sweep_v5e
    text = c.as_text()
    kernel, vec = 8 * 64 * 4096 * 4096, 4 * 1023 * 4096 * 16
    ma = c.memory_analysis()
    assert ma.argument_size_in_bytes <= kernel + 2.01 * vec
    assert ma.peak_memory_in_bytes <= \
        mdc_v5e.memory_analysis().peak_memory_in_bytes + (1 << 20)
    calls = [ln for ln in text.split("\n")
             if re.search(r"%pmt_normal_planes(\.\d+)? = ", ln)]
    assert len(calls) == 2             # the set-up's and the loop's
    assert sum(bool(re.search(
        r'op_name="[^"]*/while/body/[^"]*pmt\.MPIFredholm1\.normal_matvec/'
        r'pmt_normal_planes', ln)) for ln in calls) == 1
    assert not re.findall(
        r"^%fused_computation[\w.\-]* \([^)]*f32\[2,64,4096,4096\]",
        text, re.M)
    inside = re.findall(r'op_name="([^"]*/while/body/[^"]*)"', text)
    for scope in ("pmt.local.FFT", "pmt._MDCChain.fresh_normal_matvec",
                  "pmt.solver.step", "pmt.solver.direction"):
        assert any(scope in name.split("/") for name in inside), scope


def test_mdc_solver_compiles_for_v5e(mdc_v5e):
    """The kernel held ONCE beside the solve: arguments are the
    8.59 GB of planes and two vectors, temporaries a few vectors — no
    array of even a plane's size is made — and the 16-wide minor axis
    of ``(nt, nr, nv)`` is not padded to 128 lanes (3.2 GB for one FFT
    pair before ``local.FFT`` folded its axes)."""
    c = mdc_v5e
    kernel, vec = 8 * 64 * 4096 * 4096, 4 * 1023 * 4096 * 16
    ma = c.memory_analysis()
    assert ma.argument_size_in_bytes <= kernel + 2.01 * vec
    assert ma.temp_size_in_bytes <= 8 * vec < kernel // 4, ma
    text = c.as_text()
    assert "X64Split" not in text              # no complex at the entry
    for scope in ("pmt.local.FFT", "pmt.MPIFredholm1.matvec",
                  "pmt.MPIFredholm1.rmatvec"):
        assert re.search(r'op_name="[^"]*/while/body/[^"]*%s'
                         % re.escape(scope), text), scope


@pytest.mark.parametrize("what", ["no_half_spectrum", "constants",
                                  "temporaries", "no_fft"])
def test_mdc_solver_v5e_makes_only_the_bins_it_keeps(mdc_v5e, what):
    """PR 35, at the cell's full size: the transform is a product
    against ``(1023, 128)`` cosines and sines — nowhere in the solve an
    array of the half spectrum's ``512 x 65,536`` elements, no dense
    1,023-point DFT, and fewer temporaries than the four full
    transforms took (6.01 vectors, PR 34)."""
    text = mdc_v5e.as_text()
    if what == "no_half_spectrum":
        spectrum = 512 * 4096 * 16
        for dims in set(re.findall(r"\b(?:f32|c64)\[([\d,]+)\]", text)):
            n = int(np.prod([int(d) for d in dims.split(",")]))
            assert n not in (spectrum, 2 * spectrum), dims
    elif what == "constants":
        consts = re.findall(r"f32\[(\d+),(\d+)\]\S* constant\(", text)
        assert consts.count(("1023", "128")) == 2, consts   # Fop's, F1op's
        assert not [c for c in consts if c[0] == "1023" and c != (
            "1023", "128")], consts
    elif what == "temporaries":
        vec = 4 * 1023 * 4096 * 16
        assert mdc_v5e.memory_analysis().temp_size_in_bytes <= 4.1 * vec
    else:
        assert not re.findall(r" fft\(", text)


@pytest.fixture(scope="module")
def poststack_v5e(v5e_devices):
    """The stacked post-stack system's fused solver (the program
    ``poststack_3d.reg_cgls`` runs: two-sweep CGLS with an ``x0``)
    compiled for one described chip on a small cube of whole tiles
    (``nx % 8 == 0``, ``nt0 % 128 == 0``), the kernels compiled as on
    a TPU."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    ny, nx, nt0 = 8, 16, 256
    V = ny * nx * nt0
    real = pk._interpret
    pk._interpret = lambda: False
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        with jax.enable_x64(False):
            mesh = Mesh(np.array(v5e_devices[:1]), ("sp",))
            StackOp, _, _ = poststack_regularized(
                WAV.astype(np.float32), nt0, (ny, nx), 100.0, mesh=mesh,
                dtype=np.float32)
            vec = lambda: DistributedArray.tree_unflatten(
                (mesh, pmt.Partition.SCATTER, 0, (V,), pmt.local_split(
                    (V,), 1, pmt.Partition.SCATTER, 0), None),
                [jax.ShapeDtypeStruct((V,), jnp.float32,
                                      sharding=NamedSharding(mesh,
                                                             P("sp")))])
            fn = jax.jit(lambda op, y, x0: basic._cgls_fused(
                op, y, x0, jnp.float32(0), jnp.float32(0), niter=30))
            return fn.lower(StackOp, StackedDistributedArray(
                [vec(), vec()]), vec()).compile().as_text(), V
    finally:
        pk._interpret = real
        jax.config.update("jax_enable_compilation_cache", True)
        cc.reset_cache()


def _whole_carry_relayouts(text, n):
    """``copy`` / ``reshape`` / ``transpose`` instructions of the loop
    body's own computation whose result has a whole carry's ``n``
    elements or more: passes over HBM the algebra never asked for."""
    out = []
    for line in hlo.while_body_instructions(text):
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = \w+\[([\d,]*)\]\S* "
                     r"(copy|reshape|transpose)\(", line)
        if m and int(np.prod([int(d) for d in m.group(2).split(",")
                              if d] or [1])) >= n:
            out.append(m.group(1))
    return out


@pytest.mark.parametrize("cell", ["poststack_3d", "mdd_obc"])
def test_the_v5e_loop_body_relayouts_no_carry(poststack_v5e, mdc_v5e,
                                              cell):
    """ISSUE 37: the fused CGLS loop holds its vectors in the
    operator's own shape — ``(ny, nx, nt0)``; ``(1023, 65536)`` — so in
    the loop's body no ``copy`` and no ``reshape`` has a result of a
    whole carry (flat ``{T(1024)}`` carries against ``{T(8,128)}``
    cubes cost seven an iteration in ``poststack_3d.reg_cgls`` and four
    in ``mdd_obc.cgls_nv16``; PERF.md section 6, PR 37), and every
    scope the device trace splits the solve by is still on an op
    there."""
    if cell == "poststack_3d":
        text, n = poststack_v5e
        scopes = ["pmt.local.Conv1D", "pmt.local.FirstDerivative",
                  "pmt.MPILaplacian.matvec", "pmt.MPILaplacian.rmatvec",
                  "pmt.MPIBlockDiag.matvec", "pmt.MPIBlockDiag.rmatvec",
                  "pmt.MPIStackedVStack.matvec",
                  "pmt._ScaledLinearOperator.matvec"]
        assert text.count("pmt_laplacian") and text.count("pmt_conv1d")
    else:
        text, n = mdc_v5e.as_text(), 1023 * 4096 * 16
        scopes = ["pmt.local.FFT", "pmt.MPIFredholm1.matvec",
                  "pmt.MPIFredholm1.rmatvec", "pmt._MDCChain.matvec",
                  "pmt._MDCChain.rmatvec"]
    assert _whole_carry_relayouts(text, n) == []
    inside = re.findall(r'op_name="([^"]*/while/body/[^"]*)"', text)
    for scope in scopes + ["pmt.solver.step", "pmt.solver.direction",
                           "pmt.solver.cost"]:
        assert any(scope in name.split("/") for name in inside), scope
    assert re.search(r'op_name="[^"]*pmt\.solver\.setup', text)


def test_a_complex64_kernel_does_not_fit_a_v5e(v5e_devices):
    """What forced the planes: XLA splits a complex64 program argument
    into two float32 arrays at the program's entry, 8.59 GB of
    temporaries beside the 8.59 GB kernel (PERF.md section 6, PR 34).
    The operator therefore splits a complex kernel ONCE, at
    construction; traced, as here, that split is in the program."""
    with pytest.raises(Exception, match="RESOURCE_EXHAUSTED|memory"):
        _mdc_solver_for_v5e(v5e_devices, "complex")


# ------------------------------------------- the 3-D operators, 1/2/4 devices
def _cube(rng, ny=8, nx=6, nt0=160, dt=np.float32):
    m = np.cumsum(rng.standard_normal((ny, nx, nt0)) * 0.05, axis=-1)
    return (8.0 + m).astype(dt)


@pytest.mark.parametrize("ndev", [1, 2, 4])
def test_poststack_3d_modelling_and_laplacian(rng, ndev):
    mesh = pmt.make_mesh(ndev)
    m = _cube(rng)
    ny, nx, nt0 = m.shape
    StackOp, Op, Lap = poststack_regularized(
        WAV.astype(np.float32), nt0, (ny, nx), 100.0, mesh=mesh,
        dtype=np.float32)
    assert StackOp.dtype == Op.dtype == Lap.dtype == np.float32
    assert Lap.axes == (0, 1, 2) and Lap.dims_nd == (ny, nx, nt0)
    dm = DistributedArray.to_dist(m.ravel(), mesh=mesh,
                                  local_shapes=Op.local_shapes_m)
    y = StackOp.matvec(dm)
    m64 = m.astype(np.float64)
    want_d, want_r = np_modelling(m64, WAV), 10.0 * np_laplacian(m64)
    got_d = y.distarrays[0].asarray().reshape(m.shape)
    got_r = y.distarrays[1].asarray().reshape(m.shape)
    assert got_d.dtype == got_r.dtype == np.float32
    assert np.linalg.norm(got_d - want_d) <= 1e-6 * np.linalg.norm(want_d)
    assert np.linalg.norm(got_r - want_r) <= 1e-6 * np.linalg.norm(want_r)
    # the adjoint of the whole stack, by the dot test
    u = StackedDistributedArray([
        DistributedArray.to_dist(rng.standard_normal(m.size).astype(
            np.float32), mesh=mesh, local_shapes=Op.local_shapes_n),
        DistributedArray.to_dist(rng.standard_normal(m.size).astype(
            np.float32), mesh=mesh)])
    xa = StackOp.rmatvec(u).asarray().astype(np.float64)
    lhs = sum(np.vdot(a.asarray().astype(np.float64),
                      b.asarray().astype(np.float64))
              for a, b in zip(u.distarrays, y.distarrays))
    size = np.sqrt(sum(np.linalg.norm(a.asarray()) ** 2
                       for a in u.distarrays)
                   * sum(np.linalg.norm(b.asarray()) ** 2
                         for b in y.distarrays))
    assert abs(lhs - np.vdot(xa, m64.ravel())) <= 1e-5 * size


def test_the_2d_call_keeps_working(rng):
    nx, nt0 = 12, 64
    Op = MPIPoststackLinearModelling(WAV[30:51], nt0, nx)
    assert Op.shape == (nx * nt0, nx * nt0) and Op.dtype == np.float64
    m = rng.standard_normal((nx, nt0))
    dm = DistributedArray.to_dist(m.ravel(), local_shapes=Op.local_shapes_m)
    got = Op.matvec(dm).asarray().reshape(nx, nt0)
    np.testing.assert_allclose(got, np_modelling(m, WAV[30:51]),
                               rtol=1e-10, atol=1e-12)


# ------------------------------------------ the whole regularised solve
def _dense_system(shape, wav, scale):
    n = int(np.prod(shape))
    eye = np.eye(n).reshape((n,) + shape)
    A1 = np.stack([np_modelling(e, wav).ravel() for e in eye], axis=1)
    A2 = np.stack([scale * np_laplacian(e).ravel() for e in eye], axis=1)
    return np.concatenate([A1, A2])


@pytest.mark.parametrize("ndev,dt,tol", [(1, np.float64, 1e-9),
                                         (4, np.float64, 1e-9),
                                         (2, np.float32, 2e-4)])
def test_regularised_solve_matches_textbook_cgls(rng, ndev, dt, tol):
    mesh = pmt.make_mesh(ndev)
    shape = (4, 3, 24)
    wav = WAV[34:47].astype(dt)                           # 13 taps
    m = _cube(rng, *shape, dt=dt)
    x0 = np_conv(m.astype(np.float64), np.ones(5) / 5, 2).astype(dt)
    d = np_modelling(m.astype(np.float64), wav.astype(np.float64))
    got, Op = poststack_inversion(d.astype(dt), wav, niter=12, epsR=2.0,
                                  damp=0.0, mesh=mesh, dtype=dt, x0=x0)
    assert got.shape == shape and got.dtype == dt
    A = _dense_system(shape, wav.astype(np.float64), 2.0)
    y = np.concatenate([d.ravel(), np.zeros(m.size)])
    want = np_cgls(A, y, x0.astype(np.float64).ravel(), 12)
    assert np.linalg.norm(got.ravel() - want) <= tol * np.linalg.norm(want)
    # and it moved: the answer is not the background
    assert np.linalg.norm(want - x0.ravel()) > 1e-3 * np.linalg.norm(want)


# --------------------------------------------------- scopes and schedules
def _small_system(dt=np.float32):
    mesh = pmt.make_mesh(1)
    ny, nx, nt0 = 4, 4, 256
    StackOp, Op, Lap = poststack_regularized(
        WAV.astype(dt), nt0, (ny, nx), 100.0, mesh=mesh, dtype=dt)

    def vec():
        return DistributedArray(global_shape=ny * nx * nt0, mesh=mesh,
                                dtype=dt)
    return StackOp, Op, StackedDistributedArray([vec(), vec()]), vec()


@pytest.fixture(scope="module")
def fused_hlo():
    StackOp, _, y, x0 = _small_system()
    return hlo.compiled_hlo(
        lambda op, y, x0: basic._cgls_fused(op, y, x0, 0.0, 0.0, niter=3),
        StackOp, y, x0)


@pytest.mark.parametrize("scope", [
    "pmt.local.Conv1D", "pmt.local.FirstDerivative",
    "pmt.MPILaplacian.matvec", "pmt.MPILaplacian.rmatvec",
    "pmt.MPIBlockDiag.matvec", "pmt.MPIBlockDiag.rmatvec",
    "pmt.MPIStackedVStack.matvec", "pmt.MPIStackedVStack.rmatvec"])
def test_scopes_in_the_fused_solver(fused_hlo, scope):
    """The names the device trace splits the stacked solve by survive
    on the ops inside the fused ``while_loop``."""
    names = [ln for ln in fused_hlo.split("\n")
             if "op_name=" in ln and "/while/body/" in ln and scope in ln]
    assert names, scope
    if scope.startswith("pmt.local."):    # inside the operator's scope
        assert all("pmt.MPIBlockDiag." in ln for ln in names)
    if scope.startswith("pmt.MPILaplacian."):
        # ONE call of the kernel under the scope (interpreted here: the
        # grid's loop is its one ``while``), and no pad-and-slice left
        grid_loops = re.findall(
            r' while\([^\n]*op_name="[^"]*/while/body/[^"]*%s/pmt_laplacian/'
            r'while"' % re.escape(scope), fused_hlo)
        assert len(grid_loops) == 1, scope
        assert not [ln for ln in names if re.search(r" pad\(", ln)]


@pytest.mark.parametrize("which", ["flagship", "modelling", "stack"])
def test_sweep_schedule_each_operator_resolves_to(rng, monkeypatch, which):
    """As on a TPU (the kernels compiled): the flagship's batched
    blocks still take one sweep, the post-stack operators two."""
    monkeypatch.setattr(pk, "_interpret", lambda: False)
    if which == "flagship":
        mesh = pmt.make_mesh(1)
        blocks = [MatrixMult(rng.standard_normal((512, 512)).astype(
            np.float32)) for _ in range(2)]
        Op = pmt.MPIBlockDiag(blocks, mesh=mesh)
        x0 = DistributedArray(global_shape=1024, mesh=mesh,
                              dtype=np.float32)
        assert basic._resolve_normal(Op, x0, None) is True
        return
    StackOp, Op, _, x0 = _small_system()
    op = Op if which == "modelling" else StackOp
    assert basic._resolve_normal(op, x0, None) is False


def test_stacked_cgls_leaves_a_callers_x0_valid(rng):
    """The cell hands ``pmt.cgls`` the same ``x0`` solve after solve:
    the caller's vector stays what it was, and a second solve from it
    gives the first one's answer, bit for bit."""
    StackOp, Op, y, x0 = _small_system()
    y.distarrays[0][:] = jnp.asarray(
        rng.standard_normal(x0.global_shape[0]).astype(np.float32))
    x0[:] = jnp.asarray(8 + 0.1 * rng.standard_normal(
        x0.global_shape[0]).astype(np.float32))
    before = x0.asarray().copy()
    xa = pmt.cgls(StackOp, y, x0=x0, niter=5, tol=0.0)[0].asarray()
    np.testing.assert_array_equal(x0.asarray(), before)
    xb = pmt.cgls(StackOp, y, x0=x0, niter=5, tol=0.0)[0].asarray()
    np.testing.assert_array_equal(xa, xb)
    assert np.linalg.norm(xa - before) > 0


def test_fused_cgls_leaves_a_callers_x0_alone_without_an_eager_copy(
        rng, monkeypatch):
    """A caller's ``x0`` goes into the fused CGLS program undonated (the
    program copies it at entry): no eager device copy at the head of
    the solver's span, the caller's vector stays valid, and the entry
    is keyed apart from the donated one a fresh ``x0`` takes."""
    def refuse(v):
        raise AssertionError("fused CGLS made an eager copy of x0")
    monkeypatch.setattr(basic, "_donate_copy", refuse)
    mesh = pmt.make_mesh(1)
    Op = pmt.MPIBlockDiag([MatrixMult(
        (rng.standard_normal((12, 12)) + 6 * np.eye(12)).astype(np.float32))],
        mesh=mesh)
    y = DistributedArray.to_dist(rng.standard_normal(12).astype(np.float32),
                                 mesh=mesh)
    x0 = DistributedArray.to_dist(rng.standard_normal(12).astype(np.float32),
                                  mesh=mesh)
    before = x0.asarray().copy()
    xa = pmt.cgls(Op, y, x0=x0, niter=12, tol=0.0)[0].asarray()
    np.testing.assert_array_equal(x0.asarray(), before)
    xb = pmt.cgls(Op, y, niter=12, tol=0.0)[0].asarray()
    np.testing.assert_allclose(xa, xb, atol=5e-4)   # both at the f32 floor
    donated = sorted(bool(k[k.index("cgls") + 5]) for k in basic._FUSED_CACHE
                     if k[0] == id(Op))
    assert donated == [False, True]


# ------------------------- the Kirchhoff kernels and their solver (PR 38)
def _lsm_for_v5e(v5e_devices, what: str):
    """Compile, for one described chip at ``lsm_kirchhoff.cgls_shots8``'s
    full size (kept in THIS file because it holds the one topology
    fixture of the suite), ``tables``: the program that makes the packed
    per-pair tables; ``solver``: the fused CGLS of the stacked
    demigration, the operator a pytree ARGUMENT whose tables are
    abstract, as in the real program."""
    import importlib
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from pylops_mpi_tpu.ops.stack import MPIVStack
    M = importlib.import_module("pylops_mpi_tpu.models.lsm")
    ns, nr, nz, nx, nt = 8, 256, 512, 1024, 1024
    pairs, npix = ns * nr, nz * nx
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        with jax.enable_x64(False):
            mesh = Mesh(np.array(v5e_devices[:1]), ("sp",))
            rep = NamedSharding(mesh, P())
            S = lambda shape, dt=jnp.float32: jax.ShapeDtypeStruct(
                shape, dt, sharding=rep)
            if what == "tables":
                return jax.jit(lambda s, r, pix, ok, v, dt: M._tables(
                    s, r, pix, ok, v, dt, nt=nt)).lower(
                        S((ns, 2)), S((nr, 2)), S((npix, 2)),
                        S((npix,), bool), S(()), S(())).compile()
            it, wt, lohi = (S(p.shape, p.dtype) for p in jax.eval_shape(
                lambda i, w, ok: M._pack(i, w, ok, last=nt - 2),
                S((pairs, npix), jnp.int32), S((pairs, npix)),
                S((npix,), bool))[:3])
            spray = M.TravelTimeSpray._from_packed(
                (it, wt, lohi, 0, 0), pairs, npix, nt, 2, np.float32)
            wav = ricker(np.arange(41) * 0.004, 20)[0].astype(np.float32)
            conv = Conv1D(spray.dimsd, wav, axis=-1, offset=40,
                          dtype=np.float32)
            Op = MPIVStack([conv * spray * M._BlockOrder((nz, nx))],
                           mesh=mesh)

            def vec(n, part):
                return DistributedArray.tree_unflatten(
                    (mesh, part, 0, (n,), pmt.local_split((n,), 1, part, 0),
                     None), [S((n,))])
            fn = jax.jit(lambda op, y, x0: basic._cgls_fused(
                op, y, x0, jnp.float32(0), jnp.float32(0), niter=10))
            return fn.lower(Op, vec(pairs * nt, pmt.Partition.SCATTER),
                            vec(npix, pmt.Partition.BROADCAST)).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
        cc.reset_cache()


def _mosaic(text, name, kernel):
    """Kernel ``name``'s Mosaic module in a compiled program's text: a
    digest of its ops (locations left out) and one of the lines and
    columns of ``ops/pallas_kernels.py`` up to the end of the function
    ``kernel`` that its locations name (the kernel's own; its callers'
    move with any line below it)."""
    import base64
    import hashlib
    import inspect
    from jax._src.lib.mlir import ir
    line = next(ln for ln in text.split("\n") if "tpu_custom_call" in ln
                and re.search(r"%%%s(\.\d+)? = " % name, ln))
    body = base64.b64decode(re.search(r'"body":"([^"]+)"', line).group(1))
    ctx = ir.Context()
    ctx.allow_unregistered_dialects = True
    with ctx:
        module = ir.Module.parse(body)
        ops = module.operation.get_asm(enable_debug_info=False)
        located = module.operation.get_asm(enable_debug_info=True)
    src, first = inspect.getsourcelines(kernel)
    spans = sorted(set(
        m.group(0) for m in re.finditer(
            r'pallas_kernels\.py":(\d+):\d+ to [\d:]*\d+', located)
        if int(m.group(1)) < first + len(src)))
    digest = lambda t: hashlib.sha256(t.encode()).hexdigest()[:16]
    return digest(ops), digest("\n".join(spans))


# ``_mosaic`` of the solver's compile below under JAX 0.9.0, for each
# Kirchhoff kernel and the function that ends its own lines. The spray
# was changed on purpose when it took G traces a grid step over their
# union band (its ops read "4f83131e4cda861e" at one trace a step); the
# gather's ops are those of its lane-gather form, pinned as the spray is
SPRAY_MOSAIC = ("46aa58075873c589", "0ae4dfd4749198a1")
GATHER_MOSAIC = ("fca9030e5296c61e", "be92d8ae16fb6816")
# ``pmt_normal``'s, at the flagship cells' blocks (4,096^2 f32, 256-row
# tiles, one column: ``solve_k1``), unchanged since the plane-pair
# kernel was added beside it: the flagship cells run the kernel they ran
NORMAL_MOSAIC = ("9ce8715d9e83b727", "9e26709bd413b5a0")


def test_pmt_normal_is_the_flagships_kernel(one_chip, monkeypatch):
    """The one-sweep kernel of ``MPIBlockDiag`` compiled for a described
    v5e at the flagship's block: its Mosaic module and its source lines
    are pinned (a second one-sweep kernel beside it, ``pmt_normal_planes``,
    changed nothing of it)."""
    monkeypatch.setattr(pk, "_interpret", lambda: False)
    A = jax.ShapeDtypeStruct((2, 4096, 4096), jnp.float32, sharding=one_chip)
    X = jax.ShapeDtypeStruct((2, 1, 4096), jnp.float32, sharding=one_chip)
    assert pk._tile_args(A) == (256, False)
    with jax.enable_x64(False):
        text = jax.jit(pk.batched_normal_matvec).lower(A, X).compile(
        ).as_text()
    if jax.__version__ == "0.9.0":
        assert _mosaic(text, "pmt_normal", pk._normal_kernel) \
            == NORMAL_MOSAIC


@pytest.mark.parametrize("what", ["tables", "solver"])
def test_lsm_compiles_for_v5e(v5e_devices, monkeypatch, what):
    """Mosaic accepts ``pmt_kirchhoff`` / ``pmt_kirchhoff_adj`` at the
    cell's widths; the tables (8.59 GB and their bands) are ARGUMENTS of
    the solve with a few data vectors of temporaries beside them, and
    the program that makes them holds no second table."""
    monkeypatch.setattr(pk, "_interpret", lambda: False)
    c = _lsm_for_v5e(v5e_devices, what)
    tables, vec = 8 * 2048 * 524288, 4 * 2048 * 1024
    ma = c.memory_analysis()
    if what == "tables":
        assert tables <= ma.output_size_in_bytes <= 1.002 * tables
        assert ma.temp_size_in_bytes <= tables // 8, ma
        return
    assert tables <= ma.argument_size_in_bytes <= 1.002 * tables + 4 * vec
    assert ma.temp_size_in_bytes <= 4 * vec, ma
    text = c.as_text()
    for name in ("pmt_kirchhoff", "pmt_kirchhoff_adj", "pmt_conv1d"):
        assert re.search(r'op_name="[^"]*/while/body/[^"]*%s' % name,
                         text), name
    assert re.search(r'op_name="[^"]*/while/body/[^"]*pmt.MPIVStack.matvec/'
                     r'[^"]*pmt.local.TravelTimeSpray', text)
    if jax.__version__ == "0.9.0":
        assert _mosaic(text, "pmt_kirchhoff",
                       pk._kirchhoff_spray_kernel) == SPRAY_MOSAIC
        assert _mosaic(text, "pmt_kirchhoff_adj",
                       pk._kirchhoff_gather_kernel) == GATHER_MOSAIC
