"""Multi-dimensional deconvolution as a deployment (PR 34): the system
(``pmt.MPIMDC`` with defaults -> ``pmt.cgls``) against the benchmark
builder's plain reference — forward, adjoint and the 30-iteration
answer on 1, 2 and 8 virtual devices; the frequency shares adding up to
the uncut operator; the kernel held once, as its planes; the scopes and
the event the device trace and the log read. Small, seeded,
float32/complex64, on the CPU."""

import json
import os
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
from pylops_mpi_tpu.models import mdd
from pylops_mpi_tpu.ops import local
from pylops_mpi_tpu.solvers import basic
from pylops_mpi_tpu.utils import hlo
from chipbench.builders import mdd as B

SIZES = {"nfmax": 64, "nfmax_deployment": 256, "ns": 64, "nr": 64,
         "nt": 1023, "nv": 4, "dt": 0.004, "dr": 12.5, "f0": 20.0,
         "sigma": 0.25, "tau_max": 0.2, "events": 6, "noise": 0.05}
NITER = 30
# a kernel whose PLANE outweighs every vector, for the two tests that
# look for arrays of the kernel's size
WIDE = dict(SIZES, nfmax=8, nt=33, nv=2)


def _rel(a, b):
    a, b = np.asarray(a), np.asarray(b)
    wide = np.complex128 if np.iscomplexobj(b) else np.float64
    a, b = a.astype(wide), b.astype(wide)
    return np.linalg.norm(a - b) / np.linalg.norm(b)


def _planes(sizes=SIZES, seed=0):
    return B.make_kernel(sizes)(jax.random.key(seed))


def _mdc(P, mesh=None, sizes=SIZES, **kw):
    """Upstream's arguments and nothing else, as the cell's builder."""
    return pmt.MPIMDC(P, nt=sizes["nt"], nv=sizes["nv"], dt=sizes["dt"],
                      dr=sizes["dr"], twosided=True, mesh=mesh, **kw)


def _vec(a, mesh):
    out = DistributedArray(global_shape=a.size, mesh=mesh,
                           partition=Partition.BROADCAST, dtype=np.float32)
    out[:] = jnp.asarray(a, jnp.float32).ravel()
    return out


@pytest.fixture(scope="module")
def case():
    P = _planes()
    x = B.make_response(SIZES)(jax.random.key(1))
    mv, rmv = B.plain_system(SIZES)
    with jax.default_matmul_precision("highest"):
        d = mv(P, x)
        u = jax.random.normal(jax.random.key(2), d.shape, jnp.float32)
        xref, drop = B.plain_solve(SIZES, NITER)(P, d)
        return dict(P=P, x=x, d=d, u=u, Ad=rmv(P, u), xref=xref,
                    drop=float(drop))


# ------------------------------- the system against the plain reference
@pytest.mark.parametrize("ndev", [1, 2, 8])
@pytest.mark.parametrize("what", ["forward", "adjoint", "answer"])
def test_system_matches_the_plain_reference(case, ndev, what):
    mesh = pmt.make_mesh(ndev)
    Op = _mdc(case["P"], mesh)
    assert Op.dtype == np.float32
    if what == "forward":
        got = Op.matvec(_vec(case["x"], mesh)).asarray()
        assert got.dtype == np.float32
        assert _rel(got, np.ravel(case["d"])) < 2e-6
    elif what == "adjoint":
        got = Op.rmatvec(_vec(case["u"], mesh)).asarray()
        assert _rel(got, np.ravel(case["Ad"])) < 2e-6
        # the dot test, in the real inner product
        lhs = float(np.vdot(np.ravel(case["u"]), np.ravel(case["d"])))
        rhs = float(np.vdot(got, np.ravel(case["x"])))
        assert abs(lhs - rhs) <= 1e-5 * np.linalg.norm(case["u"]) \
            * np.linalg.norm(case["d"])
    else:
        x0 = DistributedArray(global_shape=Op.shape[1], mesh=mesh,
                              partition=Partition.BROADCAST,
                              dtype=np.float32)
        x = pmt.cgls(Op, _vec(case["d"], mesh), x0=x0, niter=NITER,
                     tol=0.0)[0]
        assert x.partition == Partition.BROADCAST
        assert _rel(x.asarray(), np.ravel(case["xref"])) < 1e-4
        assert case["drop"] < 0.5


def test_the_reference_keeps_pylops_real_fft_convention():
    """Orthonormal, the twinned bins scaled by sqrt(2): the half
    spectrum is an isometry of the real signal, and the adjoint passes
    the dot test."""
    v = jax.random.normal(jax.random.key(3), (65, 5, 3), jnp.float32)  # odd
    for nt in (65, 64):
        x = v[:nt]
        y = B.rfft_t(x, nt // 2 + 1, shift=True)
        assert float(jnp.linalg.norm(y)) == pytest.approx(
            float(jnp.linalg.norm(x)), rel=1e-5 if nt % 2 else 1e-2)
        u = jax.lax.complex(*(jax.random.normal(jax.random.key(k), y.shape,
                                                jnp.float32) for k in (4, 5)))
        lhs = float(jnp.real(jnp.vdot(u, y)))
        rhs = float(jnp.vdot(B.rfft_t_adj(u, nt, shift=True), x))
        assert abs(lhs - rhs) <= 1e-5 * float(jnp.linalg.norm(u)
                                              * jnp.linalg.norm(y))
    # and it is the program's: local.FFT on the same array
    op = local.FFT((65, 5, 3), axis=0, real=True, ifftshift_before=True,
                   dtype=np.float32)
    assert _rel(op.matvec(v.ravel()),
                B.rfft_t(v, 33, shift=True).ravel()) < 1e-6


# ------------------------------------------------------- the share test
def test_the_four_shares_add_up_to_the_uncut_forward(case):
    """The cell's share is tied to the deployment: the forward data of
    the four frequency shares (each a zero-padded kernel) add up to the
    uncut reference's forward, and the first share given as its own
    64-of-256-style cut is that share."""
    whole = dict(SIZES, nfmax=SIZES["nfmax_deployment"])
    Pw = _planes(whole, seed=5)
    x = _vec(case["x"], None)
    nf = SIZES["nfmax"]
    total = 0.0
    for k in range(whole["nfmax"] // nf):
        keep = (jnp.arange(whole["nfmax"]) // nf == k)[None, :, None, None]
        part = _mdc(jnp.where(keep, Pw, 0.0), sizes=whole).matvec(x)
        if k == 0:
            first = _mdc(Pw[:, :nf]).matvec(x).asarray()
            assert _rel(first, part.asarray()) < 1e-6
        total = total + part.asarray().astype(np.float64)
    with jax.default_matmul_precision("highest"):
        want = B.plain_system(whole)[0](Pw, case["x"])
    assert _rel(total, np.ravel(want)) < 2e-6


# ------------------------------------------------ the kernel, held once
def test_a_device_kernel_given_as_planes_is_kept_as_itself():
    P = jax.block_until_ready(_planes(WIDE, seed=6))
    size = P.nbytes

    def buffers():
        """Device buffers of a plane's size or more (two ``jax.Array``
        objects may share one)."""
        return {a.unsafe_buffer_pointer() for a in jax.live_arrays()
                if a.nbytes >= size // 2
                and len(a.sharding.device_set) == 1}

    before = buffers()
    Op = _mdc(P, pmt.make_mesh(1), WIDE)
    held = [leaf for leaf in jax.tree_util.tree_leaves(Op)
            if getattr(leaf, "shape", None) == P.shape]
    assert len(held) == 1
    assert held[0].unsafe_buffer_pointer() == P.unsafe_buffer_pointer()
    # no second array of the kernel's size (or of one plane's) was made
    assert not buffers() - before
    # and it travels into the fused solver as an argument, not a constant
    from pylops_mpi_tpu.linearoperator import operator_is_jit_arg
    assert operator_is_jit_arg(Op)


@pytest.mark.parametrize("form", ["complex-device", "complex-host",
                                  "planes-host"])
def test_a_complex_kernel_is_the_same_operator(case, form):
    """Upstream's form, ``G (nfmax, ns, nr)`` complex, is split into
    planes at construction; a host kernel on the host."""
    P = case["P"]
    G = {"complex-device": lambda: jax.lax.complex(P[0], P[1]),
         "complex-host": lambda: np.asarray(P[0]) + 1j * np.asarray(P[1]),
         "planes-host": lambda: np.asarray(P)}[form]()
    Op = _mdc(G)
    assert Op.dtype == np.float32
    stored = [leaf for leaf in jax.tree_util.tree_leaves(Op)
              if getattr(leaf, "ndim", 0) == 4]
    assert len(stored) == 1 and stored[0].dtype == jnp.float32
    assert _rel(Op.matvec(_vec(case["x"], None)).asarray(),
                np.ravel(case["d"])) < 2e-6


@pytest.mark.parametrize("bad", [np.ones((3, 4, 5, 5), np.float32),
                                 np.ones((2, 4, 5, 5), np.complex64)])
def test_a_4d_kernel_must_be_a_real_plane_pair(bad):
    with pytest.raises(ValueError, match="plane"):
        pmt.MPIFredholm1(bad, nz=1, dtype=np.complex64)


@pytest.mark.parametrize("saveGt", [True, False])
def test_saveGt_changes_nothing(case, saveGt):
    """Kept in the signature for upstream's sake: the adjoint contracts
    the other axis of the stored planes either way, and nothing of the
    kernel's size is stored beside it."""
    Op = _mdc(case["P"], saveGt=saveGt)
    got = Op.rmatvec(_vec(case["u"], None)).asarray()
    assert _rel(got, np.ravel(case["Ad"])) < 2e-6
    assert sum(getattr(leaf, "ndim", 0) >= 3
               for leaf in jax.tree_util.tree_leaves(Op)) == 1


# ------------------------------------------------- scopes and the event
@pytest.fixture(scope="module")
def fused_hlo():
    mesh = pmt.make_mesh(1)
    Op = _mdc(_planes(WIDE), mesh, WIDE)
    return hlo.compiled_hlo(
        lambda op, y, x0: basic._cgls_fused(op, y, x0, 0.0, 0.0, niter=3),
        Op, _vec(np.ones(Op.shape[0]), mesh), _vec(np.zeros(Op.shape[1]),
                                                   mesh))


@pytest.mark.parametrize("scope", [
    "pmt.local.FFT", "pmt.MPIFredholm1.matvec", "pmt.MPIFredholm1.rmatvec",
    "pmt._MDCChain.matvec", "pmt._MDCChain.rmatvec"])
def test_scopes_in_the_fused_solver(fused_hlo, scope):
    """The names the device trace splits the solve by survive on the
    ops inside the fused ``while_loop``; the local FFT sits inside the
    MDC chain's scope (``_MDCChain``: the product chain that knows its
    factors)."""
    names = [ln for ln in fused_hlo.split("\n")
             if "op_name=" in ln and "/while/body/" in ln and scope in ln]
    assert names, scope
    if scope == "pmt.local.FFT":
        assert all("pmt._MDCChain." in ln for ln in names)


def test_the_fused_solver_holds_no_second_kernel(fused_hlo):
    """No instruction of the compiled solve outside a fusion makes an
    array of the kernel's size (the kernel is a parameter). The CPU
    backend copies the plane a product reads; compiled for a v5e
    nothing of even a plane's size is left
    (``test_poststack_3d.py::test_mdc_solver_compiles_for_v5e``)."""
    plane = 2 * WIDE["nfmax"] * WIDE["ns"] * WIDE["nr"]
    import re
    fused = False
    for ln in fused_hlo.split("\n"):
        if ln and not ln.startswith(" "):
            fused = "fused_computation" in ln or "fusion" in ln.split("(")[0]
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = f32\[([\d,]+)\]\S* (\w[\w\-]*)\(",
                     ln)
        if not m or fused or m.group(2) in ("parameter", "get-tuple-element",
                                            "bitcast"):
            continue
        n = int(np.prod([int(d) for d in m.group(1).split(",")]))
        assert n < plane, ln[:200]


@pytest.mark.parametrize("engine,why", [(None, "complex_lowers"),
                                        ("planar", "kwarg"),
                                        ("complex", "kwarg")])
def test_engine_select_event(case, monkeypatch, engine, why):
    monkeypatch.setenv("PYLOPS_MPI_TPU_TRACE", "spans")
    trace.clear_events()
    _mdc(case["P"], **({} if engine is None else {"engine": engine}))
    ev = [e for e in trace.get_events() if e["name"] == "mdc.engine_select"]
    assert len(ev) == 1
    a = ev[0]["args"]
    assert a["engine"] == (engine or "complex") and a["why"] == why
    assert (a["nfmax"], a["ns"], a["nr"], a["nv"]) == (64, 64, 64, 4)
    assert a["kernel_bytes"] == 8 * 64 * 64 * 64
    monkeypatch.setenv("PYLOPS_MPI_TPU_FFT_MODE", "planar")
    from pylops_mpi_tpu.ops import dft
    dft._mode_cache = None
    trace.clear_events()
    _mdc(case["P"])
    a = [e for e in trace.get_events()
         if e["name"] == "mdc.engine_select"][0]["args"]
    assert engine is not None or (a["engine"], a["why"]) == ("planar",
                                                             "fft_mode")


# ---------------------------------------------------------- models.mdd
@pytest.mark.parametrize("where", ["device", "host"])
def test_models_mdd_runs_the_cells_solve(case, where):
    """Device or host arrays, the operator's real dtype, ``niter`` and
    ``tol`` passed through: the lines the cell's loop mirrors."""
    P, d = case["P"], case["d"]
    if where == "host":
        P, d = np.asarray(P), np.asarray(d)
    x, Op = mdd(P, d, nt=SIZES["nt"], nv=SIZES["nv"], dt=SIZES["dt"],
                dr=SIZES["dr"], twosided=True, niter=NITER, tol=0.0)
    assert Op.dtype == np.float32 and x.dtype == np.float32
    assert x.shape == (SIZES["nt"], SIZES["nr"], SIZES["nv"])
    assert _rel(x, case["xref"]) < 1e-4


# ------------------------------------------------ the folded local FFT
@pytest.mark.parametrize("axis", [0, 1, 2])
@pytest.mark.parametrize("real", [True, False])
def test_local_fft_folds_the_other_axes(rng, axis, real):
    """The apply works on ``(pre, n, post)`` with the 1s left out: the
    flat vector, forward and adjoint, is numpy's."""
    dims = (9, 6, 4)
    op = local.FFT(dims, axis=axis, real=real, dtype=np.float64)
    x = rng.standard_normal(dims)
    if real:
        want = np.fft.rfft(x, axis=axis, norm="ortho")
        k = [slice(None)] * 3
        k[axis] = slice(1, (dims[axis] - 1) // 2 + 1)
        want[tuple(k)] *= np.sqrt(2)
    else:
        want = np.fft.fft(x, axis=axis, norm="ortho")
    got = np.asarray(op.matvec(jnp.asarray(x.ravel())))
    assert _rel(np.abs(got), np.abs(want.ravel())) < 1e-12
    assert np.allclose(got, want.ravel(), atol=1e-12)
    u = rng.standard_normal(want.shape) + 1j * rng.standard_normal(want.shape)
    back = np.asarray(op.rmatvec(jnp.asarray(u.ravel())))
    lhs = np.real(np.vdot(u.ravel(), got))
    rhs = np.real(np.vdot(back, x.ravel()))
    assert abs(lhs - rhs) <= 1e-10 * np.linalg.norm(u) * np.linalg.norm(got)


# ------------------------- the transform that makes only the bins kept
def _np_cut_transform(x, axis, shift, nfkeep):
    """NumPy's: ``ifftshift``, ``rfft`` (ortho, the twinned bins times
    sqrt(2)), then the cut to the first ``nfkeep`` bins."""
    nt = x.shape[axis]
    xs = np.fft.ifftshift(x, axes=axis) if shift else x
    y = np.fft.rfft(xs, axis=axis, norm="ortho")
    k = [slice(None)] * x.ndim
    k[axis] = slice(1, (nt - 1) // 2 + 1)
    y[tuple(k)] *= np.sqrt(2)
    k[axis] = slice(0, nfkeep)
    return y[tuple(k)]


def _np_cut_adjoint(u, nt, axis, shift):
    """NumPy's: zero-pad the bins cut away, halve the doubled bins,
    ``irfft``, ``fftshift``."""
    pad = [(0, 0)] * u.ndim
    pad[axis] = (0, nt // 2 + 1 - u.shape[axis])
    up = np.pad(u, pad)
    k = [slice(None)] * u.ndim
    k[axis] = slice(1, (nt - 1) // 2 + 1)
    up[tuple(k)] /= np.sqrt(2)
    y = np.fft.irfft(up, n=nt, axis=axis, norm="ortho")
    return np.fft.fftshift(y, axes=axis) if shift else y


def _planes_of(u):
    return np.concatenate([u.real.ravel(), u.imag.ravel()])


@pytest.mark.parametrize("axis", [0, 1, 2])
@pytest.mark.parametrize("keep", ["one", "middle", "nyquist"])
@pytest.mark.parametrize("shift", [False, True])
@pytest.mark.parametrize("nt", [33, 32])
def test_the_cut_transform_is_numpys_rfft_then_cut(rng, nt, shift, keep,
                                                   axis):
    """``local.FFT(nfkeep=K)`` is ``rfft``-then-cut and its adjoint
    pad-then-``irfft``: float64 to 1e-12, float32 to 2e-6, complex and
    ``planes=True``, and the dot test. ``nyquist`` keeps every bin (the
    uncut transform, through ``jnp.fft``); the others are the one
    product."""
    nf = nt // 2 + 1
    K = {"one": 1, "middle": nf // 2, "nyquist": nf}[keep]
    dims = [5, 4, 3]
    dims[axis] = nt
    x = rng.standard_normal(dims)
    want = _np_cut_transform(x, axis, shift, K)
    u = rng.standard_normal(want.shape) + 1j * rng.standard_normal(want.shape)
    back = _np_cut_adjoint(u, nt, axis, shift)
    for dtype, tol in ((np.float64, 1e-12), (np.float32, 2e-6)):
        for planes in (False, True):
            op = local.FFT(dims, axis=axis, real=True, ifftshift_before=shift,
                           dtype=dtype, planes=planes, nfkeep=K)
            assert (op._why is None) == (K < nf)
            assert op.shape == ((2 if planes else 1) * want.size, x.size)
            cdt = np.complex128 if dtype == np.float64 else np.complex64
            assert op.dtype == (dtype if planes else cdt)
            got = np.asarray(op.matvec(jnp.asarray(x.ravel(), dtype)))
            assert got.dtype == op.dtype
            if planes:
                got = got[:want.size] + 1j * got[want.size:]
            assert _rel(got, want.ravel()) < tol
            uin = _planes_of(u).astype(dtype) if planes \
                else u.ravel().astype(cdt)
            z = np.asarray(op.rmatvec(jnp.asarray(uin)))
            assert z.dtype == dtype
            assert _rel(z, back.ravel()) < tol
            lhs = np.real(np.vdot(u.ravel(), got))
            rhs = np.vdot(z, x.ravel())
            assert abs(lhs - rhs) <= 10 * tol * np.linalg.norm(u) \
                * np.linalg.norm(got)


@pytest.mark.parametrize("nt", [33, 32])
@pytest.mark.parametrize("shift", [False, True])
def test_the_matrix_reaches_the_nyquist_bin(rng, nt, shift):
    """``W = [C | S]`` with every bin kept IS the isometric half
    spectrum: bin 0 and an even length's Nyquist bin are not doubled
    and their sine columns are zero, so ``W Wᵀ = I``."""
    nf = nt // 2 + 1
    W = local._truncated_dft_matrix(nt, nt, nf, shift, "float64")
    assert W.shape == (nt, 2 * nf) and W.dtype == np.float64
    x = rng.standard_normal((nt, 7))
    y = x.T @ W
    want = _np_cut_transform(x, 0, shift, nf)
    assert np.allclose(y[:, :nf].T, want.real, atol=1e-13)
    assert np.allclose(y[:, nf:].T, want.imag, atol=1e-13)
    assert not W[:, nf].any()
    assert nt % 2 or np.abs(W[:, -1]).max() < 1e-15
    assert np.allclose(W @ W.T, np.eye(nt), atol=1e-13)
    assert local._truncated_dft_matrix(nt, nt, 5, shift,
                                       "float32").dtype == np.float32


@pytest.mark.parametrize("nfft", [40, 24])
def test_the_cut_transform_pads_or_truncates_to_nfft(rng, nfft):
    """A sample the transform truncates away meets a zero row; a longer
    ``nfft`` is more columns of the same rows."""
    nt, K = 33, 6
    x = rng.standard_normal((nt, 5))
    op = local.FFT((nt, 5), axis=0, nfft=nfft, real=True, dtype=np.float64,
                   nfkeep=K)
    xp = np.zeros((nfft, 5))
    xp[:min(nt, nfft)] = x[:nfft]
    want = _np_cut_transform(xp, 0, False, K)
    assert _rel(op.matvec(jnp.asarray(x.ravel())), want.ravel()) < 1e-12
    assert not op._W[nfft:].any()


@pytest.mark.parametrize("planes", [False, True])
@pytest.mark.parametrize("shift", [False, True])
def test_the_rules_other_side_is_the_fft_with_the_cut_beside_it(
        rng, shift, planes):
    """A band of a power-of-two length wider than the rule takes (XLA
    has a real FFT there): the same operator as ``jnp.fft``, the cut
    after it and the pad before its adjoint."""
    nt, K, dims = 2048, 600, (2048, 3)
    op = local.FFT(dims, axis=0, real=True, ifftshift_before=shift,
                   dtype=np.float64, planes=planes, nfkeep=K)
    assert op._why == "wide" and op._W is None
    x = rng.standard_normal(dims)
    want = _np_cut_transform(x, 0, shift, K)
    got = np.asarray(op.matvec(jnp.asarray(x.ravel())))
    if planes:
        got = got[:want.size] + 1j * got[want.size:]
    assert _rel(got, want.ravel()) < 1e-12
    u = want[::-1] * (1 + 0.5j)
    z = op.rmatvec(jnp.asarray(_planes_of(u) if planes else u.ravel()))
    assert _rel(z, _np_cut_adjoint(u, nt, 0, shift).ravel()) < 1e-12


@pytest.mark.parametrize("kw", [dict(nfkeep=0), dict(nfkeep=18),
                                dict(nfkeep=4, real=False)])
def test_nfkeep_cuts_a_real_half_spectrum_only(kw):
    with pytest.raises(ValueError, match="nfkeep"):
        local.FFT((33, 4), axis=0, **kw)


@pytest.mark.parametrize("nfft,nfkeep,pays", [
    (1023, 64, True), (1023, 256, True), (1023, 511, True),
    (1000, 256, True), (4095, 2047, True),          # no power of two
    (1024, 64, True), (1024, 512, True), (4096, 512, True),
    (4096, 513, False), (4096, 2048, False),        # a power of two
    (1023, 512, False), (1024, 513, False)])        # nothing cut
def test_the_rule_by_shape(nfft, nfkeep, pays):
    """Both sides of ``truncated_dft_pays``, at the shapes whose rows
    its docstring holds (measured on the chip, PR 35)."""
    assert local.truncated_dft_pays(nfft, nfkeep) is pays


@pytest.mark.parametrize("adjoint", [0, 1])
@pytest.mark.parametrize("form,why,kw", [
    ("truncated_dft", None, dict(nfkeep=6)),
    ("fft", "uncut", dict()),
    ("fft", "wide", dict(nfkeep=16)),
    ("fft", "complex", dict(real=False))])
def test_fft_path_select_event(monkeypatch, form, why, kw, adjoint):
    """One event a traced apply, beside ``conv1d.path_select``."""
    monkeypatch.setenv("PYLOPS_MPI_TPU_TRACE", "spans")
    if why == "wide":                # a rule that stops at 8 bins
        monkeypatch.setattr(local, "truncated_dft_pays",
                            lambda nfft, nfkeep: nfkeep <= 8)
    op = local.FFT((33, 4, 3), axis=0, ifftshift_before=kw.get("real", True),
                   dtype=np.float32, **kw)
    v = jnp.ones(op.shape[0] if adjoint else op.shape[1], op.dtype)
    trace.clear_events()
    jax.jit(op.rmatvec if adjoint else op.matvec)(v)
    ev = [e["args"] for e in trace.get_events()
          if e["name"] == "fft.path_select"]
    assert len(ev) == 1
    a = ev[0]
    assert (a["form"], a.get("why")) == (form, why)
    assert (a["nt"], a["nfft"], a["traces"], a["adjoint"]) == (33, 33, 12,
                                                               adjoint)
    assert a["nfkeep"] == {"truncated_dft": 6, "uncut": 17, "wide": 16,
                           "complex": 33}[why or form]


# ------------------------------- MPIMDC against the OLD chain, written out
def _old_mdc(P, mesh, engine, sizes=SIZES):
    """``MPIMDC`` as PR 34 built it: ``F1ᴴ · I1ᴴ · Fredholm1 · I · F``,
    the whole half spectrum made by ``local.FFT`` and cut by a slice
    operator beside it (``local.Identity`` on the complex engine's
    flat prefix, a plane-aware crop on the planar one's)."""
    from pylops_mpi_tpu.linearoperator import aslinearoperator
    nt, nv = sizes["nt"], sizes["nv"]
    nfmax, ns, nr = P.shape[-3:]
    nfft = (nt + 1) // 2
    planar = engine == "planar"

    def cut(inner):
        if not planar:
            return local.Identity(nfmax * inner, nfft * inner,
                                  dtype=np.complex64)
        return local.FunctionOperator(
            lambda v: v.reshape(2, nfft, inner)[:, :nfmax].ravel(),
            lambda v: jnp.pad(v.reshape(2, nfmax, inner),
                              ((0, 0), (0, nfft - nfmax), (0, 0))).ravel(),
            N=2 * nfmax * inner, M=2 * nfft * inner, dtype=np.float32)

    fft = lambda n, shift: aslinearoperator(local.FFT(
        (nt, n, nv), axis=0, real=True, ifftshift_before=shift,
        dtype=np.float32, planes=planar))
    Frop = pmt.MPIFredholm1(
        P, nv, mesh=mesh, dtype=np.float32 if planar else np.complex64,
        planar=planar) * np.float32(sizes["dr"] * sizes["dt"] * np.sqrt(nt))
    Op = (fft(ns, False).H * aslinearoperator(cut(ns * nv)).H * Frop
          * aslinearoperator(cut(nr * nv)) * fft(nr, True))
    Op.dtype = np.dtype(np.float32)
    return Op


@pytest.mark.parametrize("ndev", [1, 2, 8])
@pytest.mark.parametrize("engine", ["complex", "planar"])
def test_mdc_is_the_old_chain(case, ndev, engine):
    """Forward, adjoint and the 30-iteration answer of the three-operator
    chain against the five-operator one it replaces."""
    mesh = pmt.make_mesh(ndev)
    new, old = _mdc(case["P"], mesh, engine=engine), \
        _old_mdc(case["P"], mesh, engine)
    assert new.shape == old.shape and new.dtype == old.dtype == np.float32
    x, u, d = (_vec(case[k], mesh) for k in ("x", "u", "d"))
    assert _rel(new.matvec(x).asarray(), old.matvec(x).asarray()) < 2e-6
    assert _rel(new.rmatvec(u).asarray(), old.rmatvec(u).asarray()) < 2e-6
    x0 = lambda Op: DistributedArray(
        global_shape=Op.shape[1], mesh=mesh, partition=Partition.BROADCAST,
        dtype=np.float32)
    got, want = (pmt.cgls(Op, d, x0=x0(Op), niter=NITER, tol=0.0)[0]
                 for Op in (new, old))
    assert _rel(got.asarray(), want.asarray()) < 2e-5


def test_mdc_with_nothing_cut_is_the_uncut_transform(monkeypatch):
    """``nfmax == nfft``: the operator is what it was, ``jnp.fft``."""
    sizes = dict(SIZES, nt=65, nfmax=33, ns=8, nr=8)
    P = _planes(sizes, seed=8)
    Op, old = _mdc(P, None, sizes), _old_mdc(P, None, "complex", sizes)
    x = _vec(np.random.default_rng(0).standard_normal(Op.shape[1]), None)
    monkeypatch.setenv("PYLOPS_MPI_TPU_TRACE", "spans")
    trace.clear_events()
    got = Op.matvec(x).asarray()
    forms = [(e["args"]["form"], e["args"]["why"], e["args"]["adjoint"])
             for e in trace.get_events() if e["name"] == "fft.path_select"]
    assert forms == [("fft", "uncut", 0), ("fft", "uncut", 1)]
    assert _rel(got, old.matvec(x).asarray()) < 1e-6


def test_mdc_signature_is_upstreams_and_the_engine():
    import inspect
    assert list(inspect.signature(pmt.MPIMDC).parameters) == [
        "G", "nt", "nv", "nfreq", "dt", "dr", "twosided", "saveGt", "conj",
        "prescaled", "mesh", "compute_dtype", "engine"]


def test_the_configuration_is_what_these_tests_run():
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "mdd_obc.json")) as f:
        cfg = json.load(f)
    small = dict(cfg["sizes"], **cfg["rehearse"])
    assert small == SIZES


# ------------------------------- one read of the kernel an iteration
from pylops_mpi_tpu.diagnostics import metrics          # noqa: E402
from pylops_mpi_tpu.ops import fredholm, mdc            # noqa: E402
from pylops_mpi_tpu.ops import pallas_kernels as pk     # noqa: E402


def _spectra(rng, Fred, dtype):
    """A model-side and a data-side spectrum of ``Fred`` as its own
    vectors, bin 0's imaginary part not zero."""
    cdt = np.result_type(dtype, np.complex64)

    def one(inner):
        n = Fred.nsl * inner * Fred.nz
        a = rng.standard_normal(n) + 1j * rng.standard_normal(n)
        return DistributedArray.to_dist(a.astype(cdt), mesh=Fred.mesh,
                                        partition=Partition.BROADCAST)
    return one(Fred.ny), one(Fred.nx)


@pytest.mark.parametrize("ndev", [1, 2, 4])
@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_the_plane_pair_kernel_is_the_two_sweeps(rng, dtype, ndev):
    """``pmt_normal_planes`` (interpreted) makes ``(Q, Gᴴ M Q, Gᴴ S)``
    as the two ``_contract_planes`` sweeps (the operator's ``matvec``
    and ``rmatvec``) do, within the dtype's rounding, each shard's
    slices under ``shard_map``; bin 0's block is not zero and its
    spectra have an imaginary part, so the mask ``M`` (bin 0's
    imaginary part of ``Q`` zeroed, global bin 0 only) is exercised,
    and both agree with NumPy's complex product."""
    nsl, ns, nr, nz = 4, 64, 48, 3
    P = rng.standard_normal((2, nsl, ns, nr)).astype(dtype)
    Fred = pmt.MPIFredholm1(P, nz, mesh=pmt.make_mesh(ndev),
                            dtype=np.result_type(dtype, np.complex64))
    v, s = _spectra(rng, Fred, dtype)
    one = [np.asarray(t.asarray()) for t in Fred.normal_planes(v, s)]
    Q = np.asarray(Fred.matvec(v).asarray()).reshape(nsl, ns, nz)
    MQ = Q.copy()
    MQ[0] = MQ[0].real
    MQ = DistributedArray.to_dist(MQ.ravel(), mesh=Fred.mesh,
                                  partition=Partition.BROADCAST)
    two = [Q.ravel()] + [np.asarray(Fred.rmatvec(t).asarray())
                         for t in (MQ, s)]
    G = P[0].astype(np.float64) + 1j * P[1]
    V = np.asarray(v.asarray()).reshape(nsl, nr, nz)
    S = np.asarray(s.asarray()).reshape(nsl, ns, nz)
    Q = np.einsum("kxy,kyz->kxz", G, V)
    MQ = Q.copy()
    MQ[0] = MQ[0].real
    want = [Q, np.einsum("kxy,kxz->kyz", G.conj(), MQ),
            np.einsum("kxy,kxz->kyz", G.conj(), S)]
    tol = 1e-6 if dtype == np.float32 else 1e-13
    for a, b, w in zip(one, two, want):
        assert a.dtype == b.dtype == Fred.dtype
        assert _rel(a, b) < tol
        assert _rel(a, w.ravel()) < tol
    # the mask touches bin 0 alone
    Z1 = one[1].reshape(nsl, nr, nz)
    unmasked = np.einsum("kxy,kxz->kyz", G.conj(), Q)
    assert _rel(Z1[1:], unmasked[1:]) < tol
    assert _rel(Z1[0], unmasked[0]) > 1e-2


@pytest.mark.parametrize("shift", [True, False])
def test_the_kept_bins_round_trip_is_the_mask(shift):
    """``Wᵀ W`` of the cell's ``_truncated_dft_matrix`` (``nt`` 1,023,
    64 bins kept; ``F`` shifts, ``F1`` does not), made in float32 and
    multiplied in float64, is the identity but for a zero at bin 0's
    imaginary part: on the kept bins ``F1 F1ᴴ = M``, the mask the
    plane-pair kernel applies."""
    nt, nf = 1023, 64
    W = local._truncated_dft_matrix(nt, nt, nf, shift,
                                    "float32").astype(np.float64)
    want = np.eye(2 * nf)
    want[nf, nf] = 0.0
    WtW = W.T @ W
    assert np.abs(np.diag(WtW) - np.diag(want)).max() < 2.5e-8
    assert np.abs(WtW - np.diag(np.diag(WtW))).max() < 2e-8
    assert WtW[nf, nf] == 0.0


def _force(monkeypatch, form):
    """``normal_form``'s answer forced to ``form`` (the kernel runs
    interpreted where ``one_sweep`` is forced off a TPU)."""
    real = fredholm.MPIFredholm1.normal_form

    def forced(self, columns=False):
        got = real(self, columns)
        return (form, None if form == "one_sweep" else got[1], got[2])
    monkeypatch.setattr(fredholm.MPIFredholm1, "normal_form", forced)


@pytest.mark.parametrize("engine", ["complex", "planar"])
@pytest.mark.parametrize("ndev", [1, 2])
@pytest.mark.parametrize("form", ["one_sweep", "pair"])
def test_the_chain_gives_u_q_g_from_one_product(case, monkeypatch, form,
                                                ndev, engine):
    """``(q, adjoint) = Op.fresh_normal_matvec(c, s)``: ``q = Op c``,
    ``adjoint(0) = Opᴴ s`` and ``adjoint(0) − adjoint(1) = OpᴴOp c``,
    each to the chain's own ``matvec`` / ``rmatvec``; ``adjoint(t)``
    at a step is ``Opᴴ (s − t q)``. Both engines: the planar one's
    spectra are plane pairs, its mask plane 1's bin 0."""
    _force(monkeypatch, form)
    mesh = pmt.make_mesh(ndev)
    Op = _mdc(case["P"], mesh, engine=engine)
    assert isinstance(Op, mdc._MDCChain) and Op.has_fresh_normal
    c, s = _vec(case["x"], mesh), _vec(case["u"], mesh)
    q, g0, g1, gt = jax.jit(lambda op, c_, s_: (lambda q_, f: (
        q_, f(jnp.float32(0)), f(jnp.float32(1)), f(jnp.float32(0.37))))(
            *op.fresh_normal_matvec(c_, s_)))(Op, c, s)
    Ac = Op.matvec(c)
    assert _rel(q.asarray(), Ac.asarray()) < 2e-6
    assert _rel(g0.asarray(), Op.rmatvec(s).asarray()) < 2e-6
    assert _rel((g0 - g1).asarray(), Op.rmatvec(Ac).asarray()) < 2e-6
    step = s - Ac * np.float32(0.37)
    assert _rel(gt.asarray(), Op.rmatvec(step).asarray()) < 2e-6


def _x0(Op, mesh):
    return DistributedArray(global_shape=Op.shape[1], mesh=mesh,
                            partition=Partition.BROADCAST, dtype=np.float32)


@pytest.mark.parametrize("form", ["one_sweep", "pair"])
def test_one_sweep_cgls_on_mdc_keeps_the_classic_accuracy(
        case, monkeypatch, form):
    """``pmt.cgls(normal=True)`` on an ``MPIMDC`` takes the fresh-residual
    body because the operator offers ``fresh_normal_matvec`` (counted,
    and the product's event says which form ran, in the set-up and in
    the loop's body): its answer stays
    within 1e-6 of the classic schedule's and of the plain reference's,
    where the fused-normal body — the residual by recurrence — drifts
    to ~2e-5 on this operator (2.8e-5–2.9e-5 on the CPU at these
    sizes)."""
    _force(monkeypatch, form)
    mesh = pmt.make_mesh(1)
    Op = _mdc(case["P"], mesh)
    d = _vec(case["d"], mesh)
    monkeypatch.setenv("PYLOPS_MPI_TPU_METRICS", "on")
    monkeypatch.setenv("PYLOPS_MPI_TPU_TRACE", "spans")
    metrics.clear_metrics()
    trace.clear_events()
    basic.clear_fused_cache()
    fresh = pmt.cgls(Op, d, x0=_x0(Op, mesh), niter=NITER, tol=0.0,
                     normal=True)[0].asarray()
    counters = metrics.snapshot()["counters"]
    assert counters["solver.cgls.fresh_residual"] == 1
    assert counters["solver.cgls.one_sweep"] == 1
    ev = [e["args"] for e in trace.get_events()
          if e["name"] == "mdc.normal_select"]
    assert [e["form"] for e in ev] == [form] * 2      # set-up and body
    classic = pmt.cgls(Op, d, x0=_x0(Op, mesh), niter=NITER, tol=0.0,
                       normal=False)[0].asarray()
    assert metrics.snapshot()["counters"]["solver.cgls.fresh_residual"] == 1
    assert _rel(fresh, classic) < 1e-6
    assert _rel(fresh, np.ravel(case["xref"])) < 1e-6
    # the one-sweep body by recurrence, on the same operator
    monkeypatch.setattr(mdc._MDCChain, "has_fresh_normal", False)
    basic.clear_fused_cache()
    drift = pmt.cgls(Op, d, x0=_x0(Op, mesh), niter=NITER, tol=0.0,
                     normal=True)[0].asarray()
    assert _rel(drift, classic) > 1e-5


@pytest.mark.parametrize("why", ["interpret", "planar", "columns", "tile",
                                 "cols", None])
def test_prefers_fused_normal_is_the_compiled_kernel_only(monkeypatch, why):
    """``cgls(normal=None)`` asks the chain: yes only where its product
    is the compiled ``pmt_normal_planes`` for one vector — so every
    program off a TPU, on the ``planar`` engine or for columns stays the
    classic one. Shapes whose plane tile is 2 MiB (the chip's row
    table says tiles of 512 KiB and more pay)."""
    sizes = dict(SIZES, nfmax=2, ns=512, nr=1024, nt=33, nv=16)
    P = np.zeros((2, 2, 512, 1024), np.float32)
    if why != "interpret":
        monkeypatch.setattr(pk, "_interpret", lambda: False)
    if why == "tile":
        monkeypatch.setattr(pk, "_tile_beats_two_sweeps", lambda *a: False)
    if why == "cols":
        sizes["nv"] = 17                  # 34 forward columns: past 32
    Op = _mdc(P, pmt.make_mesh(1), sizes,
              **({"engine": "planar"} if why == "planar" else {}))
    x = np.zeros(Op.shape[1], np.float32)
    v = DistributedArray.to_dist(np.stack([x, x], 1) if why == "columns"
                                 else x, partition=Partition.BROADCAST,
                                 mesh=pmt.make_mesh(1))
    form, got, tile = Op.normal_select(v)
    assert (form, got) == (("pair", why) if why else ("one_sweep", None))
    assert tile == 512
    assert Op.prefers_fused_normal(v) is (why is None)
    assert basic._resolve_normal(Op, v, None) is (why is None)


def test_mdc_normal_select_event(case, monkeypatch):
    """One ``mdc.normal_select`` a traced apply of the chain's product:
    ``form``, ``cols`` (the forward's ``2 nv``), ``tile``, and off a TPU
    ``why`` = ``interpret``."""
    monkeypatch.setenv("PYLOPS_MPI_TPU_TRACE", "spans")
    Op = _mdc(case["P"], pmt.make_mesh(1))
    c = _vec(case["x"], pmt.make_mesh(1))
    s = _vec(case["d"], pmt.make_mesh(1))
    trace.clear_events()
    jax.jit(lambda op, a, b: op.fresh_normal_matvec(a, b)[0])(Op, c, s)
    ev = [e["args"] for e in trace.get_events()
          if e["name"] == "mdc.normal_select"]
    assert len(ev) == 1
    assert (ev[0]["form"], ev[0]["why"], ev[0]["cols"], ev[0]["tile"]) == (
        "pair", "interpret", 2 * SIZES["nv"], SIZES["ns"])


def test_no_other_chain_offers_the_fresh_product():
    """``_ProductLinearOperator`` and ``_ScaledLinearOperator`` keep
    their generic behaviour: a chain of the same factors built by hand,
    a ``conj`` MDC and one on a real kernel offer no fresh product and
    run the classic or the fused-normal body as before."""
    P = _planes(WIDE)
    Op = _mdc(P, None, WIDE, conj=True)
    assert not Op.has_fresh_normal
    assert not basic._fresh(Op, True)
    real = _mdc(np.asarray(P[0]), None, WIDE)        # a real kernel
    assert not real.has_fresh_normal and not real.prefers_fused_normal(
        _vec(np.zeros(real.shape[1]), None))
    F = Op.args[1]
    plain = Op.args[0] * F
    assert type(plain).__name__ == "_ProductLinearOperator"
    assert not plain.has_fresh_normal and not basic._fresh(plain, True)
    assert not basic._fresh(_mdc(P, None, WIDE), False)


def test_the_guarded_fresh_body_is_the_unguarded_one(case):
    """``cgls_guarded(normal=True)`` on an ``MPIMDC`` runs the fresh body
    with its guard carry: the same answer as the unguarded solve, the
    status ``MAXITER`` after the 30 iterations; a NaN injected into
    ``q`` at an iteration is a ``BREAKDOWN`` that keeps the last finite
    iterate."""
    from pylops_mpi_tpu.resilience import faults, status
    mesh = pmt.make_mesh(1)
    Op = _mdc(case["P"], mesh)
    d = _vec(case["d"], mesh)
    basic.clear_fused_cache()
    x = pmt.cgls(Op, d, x0=_x0(Op, mesh), niter=NITER, tol=0.0,
                 normal=True)[0].asarray()
    xg, iiter, *_, code = basic.cgls_guarded(
        Op, d, x0=_x0(Op, mesh), niter=NITER, tol=0.0, normal=True)
    assert (iiter, code) == (NITER, status.MAXITER)
    assert _rel(xg.asarray(), x) < 1e-6
    faults.arm("nan", 5)
    xb, iiter, *_, code = basic.cgls_guarded(
        Op, d, x0=_x0(Op, mesh), niter=NITER, tol=0.0, normal=True)
    assert code == status.BREAKDOWN and iiter < NITER
    assert np.all(np.isfinite(xb.asarray()))


# an even nt, one-sided, the whole half spectrum kept: the Nyquist bin is
# one of the kept bins, and F1ᴴ drops its imaginary part as it does bin 0's
NYQ = dict(SIZES, nt=64, nfmax=33, ns=16, nr=16, nv=2)


def _nyq_mdc(P, mesh=None, sizes=NYQ):
    return pmt.MPIMDC(P, nt=sizes["nt"], nv=sizes["nv"], dt=sizes["dt"],
                      dr=sizes["dr"], twosided=False, mesh=mesh)


def test_a_kept_nyquist_bin_is_dropped_by_the_round_trip():
    """``Wᵀ W`` at an even ``nt`` with the whole half spectrum kept is
    the identity but for zeros at bin 0's AND the Nyquist bin's
    imaginary parts: there ``F1 F1ᴴ`` is not the kernel's mask ``M``,
    which zeroes bin 0's alone."""
    nt = NYQ["nt"]
    nf = nt // 2 + 1
    W = local._truncated_dft_matrix(nt, nt, nf, False,
                                    "float32").astype(np.float64)
    want = np.eye(2 * nf)
    want[nf, nf] = want[2 * nf - 1, 2 * nf - 1] = 0.0
    assert np.abs(W.T @ W - want).max() < 1e-6


@pytest.mark.parametrize("nt", [2, 3])
def test_a_kept_nyquist_bin_takes_the_pair(monkeypatch, nt):
    """Where the core would run the kernel, a chain that keeps the
    Nyquist bin of an even ``nt`` says ``pair`` with the ``why``
    ``nyquist``, and ``cgls(normal=None)`` stays classic; at an odd
    ``nt`` with as many bins the same chain takes the kernel."""
    monkeypatch.setattr(pk, "_interpret", lambda: False)
    P = np.zeros((2, 2, 512, 1024), np.float32)
    Op = pmt.MPIMDC(P, nt=nt, nv=16, twosided=False, mesh=pmt.make_mesh(1))
    v = _vec(np.zeros(Op.shape[1]), pmt.make_mesh(1))
    want = ("pair", "nyquist", 512) if nt == 2 else ("one_sweep", None, 512)
    assert Op.normal_select(v) == want
    assert Op.has_fresh_normal
    assert Op.prefers_fused_normal(v) is (nt == 3)
    assert basic._resolve_normal(Op, v, None) is (nt == 3)


def test_fresh_cgls_with_a_kept_nyquist_bin_is_the_classic(monkeypatch):
    """``pmt.cgls(normal=True)`` on a chain that keeps the Nyquist bin:
    with the core forced to answer ``one_sweep``, the chain's product
    is still the pair (``why`` = ``nyquist``: the kernel's mask would
    leave the Nyquist bin's imaginary part in), and the fresh body's
    answer is the classic one's."""
    _force(monkeypatch, "one_sweep")
    mesh = pmt.make_mesh(1)
    Op = _nyq_mdc(_planes(NYQ), mesh)
    x = jax.random.normal(jax.random.key(4), (Op.shape[1],), jnp.float32)
    d = Op.matvec(_vec(np.asarray(x), mesh))
    monkeypatch.setenv("PYLOPS_MPI_TPU_METRICS", "on")
    monkeypatch.setenv("PYLOPS_MPI_TPU_TRACE", "spans")
    metrics.clear_metrics()
    trace.clear_events()
    basic.clear_fused_cache()
    fresh = pmt.cgls(Op, d, x0=_x0(Op, mesh), niter=NITER, tol=0.0,
                     normal=True)[0].asarray()
    assert metrics.snapshot()["counters"]["solver.cgls.fresh_residual"] == 1
    ev = [e["args"] for e in trace.get_events()
          if e["name"] == "mdc.normal_select"]
    assert [(e["form"], e["why"]) for e in ev] == [("pair", "nyquist")] * 2
    classic = pmt.cgls(Op, d, x0=_x0(Op, mesh), niter=NITER, tol=0.0,
                       normal=False)[0].asarray()
    assert _rel(fresh, classic) < 1e-6


def test_the_ca_engine_runs_mdc_classic(case, monkeypatch):
    """Under ``PYLOPS_MPI_TPU_CA`` the engine has no fresh-residual body:
    a one-sweep solve of an ``MPIMDC`` runs the pipelined engine's
    classic body and is counted as classic — no ``one_sweep``, no
    ``fresh_residual`` — with the answer of the pipelined classic
    solve."""
    mesh = pmt.make_mesh(1)
    Op = _mdc(case["P"], mesh)
    d = _vec(case["d"], mesh)
    monkeypatch.setenv("PYLOPS_MPI_TPU_CA", "pipelined")
    monkeypatch.setenv("PYLOPS_MPI_TPU_METRICS", "on")
    metrics.clear_metrics()
    basic.clear_fused_cache()
    one = pmt.cgls(Op, d, x0=_x0(Op, mesh), niter=NITER, tol=0.0,
                   normal=True)[0].asarray()
    counters = metrics.snapshot()["counters"]
    assert counters["solver.cgls.solves"] == 1
    assert "solver.cgls.one_sweep" not in counters
    assert "solver.cgls.fresh_residual" not in counters
    two = pmt.cgls(Op, d, x0=_x0(Op, mesh), niter=NITER, tol=0.0,
                   normal=False)[0].asarray()
    assert np.array_equal(one, two)
