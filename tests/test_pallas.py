"""Pallas stencil kernels — interpret-mode validation against the jnp
stencils (native lowering exercises the same code on TPU)."""

import re

import numpy as np
import pytest
import jax.numpy as jnp

from pylops_mpi_tpu.ops import pallas_kernels as pk


@pytest.mark.parametrize("shape,axis", [((32, 8), 0), ((16, 128), 0),
                                        ((8, 32), 1)])
def test_first_derivative_kernel(rng, shape, axis):
    x = jnp.asarray(rng.standard_normal(shape))
    got = np.asarray(pk.first_derivative_centered(x, axis=axis, sampling=0.5))
    v = np.moveaxis(np.asarray(x), axis, 0)
    expected = np.zeros_like(v)
    expected[1:-1] = (v[2:] - v[:-2]) / 1.0
    expected = np.moveaxis(expected, 0, axis)
    np.testing.assert_allclose(got, expected, rtol=1e-6, atol=1e-12)


def test_second_derivative_kernel(rng):
    x = jnp.asarray(rng.standard_normal((32, 16)))
    got = np.asarray(pk.second_derivative(x, axis=0, sampling=2.0))
    v = np.asarray(x)
    expected = np.zeros_like(v)
    expected[1:-1] = (v[2:] - 2 * v[1:-1] + v[:-2]) / 4.0
    np.testing.assert_allclose(got, expected, rtol=1e-6, atol=1e-12)


# ---------------------------------------------------- fused normal matvec
def _normal_oracle(A, X):
    """``(AᴴA X, A X)`` per block by NumPy einsum at double width;
    ``X (nblk, K, n)``: K columns a block, row-wise — or ``(nblk, n)``,
    a vector a block, answered in that shape."""
    wide = np.result_type(np.asarray(A).dtype, np.float64)
    A, X = np.asarray(A, dtype=wide), np.asarray(X, dtype=wide)
    Xk = X if X.ndim == 3 else X[:, None, :]
    q = np.einsum("bmn,bkn->bkm", A, Xk)
    u = np.einsum("bmn,bkm->bkn", A.conj(), q)
    return (u, q) if X.ndim == 3 else (u[:, 0], q[:, 0])


def _rel(got, want):
    return np.linalg.norm(np.asarray(got) - want) / np.linalg.norm(want)


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
@pytest.mark.parametrize("shape", [(2, 24, 16), (1, 64, 64), (3, 40, 56),
                                   (2, 17, 5)])
def test_batched_normal_matvec_oracle(rng, dtype, shape):
    """One sweep against the einsum oracle at K = 1 (a vector a block):
    one 64-row tile a block, several 8-row tiles (24, 40), and a ragged
    block whose only legal tile is the whole block (17 x 5)."""
    nblk, m, n = shape
    A = jnp.asarray(rng.standard_normal(shape).astype(dtype))
    X = jnp.asarray(rng.standard_normal((nblk, 1, n)).astype(dtype))
    assert pk.normal_matvec_supported(A)
    u, q = pk.batched_normal_matvec(A, X)
    assert u.shape == (nblk, 1, n) and q.shape == (nblk, 1, m)
    assert u.dtype == q.dtype == dtype
    wu, wq = _normal_oracle(A, X)
    tol = 1e-5 if dtype == np.float32 else 1e-12
    assert _rel(q, wq) < tol and _rel(u, wu) < tol


@pytest.mark.parametrize("K", [1, 2, 3, 8, 16])
@pytest.mark.parametrize("shape,tile", [((2, 384, 40), 128),
                                        ((2, 48, 40), 16)],
                         ids=["lane_dense_q", "sub_128_row_tile"])
@pytest.mark.parametrize("storage,name", [
    (np.float32, "pmt_normal"), (jnp.bfloat16, "pmt_normal_stream")],
    ids=["f32", "bf16_stream"])
def test_batched_normal_matvec_columns_oracle(rng, storage, name, shape,
                                              tile, K):
    """K columns a block from one read of A: ``X (nblk, K, n)`` in,
    ``U (nblk, K, n)`` and ``Q (nblk, K, m)`` out, equal to the einsum
    pair column by column — f32 blocks, and bf16 storage under the
    streaming name (the f32 columns are never narrowed). Three row
    tiles a block in both shapes: 128 rows, where the kernel writes Q's
    ``(1, K, tm)`` blocks in place, and 16, where Q leaves as
    ``(nblk, m/tm, K, tm)`` and is put in order outside."""
    nblk, m, n = shape
    A = jnp.asarray(rng.standard_normal(shape).astype(np.float32)
                    ).astype(storage)
    X = jnp.asarray(rng.standard_normal((nblk, K, n)).astype(np.float32))
    assert pk._tile_args(A) == (tile, storage != np.float32)
    u, q = pk.batched_normal_matvec(A, X)
    assert u.shape == (nblk, K, n) and q.shape == (nblk, K, m)
    assert u.dtype == q.dtype == np.float32
    wu, wq = _normal_oracle(A.astype(jnp.float32), X)
    assert _rel(q, wq) < 1e-5 and _rel(u, wu) < 1e-5
    import jax
    text = jax.jit(pk.batched_normal_matvec).lower(A, X).as_text(
        debug_info=True)
    assert set(re.findall(r"pmt_normal\w*", text)) == {name}


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_normal_matvec_without_a_legal_tile_takes_two_sweeps(
        rng, monkeypatch, dtype):
    """Where no Mosaic-legal row tile fits the VMEM budget the operator
    has no one-sweep kernel, says so, and ``normal_matvec`` still meets
    the oracle through matvec + rmatvec."""
    from pylops_mpi_tpu import MPIBlockDiag, DistributedArray
    from pylops_mpi_tpu.ops.local import MatrixMult
    import jax
    P = len(jax.devices())
    blocks = rng.standard_normal((P, 17, 5)).astype(dtype)
    Op = MPIBlockDiag([MatrixMult(b, dtype=dtype) for b in blocks])
    x = DistributedArray.to_dist(rng.standard_normal(P * 5).astype(dtype))
    assert Op.has_fused_normal and Op._normal_kernel_for(x) is not None
    # 17 rows divide by no sublane multiple; shrink the budget under the
    # whole block and nothing is left
    monkeypatch.setattr(pk, "_VMEM_TILE_BYTES", 64)
    assert not pk.normal_matvec_supported(Op._batched)
    assert not Op.has_fused_normal and Op._normal_kernel_for(x) is None
    u, q = Op.normal_matvec(x)
    wu, wq = _normal_oracle(blocks, x.asarray().reshape(P, 5))
    tol = 1e-5 if dtype == np.float32 else 1e-12
    assert _rel(q.asarray().reshape(P, 17), wq) < tol
    assert _rel(u.asarray().reshape(P, 5), wu) < tol


@pytest.mark.parametrize("n,K,fits", [(4096, 256, True), (4096, 257, False),
                                      (16384, 64, True), (16384, 65, False),
                                      (1024, 1024, True), (1024, 1025, False)])
def test_normal_matvec_supported_bounds_the_column_block(n, K, fits):
    """*Can* has a width: the K columns stay in VMEM beside the tile,
    so a column block larger than the tile budget has no kernel (the
    bound is where the compile for a described v5e starts to fail)."""
    import jax
    A = jax.ShapeDtypeStruct((2, 1024, n), jnp.float32)
    assert pk.normal_matvec_supported(A) and pk.normal_matvec_supported(A, 1)
    assert pk.normal_matvec_supported(A, K) is fits


def test_normal_matvec_wider_than_vmem_takes_two_sweeps(rng, monkeypatch):
    """A block input wider than the kernel can hold falls back to
    matvec + rmatvec, like any input without a kernel; narrower inputs
    of the same operator keep the kernel."""
    from pylops_mpi_tpu import MPIBlockDiag, DistributedArray
    from pylops_mpi_tpu.ops.local import MatrixMult
    import jax
    P = len(jax.devices())
    blocks = rng.standard_normal((P, 24, 16)).astype(np.float32)
    Op = MPIBlockDiag([MatrixMult(b, dtype=np.float32) for b in blocks])
    monkeypatch.setattr(pk, "_VMEM_TILE_BYTES", 2048)   # 32 columns of 16
    xs = {}
    for K in (32, 33):
        xs[K] = DistributedArray(global_shape=(P * 16, K), dtype=np.float32)
        xs[K][:] = rng.standard_normal((P * 16, K)).astype(np.float32)
    assert Op.has_fused_normal
    assert Op._normal_kernel_for(xs[32]) is pk.batched_normal_matvec
    assert Op._normal_kernel_for(xs[33]) is None
    with pytest.raises(ValueError, match="33 columns"):
        pk.batched_normal_matvec(Op._batched, jnp.zeros((P, 33, 16)))
    u, q = Op.normal_matvec(xs[33])
    q2 = Op.matvec(xs[33])
    np.testing.assert_array_equal(q.asarray(), q2.asarray())
    np.testing.assert_array_equal(u.asarray(), Op.rmatvec(q2).asarray())


@pytest.mark.parametrize("storage,name", [(None, "pmt_normal"),
                                          (jnp.bfloat16, "pmt_normal_stream")])
def test_blockdiag_normal_matvec_lowers_to_the_pallas_kernel(
        rng, ndev, storage, name):
    """On every backend the batched BlockDiag normal product is the
    Pallas kernel the chip runs (interpreted here): the lowered program
    names it, holds no native custom call, and agrees with the generic
    two-sweep pair (the solver-facing contract of cgls(normal=True))."""
    import jax
    from pylops_mpi_tpu import MPIBlockDiag, DistributedArray
    from pylops_mpi_tpu.ops.local import MatrixMult
    # P blocks: the batched layout (and thus the kernel) needs
    # nblocks % P == 0 at ANY test mesh size
    blocks = [rng.standard_normal((32, 24)).astype(np.float32)
              for _ in range(ndev)]
    Op = MPIBlockDiag([MatrixMult(b, dtype=np.float32) for b in blocks],
                      compute_dtype=storage)
    assert Op.has_fused_normal
    x = DistributedArray.to_dist(
        rng.standard_normal(Op.shape[1]).astype(np.float32))
    assert Op._normal_kernel_for(x) is pk.batched_normal_matvec

    def both(op, v):
        return tuple(o.array for o in op.normal_matvec(v))

    text = jax.jit(both).lower(Op, x).as_text(debug_info=True)
    assert set(re.findall(r"pmt_normal\w*", text)) == {name}
    assert "pylops_mpi_tpu_fused_normal" not in text
    assert "custom_call" not in text       # interpreted: plain HLO
    u, q = Op.normal_matvec(x)
    q2 = Op.matvec(x)
    u2 = Op.rmatvec(q2)
    tol = 2e-5 if storage is None else 2e-2
    assert _rel(q.asarray(), q2.asarray()) < tol
    assert _rel(u.asarray(), u2.asarray()) < 10 * tol


def test_blockdiag_normal_matvec_matches_two_sweeps(rng):
    from pylops_mpi_tpu import MPIBlockDiag, DistributedArray
    from pylops_mpi_tpu.ops.local import MatrixMult
    import jax
    P = len(jax.devices())  # batched path needs nblocks %% P == 0
    blocks = [rng.standard_normal((12, 8)) for _ in range(P)]
    Op = MPIBlockDiag([MatrixMult(b, dtype=np.float64) for b in blocks])
    assert Op.has_fused_normal
    x = DistributedArray.to_dist(rng.standard_normal(P * 8))
    u, q = Op.normal_matvec(x)
    q_ref = Op.matvec(x)
    u_ref = Op.rmatvec(q_ref)
    np.testing.assert_allclose(q.asarray(), q_ref.asarray(), rtol=1e-12)
    np.testing.assert_allclose(u.asarray(), u_ref.asarray(), rtol=1e-12)


def test_normal_matvec_generic_fallback(rng):
    # heterogeneous blocks -> no batched path; generic two-sweep pair
    from pylops_mpi_tpu import MPIBlockDiag, DistributedArray
    from pylops_mpi_tpu.ops.local import MatrixMult
    blocks = [rng.standard_normal((6 + i % 2, 5)) for i in range(8)]
    Op = MPIBlockDiag([MatrixMult(b, dtype=np.float64) for b in blocks])
    assert not Op.has_fused_normal
    x = DistributedArray.to_dist(rng.standard_normal(8 * 5))
    u, q = Op.normal_matvec(x)
    np.testing.assert_allclose(u.asarray(),
                               Op.rmatvec(Op.matvec(x)).asarray(), rtol=1e-12)


def _cgls_case_f64(rng, ndev):
    blocks = [rng.standard_normal((16, 16)) + 16 * np.eye(16)
              for _ in range(8)]
    return blocks, np.float64, 30, (0.0, 0.5), dict(rtol=1e-8, atol=1e-12)


def _cgls_case_f32_block_a_device(rng, ndev):
    # one block a device, f32: the flagship's layout at a tiny size
    blocks = []
    for _ in range(ndev):
        b = (rng.standard_normal((32, 32)) / np.sqrt(32)).astype(np.float32)
        np.fill_diagonal(b, b.diagonal() + 4.0)
        blocks.append(b)
    return blocks, np.float32, 50, (0.0,), dict(rtol=1e-3, atol=1e-4)


@pytest.mark.parametrize("case", [_cgls_case_f64,
                                  _cgls_case_f32_block_a_device],
                         ids=["f64_8_blocks", "f32_block_a_device"])
def test_cgls_normal_mode_matches_standard(rng, ndev, case):
    from pylops_mpi_tpu import MPIBlockDiag, DistributedArray, cgls
    from pylops_mpi_tpu.ops.local import MatrixMult
    blocks, dtype, niter, damps, tol = case(rng, ndev)
    Op = MPIBlockDiag([MatrixMult(b, dtype=dtype) for b in blocks])
    assert Op.has_fused_normal
    nx = Op.shape[1]
    xt = rng.standard_normal(nx).astype(dtype)
    y = Op.matvec(DistributedArray.to_dist(xt))
    # nonzero x0 exercises the damp-quirk initialization of the
    # gradient recurrence (r must start from the damp² form)
    x0s = [y.zeros_like(),
           DistributedArray.to_dist(rng.standard_normal(nx).astype(dtype))]
    for x0 in x0s:
        for damp in damps:
            xs = cgls(Op, y, x0=x0.copy(), niter=niter, damp=damp, tol=0,
                      normal=False)[0]
            xn = cgls(Op, y, x0=x0.copy(), niter=niter, damp=damp, tol=0,
                      normal=True)[0]
            np.testing.assert_allclose(xn.asarray(), xs.asarray(), **tol)
            if damp == 0.0:
                assert _rel(xn.asarray(), xt) < 1e-4


def test_cgls_normal_requires_fused(rng):
    from pylops_mpi_tpu import MPIBlockDiag, DistributedArray, cgls
    from pylops_mpi_tpu.ops.local import MatrixMult
    Op = MPIBlockDiag([MatrixMult(rng.standard_normal((8, 8)))
                       for _ in range(8)])
    y = DistributedArray.to_dist(rng.standard_normal(64))
    with pytest.raises(ValueError, match="normal=True requires"):
        cgls(Op, y, niter=2, normal=True, fused=False)


def test_normal_matvec_complex_vector_falls_back(rng):
    # real blocks, complex vector: the real kernel would truncate it
    from pylops_mpi_tpu import MPIBlockDiag, DistributedArray
    from pylops_mpi_tpu.ops.local import MatrixMult
    Op = MPIBlockDiag([MatrixMult(rng.standard_normal((8, 8)),
                                  dtype=np.float64) for _ in range(8)])
    xc = DistributedArray.to_dist(
        rng.standard_normal(64) + 1j * rng.standard_normal(64))
    assert Op.has_fused_normal and Op._normal_kernel_for(xc) is None
    u, q = Op.normal_matvec(xc)
    q_ref = Op.matvec(xc)
    np.testing.assert_allclose(q.asarray(), q_ref.asarray(), rtol=1e-12)
    np.testing.assert_allclose(u.asarray(), Op.rmatvec(q_ref).asarray(),
                               rtol=1e-12)


@pytest.mark.parametrize("dtype", [np.complex64, np.complex128])
def test_normal_matvec_complex_falls_back(rng, ndev, dtype):
    """Complex blocks have no one-sweep kernel on any backend:
    ``has_fused_normal`` says no, ``normal_matvec`` is the generic pair
    (adjoint side conjugated), and ``cgls(normal=True)`` — the
    one-sweep recurrence over two sweeps — lands where the classic
    schedule does."""
    from pylops_mpi_tpu import MPIBlockDiag, DistributedArray, cgls
    from pylops_mpi_tpu.ops.local import MatrixMult
    nb = 16
    blocks = []
    for _ in range(ndev):
        b = (rng.standard_normal((nb, nb))
             + 1j * rng.standard_normal((nb, nb))) / np.sqrt(nb)
        blocks.append((b + 4.0 * np.eye(nb)).astype(dtype))
    Op = MPIBlockDiag([MatrixMult(b) for b in blocks])
    assert Op.has_fused_normal is False
    xt = (rng.standard_normal(ndev * nb)
          + 1j * rng.standard_normal(ndev * nb)).astype(dtype)
    x = DistributedArray.to_dist(xt)
    assert Op._normal_kernel_for(x) is None
    u, q = Op.normal_matvec(x)
    wu, wq = _normal_oracle(np.stack(blocks), xt.reshape(ndev, nb))
    tol = 1e-5 if dtype == np.complex64 else 1e-12
    assert _rel(q.asarray().reshape(ndev, nb), wq) < tol
    assert _rel(u.asarray().reshape(ndev, nb), wu) < tol
    y = Op.matvec(x)
    xa = cgls(Op, y, niter=60, tol=0.0, normal=True)[0].asarray()
    xb = cgls(Op, y, niter=60, tol=0.0, normal=False)[0].asarray()
    assert _rel(xa, xt) < 100 * tol
    assert _rel(xa, xb) < 100 * tol


def test_blockdiag_compute_dtype_bf16(rng):
    from pylops_mpi_tpu import MPIBlockDiag, DistributedArray
    from pylops_mpi_tpu.ops.local import MatrixMult
    blocks = [rng.standard_normal((16, 16)).astype(np.float32)
              for _ in range(8)]
    Op32 = MPIBlockDiag([MatrixMult(b) for b in blocks])
    Opbf = MPIBlockDiag([MatrixMult(b) for b in blocks],
                        compute_dtype=jnp.bfloat16)
    x = DistributedArray.to_dist(
        rng.standard_normal(8 * 16).astype(np.float32))
    y32 = Op32.matvec(x).asarray()
    ybf = Opbf.matvec(x).asarray()
    assert ybf.dtype == np.float32  # vectors stay f32
    rel = np.linalg.norm(ybf - y32) / np.linalg.norm(y32)
    assert rel < 2e-2  # bf16 storage error, not garbage
    u, q = Opbf.normal_matvec(x)
    uref = Opbf.rmatvec(Opbf.matvec(x))
    rel_u = np.linalg.norm(u.asarray() - uref.asarray()) \
        / np.linalg.norm(uref.asarray())
    assert rel_u < 2e-2


@pytest.mark.parametrize("taps,w", [
    (((1, 2.0), (0, -2.0)), 1),                      # forward-like
    (((-1, -0.5), (1, 0.5)), 1),                     # centered-3
    (((-2, 1 / 12), (-1, -8 / 12), (1, 8 / 12), (2, -1 / 12)), 2),  # c5
    (((0, 1.0), (1, -2.0), (2, 1.0)), 2),            # SD forward
])
def test_stencil_taps_kernel(rng, taps, w):
    """The generic one-VMEM-pass tap kernel (interpret mode on CPU)
    matches the plain shifted-slice formulation for every tap pattern
    the explicit distributed stencil path emits."""
    from pylops_mpi_tpu.ops.pallas_kernels import stencil_taps
    slab = rng.standard_normal((40 + 2 * w, 12)).astype(np.float32)
    got = np.asarray(stencil_taps(jnp.asarray(slab), taps, w))
    want = sum(c * slab[w + d: w + d + 40] for d, c in taps)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


def test_stencil_taps_out_pad_and_short_axis(rng):
    """out_pad writes the zero edge rows inside the kernel pass, and
    the centered-3 wrappers handle axis lengths < 3 (all edge rows)."""
    from pylops_mpi_tpu.ops.pallas_kernels import (
        stencil_taps, first_derivative_centered, second_derivative)
    slab = rng.standard_normal((12, 5)).astype(np.float32)
    taps = ((-1, -0.5), (1, 0.5))
    got = np.asarray(stencil_taps(jnp.asarray(slab), taps, 1,
                                  out_pad=(1, 1)))
    want = np.zeros((12, 5), np.float32)
    want[1:-1] = 0.5 * (slab[2:] - slab[:-2])
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    for n in (1, 2):
        x = rng.standard_normal((n, 4)).astype(np.float32)
        np.testing.assert_array_equal(
            np.asarray(first_derivative_centered(jnp.asarray(x))), 0.0)
        np.testing.assert_array_equal(
            np.asarray(second_derivative(jnp.asarray(x))), 0.0)


@pytest.mark.parametrize("cols", [384, 1024, 300])  # 300: ragged block
def test_stencil_taps_column_tiling(rng, cols, monkeypatch):
    """Wide slabs tile over the lane axis (no stencil dependency along
    columns): a genuinely MULTI-BLOCK grid (budget shrunk so the tile
    is 128 columns, incl. a ragged masked last block) must equal the
    plain slice formulation, with and without out_pad."""
    from pylops_mpi_tpu.ops import pallas_kernels as pk
    w = 2
    # shrink the budget so nrows=36 f32 allows only 128-col tiles:
    # grid = ceil(cols/128) = 3, 8, 3 (last one ragged)
    monkeypatch.setattr(pk, "_STENCIL_TILE_BYTES", 36 * 4 * 130)
    assert pk._stencil_col_tile(36, cols, 4) == 128
    taps = ((-2, 1 / 12), (-1, -8 / 12), (1, 8 / 12), (2, -1 / 12))
    slab = rng.standard_normal((36, cols)).astype(np.float32)
    want = sum(c * slab[w + d: w + d + 32] for d, c in taps)
    got = np.asarray(pk.stencil_taps(jnp.asarray(slab), taps, w))
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    gotp = np.asarray(pk.stencil_taps(jnp.asarray(slab), taps, w,
                                      out_pad=(2, 2)))
    np.testing.assert_allclose(gotp[2:-2], want, rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(gotp[:2], 0.0)
    np.testing.assert_array_equal(gotp[-2:], 0.0)


def test_stencil_col_tile_budgeting():
    """Tile selection: whole slab when it fits, 128-aligned tile when
    not (ceil-division grid, ragged last block allowed), 0 (XLA
    fallback) when even one strip cannot fit."""
    from pylops_mpi_tpu.ops.pallas_kernels import (_stencil_col_tile,
                                                   _STENCIL_TILE_BYTES)
    assert _stencil_col_tile(100, 256, 4) == 256  # fits whole
    nrows = _STENCIL_TILE_BYTES // 4 // 128  # 128 cols exactly fill
    assert _stencil_col_tile(nrows, 1024, 4) == 128
    assert _stencil_col_tile(nrows, 1000, 4) == 128  # non-divisor OK
    assert _stencil_col_tile(10 * _STENCIL_TILE_BYTES, 1024, 4) == 0


# ------------------------------------------- pmt_laplacian (MPILaplacian)
def _laplacian_pair(dims, axes, dtype, shards, **kw):
    """``MPILaplacian`` on ``shards`` devices, jitted (an eager apply
    re-traces the interpreted kernel every call), and the slice form it
    must equal — the weighted sum of ``ops/local.py::SecondDerivative``
    — with unequal weights and samplings."""
    import jax
    import pylops_mpi_tpu as pmt
    from pylops_mpi_tpu.ops.local import SecondDerivative
    w = tuple(1.0 + 0.5 * i for i in range(len(axes)))
    s = tuple(1.0 + 0.25 * i for i in range(len(axes)))
    mesh = pmt.make_mesh(shards)
    Lop = pmt.MPILaplacian(dims, axes=axes, weights=w, sampling=s, mesh=mesh,
                           dtype=dtype, **kw)
    ops = [SecondDerivative(dims, axis=ax, sampling=si, dtype=dtype, **kw)
           for ax, si in zip(axes, s)]

    def slices(x, adjoint=False):
        x = jnp.asarray(x)
        return np.asarray(sum(
            wi * (op._rmatvec(x) if adjoint else op._matvec(x))
            for wi, op in zip(w, ops)))
    return (Lop, jax.jit(lambda v: Lop.matvec(v)),
            jax.jit(lambda v: Lop.rmatvec(v)), slices, mesh)


def _path_events(fn):
    """What ``laplacian.path_select`` recorded while ``fn`` ran."""
    from pylops_mpi_tpu.diagnostics import trace
    trace.clear_events()
    out = fn()
    ev = [e["args"] for e in trace.get_events()
          if e["name"] == "laplacian.path_select"]
    trace.clear_events()
    return out, ev


LAP_CASES = [((8, 6, 10), (0,)), ((8, 6, 10), (1, 2)),
             ((8, 6, 10), (0, 1, 2)), ((16, 12), (0,)), ((16, 12), (1,)),
             ((16, 12), (0, 1))]


@pytest.mark.parametrize("shards", [1, 2, 8])
@pytest.mark.parametrize("dtype", [np.float32, np.float64],
                         ids=["f32", "f64"])
@pytest.mark.parametrize("dims,axes", LAP_CASES)
def test_pmt_laplacian_equals_the_slice_form(rng, monkeypatch, ndev, dims,
                                             axes, dtype, shards):
    """Forward and adjoint through the kernel (interpreted here) equal
    the sum of ``SecondDerivative`` slices to rounding: 2-D and 3-D,
    any subset of axes, unequal weights and samplings, one plane a
    shard included (8 planes over 8 devices); and the rule says, once a
    traced apply, that it took the kernel."""
    from pylops_mpi_tpu import DistributedArray
    if shards > ndev:
        pytest.skip(f"needs {shards} devices")
    monkeypatch.setenv("PYLOPS_MPI_TPU_TRACE", "spans")
    Lop, mv, rmv, slices, mesh = _laplacian_pair(dims, axes, dtype, shards)
    x = rng.standard_normal(int(np.prod(dims))).astype(dtype)
    xd = DistributedArray.to_dist(x, mesh=mesh)
    (y, ya), ev = _path_events(
        lambda: (mv(xd).asarray(), rmv(xd).asarray()))
    assert [(e["form"], e["adjoint"]) for e in ev] == [
        ("pmt_laplacian", 0), ("pmt_laplacian", 1)]
    assert all(e["shards"] == shards and "why" not in e
               and tuple(e["dims"]) == dims and tuple(e["axes"]) == axes
               for e in ev)
    tol = 2e-6 if dtype == np.float32 else 1e-13
    want, wanta = slices(x), slices(x, adjoint=True)
    assert y.dtype == ya.dtype == dtype
    np.testing.assert_allclose(y, want, rtol=0, atol=tol * np.abs(want).max())
    np.testing.assert_allclose(ya, wanta, rtol=0,
                               atol=tol * np.abs(wanta).max())


@pytest.mark.parametrize("shards", [1, 2, 8])
@pytest.mark.parametrize("dims,axes", [((8, 6, 10), (0, 1, 2)),
                                       ((16, 12), (0, 1)),
                                       ((8, 6, 10), (1, 2))])
def test_pmt_laplacian_dot_test(rng, ndev, dims, axes, shards):
    """``<L x, y> = <x, L^H y>`` through the kernel's two sides."""
    from pylops_mpi_tpu import DistributedArray
    if shards > ndev:
        pytest.skip(f"needs {shards} devices")
    Lop, mv, rmv, _, mesh = _laplacian_pair(dims, axes, np.float64, shards)
    n = int(np.prod(dims))
    u, v = rng.standard_normal(n), rng.standard_normal(n)
    ud = DistributedArray.to_dist(u, mesh=mesh)
    vd = DistributedArray.to_dist(v, mesh=mesh)
    assert Lop._kernel_refusal(ud) is None
    lhs, rhs = np.vdot(mv(ud).asarray(), v), np.vdot(u, rmv(vd).asarray())
    assert abs(lhs - rhs) <= 1e-12 * max(abs(lhs), abs(rhs))


@pytest.mark.parametrize("shards", [1, 2])
@pytest.mark.parametrize("dims,axes", [((4, 3, 5), (0, 1, 2)),
                                       ((6, 4), (0, 1))])
def test_pmt_laplacian_dense_on_a_tiny_cube(ndev, dims, axes, shards):
    """Column by column against the stencil's matrix written out in
    NumPy (Kronecker products of the 1-D second difference with its two
    boundary rows zero): every boundary plane of every axis, where a
    wrong mask hides; the adjoint is its transpose."""
    from pylops_mpi_tpu import DistributedArray
    if shards > ndev:
        pytest.skip(f"needs {shards} devices")
    Lop, mv, rmv, _, mesh = _laplacian_pair(dims, axes, np.float64, shards)

    def second(n, s):
        D = np.zeros((n, n))
        for i in range(1, n - 1):
            D[i, i - 1:i + 2] = np.array([1.0, -2.0, 1.0]) / s ** 2
        return D
    want = 0
    for ax, w, s in zip(Lop.axes, Lop.weights, Lop.sampling):
        term = np.ones((1, 1))
        for a, n in enumerate(dims):
            term = np.kron(term, second(n, s) if a == ax else np.eye(n))
        want = want + w * term
    eye = np.eye(int(np.prod(dims)))
    got = np.stack([mv(DistributedArray.to_dist(e, mesh=mesh)).asarray()
                    for e in eye], axis=1)
    gota = np.stack([rmv(DistributedArray.to_dist(e, mesh=mesh)).asarray()
                     for e in eye], axis=1)
    np.testing.assert_allclose(got, want, rtol=0, atol=1e-13)
    np.testing.assert_allclose(gota, want.T, rtol=0, atol=1e-13)


@pytest.mark.parametrize("why,dims,kw,dtype", [
    ("kind", (16, 12), dict(kind="forward"), np.float64),
    ("kind", (16, 12), dict(kind="backward"), np.float64),
    ("edge", (16, 12), dict(edge=True), np.float64),
    ("dtype", (16, 12), {}, np.complex128),
    ("ragged", (13, 12), {}, np.float64),
    ("short", (16, 2), {}, np.float64),
    ("short", (16,), {}, np.float64)])
def test_laplacian_fallbacks_keep_the_slice_form(rng, monkeypatch, ndev, why,
                                                 dims, kw, dtype):
    """What the kernel does not take gives the answers it gave, and
    ``laplacian.path_select`` says ``slices`` with the one-word
    reason."""
    from pylops_mpi_tpu import DistributedArray
    if why == "ragged" and ndev < 2:
        pytest.skip("one device has no ragged split")
    monkeypatch.setenv("PYLOPS_MPI_TPU_TRACE", "spans")
    axes = (0, 1) if len(dims) > 1 else (0,)
    Lop, mv, rmv, slices, mesh = _laplacian_pair(dims, axes, dtype,
                                                 min(2, ndev), **kw)
    n = int(np.prod(dims))
    x = rng.standard_normal(n).astype(dtype)
    if dtype == np.complex128:
        x = x + 1j * rng.standard_normal(n)
    xd = DistributedArray.to_dist(x, mesh=mesh)
    (y, ya), ev = _path_events(
        lambda: (mv(xd).asarray(), rmv(xd).asarray()))
    assert [(e["form"], e["why"], e["adjoint"]) for e in ev] == [
        ("slices", why, 0), ("slices", why, 1)]
    np.testing.assert_allclose(y, slices(x), rtol=1e-12, atol=1e-12)
    np.testing.assert_allclose(ya, slices(x, adjoint=True), rtol=1e-12,
                               atol=1e-12)


@pytest.mark.parametrize("why,dims", [("align", (8, 12, 128)),
                                      ("align", (8, 16, 100)),
                                      (None, (8, 16, 128))])
def test_laplacian_rule_where_the_kernel_is_compiled(monkeypatch, why, dims):
    """As on a TPU: Mosaic takes f32 planes of whole (8, 128) tiles of
    at most 4 MiB; anything else keeps the slices (``align``)."""
    from pylops_mpi_tpu import DistributedArray
    import pylops_mpi_tpu as pmt
    monkeypatch.setattr(pk, "_interpret", lambda: False)
    mesh = pmt.make_mesh(1)
    Lop = pmt.MPILaplacian(dims, axes=(0, 1, 2), weights=(1, 1, 1),
                           sampling=(1, 1, 1), mesh=mesh, dtype=np.float32)
    for dtype, said in ((np.float32, why), (np.float64, "align")):
        x = DistributedArray(global_shape=int(np.prod(dims)), mesh=mesh,
                             dtype=dtype)
        assert Lop._kernel_refusal(x) == said
    assert not pk.laplacian_legal((2, 2048, 1024), np.float32)   # 8 MiB
