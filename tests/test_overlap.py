"""Pipelined-collectives tests (round 8, ``PYLOPS_MPI_TPU_OVERLAP``).

Three families of pins, per the overlap contract:

- **oracles**: every overlapped schedule (ring SUMMA, ring stack
  reduction, chunked pencil transpose, interior/boundary-split halo
  stencil) matches the dense NumPy oracle and its own bulk (``off``)
  result within dtype tolerance;
- **bit-identity**: ``overlap="off"`` produces EXACTLY the default
  (pre-round-8) results on the CPU sim, and the bulk programs' op
  counts are unchanged;
- **HLO schedule pins** (``utils/hlo.py``): the ring compiles to P-1
  collective-permutes forming a dependency chain, interleaved with P
  dots (``assert_ring_schedule``); the chunked transpose compiles to K
  all-to-alls per transpose (``count_collectives``) — enforced in CI,
  not prose.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import pylops_mpi_tpu as pmt
from pylops_mpi_tpu import DistributedArray, MPIMatrixMult, MPIFFTND
from jax import shard_map
from jax.sharding import PartitionSpec as PSpec
from pylops_mpi_tpu.parallel import collectives as C
from pylops_mpi_tpu.parallel.mesh import make_mesh
from pylops_mpi_tpu.utils.hlo import (assert_ring_schedule,
                                      count_collectives)
from pylops_mpi_tpu.utils import deps

P = len(jax.devices())


@pytest.fixture(scope="module")
def mesh():
    return make_mesh()


# ------------------------------------------------------------ primitives
def test_ring_pass_visits_every_block_once(mesh, rng):
    """Summing the resident blocks over the ring reproduces the
    all-reduce; owner indices label blocks correctly at every step."""
    name = mesh.axis_names[0]
    n = int(mesh.devices.size)
    x = jnp.asarray(rng.standard_normal((n, 3)))

    def f(xs):
        def kernel(xb):
            def body(acc, res, owner, s):
                # weight by the owner index so mislabeled blocks show
                part = res * (owner + 1)
                return part if acc is None else acc + part
            return C.ring_pass(xb, name, n, body)
        return shard_map(kernel, mesh=mesh, in_specs=PSpec(name),
                         out_specs=PSpec(name), check_vma=False)(xs)

    got = np.asarray(f(x)).reshape(n, 3)
    xv = np.asarray(x)
    want = sum((j + 1) * xv[j] for j in range(n))
    for i in range(n):
        np.testing.assert_allclose(got[i], want, rtol=1e-12)


def test_ring_halo_ghosts_matches_halo_slab(mesh, rng):
    """The unstitched ghost slabs are exactly what halo_slab would
    concatenate (zeros at the domain edges)."""
    name = mesh.axis_names[0]
    n = int(mesh.devices.size)
    x = jnp.asarray(rng.standard_normal((2 * n, 3)))

    def f(xs):
        def kernel(xb):
            gf, gb = C.ring_halo_ghosts(xb, name, n, 1, 1,
                                        jnp.int32(xb.shape[0]))
            return jnp.concatenate([gf, xb, gb], axis=0)
        return shard_map(kernel, mesh=mesh, in_specs=PSpec(name),
                         out_specs=PSpec(name), check_vma=False)(xs)

    got = np.asarray(f(x)).reshape(n, 4, 3)
    want = np.asarray(_run_ring_reference(mesh, x, 1, 1)).reshape(n, 4, 3)
    np.testing.assert_allclose(got, want, rtol=1e-14)


def _run_ring_reference(mesh, x, front, back):
    name = mesh.axis_names[0]
    n = int(mesh.devices.size)

    def kernel(xb):
        return C.ring_halo_extend(xb, name, n, front, back)

    return shard_map(kernel, mesh=mesh, in_specs=PSpec(name),
                     out_specs=PSpec(name), check_vma=False)(x)


def test_resolve_chunks_fallback_logged(caplog):
    import logging
    assert C.resolve_chunks(128, 8, 4) == 4
    assert C.resolve_chunks(128, 8, 1) == 1
    assert C.resolve_chunks(10, 1, 4) == 1   # single shard: bulk
    with caplog.at_level(logging.INFO, "pylops_mpi_tpu.collectives"):
        # 10 rows over 8 shards can hold at most 1 chunk
        assert C.resolve_chunks(10, 8, 4) == 1
        # 40 rows over 8 shards cap at 5 chunks
        assert C.resolve_chunks(40, 8, 64) == 5
    notes = [r for r in caplog.records if "falling back" in r.message]
    assert len(notes) == 2


def test_all_to_all_resharding_non_dividing(mesh, rng):
    """Non-divisible shapes no longer raise: the planner-backed
    pad-and-crop fallback (parallel/reshard.reshard_raw) handles them,
    matching the bulk path's numerics. Only an impossible budget still
    refuses — with the minimum that would succeed in the message."""
    n = int(mesh.devices.size)
    if n == 1:
        pytest.skip("divisibility is trivial on one device")
    x = jnp.asarray(rng.standard_normal((n + 1, 2 * n)))
    out = C.all_to_all_resharding(x, mesh, old_axis=0, new_axis=1)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x))
    x2 = jnp.asarray(rng.standard_normal((n, 2 * n + 1)))
    out2 = C.all_to_all_resharding(x2, mesh, old_axis=0, new_axis=1)
    np.testing.assert_array_equal(np.asarray(out2), np.asarray(x2))
    # an impossible budget is the one remaining refusal, and it names
    # the minimum budget that would let the move through
    from pylops_mpi_tpu.parallel.reshard import ReshardError, reshard_raw
    with pytest.raises(ReshardError, match=r"minimum budget"):
        reshard_raw(x, mesh, 0, 1, budget=1)


def test_overlap_env_resolution(monkeypatch):
    """auto = off on the CPU sim; explicit kwarg beats the env; junk
    values raise (kwarg) or warn-and-auto (env)."""
    monkeypatch.delenv("PYLOPS_MPI_TPU_OVERLAP", raising=False)
    assert deps.overlap_mode() == "auto"
    assert deps.overlap_enabled(None) is False      # cpu backend
    assert deps.overlap_enabled(True) is True
    assert deps.overlap_enabled("on") is True
    assert deps.overlap_enabled("off") is False
    monkeypatch.setenv("PYLOPS_MPI_TPU_OVERLAP", "on")
    assert deps.overlap_enabled(None) is True
    assert deps.overlap_enabled("off") is False     # kwarg wins
    with pytest.raises(ValueError, match="overlap"):
        deps.overlap_enabled("sideways")


# ------------------------------------------------------------- ring SUMMA
# the stationary-A schedule is the compile-heavier twin (~9 s) of the
# gather schedule on the same shapes; it rides the test-overlap /
# test-hierarchical CI legs unfiltered (tier-1 wall budget, ISSUE 13)
@pytest.mark.parametrize("schedule", [
    "gather", pytest.param("stat_a", marks=pytest.mark.slow)])
@pytest.mark.parametrize("N,K,M", [
    (24, 16, 8),
    # the ragged-shape rows ride the test-overlap CI leg (full file);
    # slow-marked for the tier-1 wall budget
    pytest.param(13, 11, 7, marks=pytest.mark.slow),
])
def test_summa_ring_matches_oracle(rng, schedule, N, K, M):
    A = rng.standard_normal((N, K))
    X = rng.standard_normal((K, M))
    Y = rng.standard_normal((N, M))
    Op = MPIMatrixMult(A, M, kind="summa", dtype=np.float64,
                       schedule=schedule, overlap="on")
    dx = DistributedArray.to_dist(X.ravel())
    dy = DistributedArray.to_dist(Y.ravel())
    np.testing.assert_allclose(Op.matvec(dx).asarray().reshape(N, M),
                               A @ X, rtol=1e-10, atol=1e-12)
    np.testing.assert_allclose(Op.rmatvec(dy).asarray().reshape(K, M),
                               A.conj().T @ Y, rtol=1e-10, atol=1e-12)
    pmt.dottest(Op, dx, dy)


@pytest.mark.slow  # ~10 s compile; the overlap CI leg runs it every push
def test_summa_ring_complex(rng):
    A = (rng.standard_normal((14, 10))
         + 1j * rng.standard_normal((14, 10)))
    X = (rng.standard_normal((10, 6))
         + 1j * rng.standard_normal((10, 6)))
    for schedule in ("gather", "stat_a"):
        Op = MPIMatrixMult(A, 6, kind="summa", dtype=np.complex128,
                           schedule=schedule, overlap="on")
        dx = DistributedArray.to_dist(X.ravel())
        np.testing.assert_allclose(
            Op.matvec(dx).asarray().reshape(14, 6), A @ X,
            rtol=1e-10, atol=1e-12)
        dy = DistributedArray.to_dist(
            (rng.standard_normal(Op.shape[0])
             + 1j * rng.standard_normal(Op.shape[0])))
        pmt.dottest(Op, dx, dy)


def test_summa_off_bit_identical(rng, monkeypatch):
    """overlap='off' IS the pre-round-8 program: exact array equality
    with a default-constructed operator (env unset → auto = off on
    CPU), and unchanged bulk op counts."""
    monkeypatch.delenv("PYLOPS_MPI_TPU_OVERLAP", raising=False)
    A = rng.standard_normal((24, 16))
    X = rng.standard_normal((16, 8))
    dx = DistributedArray.to_dist(X.ravel())
    for schedule in ("gather", "stat_a"):
        off = MPIMatrixMult(A, 8, kind="summa", dtype=np.float64,
                            schedule=schedule, overlap="off")
        default = MPIMatrixMult(A, 8, kind="summa", dtype=np.float64,
                                schedule=schedule)
        assert np.array_equal(np.asarray(off.matvec(dx).asarray()),
                              np.asarray(default.matvec(dx).asarray()))
        counts = count_collectives(jax.jit(off._matvec), dx)
        assert counts.get("collective-permute", 0) == 0


def _assert_adj_ring(Op, rng):
    """Adjoint ring pin on the isolated kernel (the full _rmatvec adds
    one output-layout permute that is not part of the ring)."""
    from pylops_mpi_tpu.ops.matrixmult import _pad_to
    pc = Op.grid[1]
    Y = _pad_to(jnp.asarray(rng.standard_normal((24, 8))), Op.Np, Op.Mp)

    def f(Ap, Yp):
        return shard_map(Op._kernel_adj_ring, mesh=Op.mesh2,
                         in_specs=(PSpec("r", "c"), PSpec("r", "c")),
                         out_specs=PSpec("c", None),
                         check_vma=False)(Ap, Yp)

    assert_ring_schedule(jax.jit(f), Op.Ap, Y, steps=pc - 1, dots=pc)


@pytest.mark.parametrize("schedule", ["gather", "stat_a"])
def test_summa_ring_hlo_pin(rng, schedule):
    """The gather ring forward compiles to pc-1 chained
    collective-permutes interleaved with pc dots (the double-buffered
    schedule). The stationary-A forward has no ring: under
    overlap="on" it keeps the bulk product's collectives, and the
    operator's adjoint is the one that rings."""
    A = rng.standard_normal((24, 16))
    X = rng.standard_normal((16, 8))
    Op = MPIMatrixMult(A, 8, kind="summa", dtype=np.float64,
                       schedule=schedule, overlap="on")
    pc = Op.grid[1]
    if pc < 2:
        pytest.skip("ring needs a >1 column grid")
    dx = DistributedArray.to_dist(X.ravel())
    if schedule == "stat_a":
        off = MPIMatrixMult(A, 8, kind="summa", dtype=np.float64,
                            schedule=schedule, overlap="off")
        counts = count_collectives(jax.jit(Op._matvec), dx)
        assert counts == count_collectives(jax.jit(off._matvec), dx)
        assert counts.get("collective-permute", 0) == 0
        _assert_adj_ring(Op, rng)
        return
    n_perm, n_dots = assert_ring_schedule(jax.jit(Op._matvec), dx,
                                          steps=pc - 1, dots=pc)
    assert (n_perm, n_dots >= pc) == (pc - 1, True)


def test_summa_adj_ring_hlo_pin(rng):
    A = rng.standard_normal((24, 16))
    Op = MPIMatrixMult(A, 8, kind="summa", dtype=np.float64,
                       schedule="gather", overlap="on")
    if Op.grid[1] < 2:
        pytest.skip("ring needs a >1 column grid")
    _assert_adj_ring(Op, rng)


# ----------------------------------------------------------- ring VStack
# the stack-ring oracles (~7-8 s of compile each) ride the
# test-overlap / test-hierarchical CI legs unfiltered; the flat stack
# suites keep tier-1 stack coverage (tier-1 wall budget, ISSUE 13)
@pytest.mark.slow
def test_vstack_ring_adjoint_oracle(rng):
    from pylops_mpi_tpu.ops.local import MatrixMult
    mats = [rng.standard_normal((5, 10)) for _ in range(2 * P)]
    on = pmt.MPIVStack([MatrixMult(m, dtype=np.float64) for m in mats],
                       overlap="on")
    off = pmt.MPIVStack([MatrixMult(m, dtype=np.float64) for m in mats],
                        overlap="off")
    assert on._batched is not None
    x = DistributedArray.to_dist(rng.standard_normal(10),
                                 partition=pmt.Partition.BROADCAST)
    y = on.matvec(x)
    z_on = np.asarray(on.rmatvec(y).asarray())
    z_off = np.asarray(off.rmatvec(y).asarray())
    want = np.vstack(mats).T @ (np.vstack(mats) @ np.asarray(x.asarray()))
    np.testing.assert_allclose(z_on, want, rtol=1e-10)
    np.testing.assert_allclose(z_on, z_off, rtol=1e-12)
    if P > 1:
        counts = count_collectives(jax.jit(on._rmatvec), y)
        assert counts.get("collective-permute", 0) == P - 1
        counts_off = count_collectives(jax.jit(off._rmatvec), y)
        assert counts_off.get("collective-permute", 0) == 0


@pytest.mark.slow
def test_hstack_ring_forward(rng):
    from pylops_mpi_tpu.ops.local import MatrixMult
    mats = [rng.standard_normal((10, 4)) for _ in range(2 * P)]
    on = pmt.MPIHStack([MatrixMult(m, dtype=np.float64) for m in mats],
                       overlap="on")
    x = DistributedArray.to_dist(rng.standard_normal(on.shape[1]))
    want = np.hstack(mats) @ np.asarray(x.asarray())
    np.testing.assert_allclose(np.asarray(on.matvec(x).asarray()), want,
                               rtol=1e-10)


# --------------------------------------------------- chunked pencil FFT
# all chunked-FFT cells (~9 s of compile each) ride the test-overlap
# CI leg unfiltered; tier-1 keeps pencil-FFT coverage via test_fft's
# bulk suites (tier-1 wall budget, ISSUE 13)
@pytest.mark.slow
@pytest.mark.parametrize("engine", ["matmul", "planar"])
@pytest.mark.parametrize("real", [False, True])
def test_fft_chunked_matches_bulk(rng, monkeypatch, engine, real):
    """Chunked transpose (overlap on, K=2) matches the bulk schedule
    across engines, real/complex, ragged dims, forward and adjoint."""
    monkeypatch.setenv("PYLOPS_MPI_TPU_FFT_MODE", engine)
    dims = (18, 16)   # 18 % 8 != 0: ragged rows over the 8-device mesh
    dtype = np.float64 if real else np.complex128
    kw = dict(axes=(0, 1), real=real, dtype=dtype)
    on = MPIFFTND(dims, overlap="on", comm_chunks=2, **kw)
    off = MPIFFTND(dims, overlap="off", **kw)
    x = rng.standard_normal(dims)
    if not real:
        x = x + 1j * rng.standard_normal(dims)
    dx = DistributedArray.to_dist(x.ravel())
    np.testing.assert_allclose(np.asarray(on.matvec(dx).asarray()),
                               np.asarray(off.matvec(dx).asarray()),
                               rtol=1e-9, atol=1e-9)
    y = (rng.standard_normal(on.shape[0])
         + 1j * rng.standard_normal(on.shape[0]))
    dy = DistributedArray.to_dist(y)
    np.testing.assert_allclose(np.asarray(on.rmatvec(dy).asarray()),
                               np.asarray(off.rmatvec(dy).asarray()),
                               rtol=1e-9, atol=1e-9)


def test_fft_chunked_hlo_pin(rng):
    """K chunks → exactly 2K all-to-alls in the forward program (K per
    pencil transpose); the bulk program keeps exactly 2."""
    dims = (16, 128)
    for K, want in ((2, 4), (4, 8)):
        on = MPIFFTND(dims, axes=(0, 1), dtype=np.complex128,
                      overlap="on", comm_chunks=K)
        dx = DistributedArray.to_dist(
            (rng.standard_normal(dims)
             + 1j * rng.standard_normal(dims)).ravel())
        assert count_collectives(jax.jit(on._matvec), dx,
                                 kind="all-to-all") == want
    off = MPIFFTND(dims, axes=(0, 1), dtype=np.complex128, overlap="off")
    dx = DistributedArray.to_dist(
        (rng.standard_normal(dims)
         + 1j * rng.standard_normal(dims)).ravel())
    assert count_collectives(jax.jit(off._matvec), dx,
                             kind="all-to-all") == 2


def test_fft_planar_chunked_complex_free(rng, monkeypatch):
    """The chunked planar plane-pair program stays complex-free (one
    stacked real all-to-all per chunk) — the hardware path's pin."""
    from pylops_mpi_tpu.utils.hlo import assert_complex_free
    monkeypatch.setenv("PYLOPS_MPI_TPU_FFT_MODE", "planar")
    F = MPIFFTND((64, 128), axes=(0, 1), real=True, dtype=np.float32,
                 overlap="on", comm_chunks=2)
    xf = DistributedArray.to_dist(
        rng.standard_normal(64 * 128).astype(np.float32),
        local_shapes=F.model_local_shapes)
    rep = assert_complex_free(lambda v: F.matvec_planes(v)[0], xf)
    assert rep.get("all-to-all", {}).get("count", 0) == 4


def test_fft_chunk_count_falls_back(rng):
    """A chunk count the axis cannot hold degrades to the bulk
    schedule (K=1) instead of erroring — small-dims safety."""
    dims = (16, 10)   # 10 cols over 8 devices: at most 1 chunk
    on = MPIFFTND(dims, axes=(0, 1), dtype=np.complex128,
                  overlap="on", comm_chunks=4)
    x = rng.standard_normal(dims) + 1j * rng.standard_normal(dims)
    dx = DistributedArray.to_dist(x.ravel())
    got = on.matvec(dx).asarray().reshape(on.dimsd_nd)
    np.testing.assert_allclose(got, np.fft.fftn(x), rtol=1e-10,
                               atol=1e-10)
    assert count_collectives(jax.jit(on._matvec), dx,
                             kind="all-to-all") == 2  # bulk


def test_fft_comm_chunks_validation():
    with pytest.raises(ValueError, match="comm_chunks"):
        MPIFFTND((16, 16), axes=(0, 1), comm_chunks=0)


# ------------------------------------------------------ halo / stencils
@pytest.mark.parametrize("kind,order,edge", [
    # the full kind x order x edge matrix (incl. the second-derivative
    # sweep and the halo equality below) rides the test-overlap CI leg;
    # slow-marked rows keep tier-1 inside its wall budget — since
    # ISSUE 13 that includes the last quick cell (~10 s of compile)
    pytest.param("centered", 3, False, marks=pytest.mark.slow),
    pytest.param("centered", 3, True, marks=pytest.mark.slow),
    pytest.param("centered", 5, True, marks=pytest.mark.slow),
    pytest.param("forward", 3, False, marks=pytest.mark.slow),
    pytest.param("backward", 3, False, marks=pytest.mark.slow),
])
def test_first_derivative_overlap_matches(rng, kind, order, edge):
    """Interior/patch-split stencil == bulk ghosted-slab stencil,
    ragged splits included; the exchange stays 2 boundary ppermutes."""
    dims = (8 * P + 3,)   # ragged over any device count
    on = pmt.MPIFirstDerivative(dims, sampling=0.7, kind=kind,
                                order=order, edge=edge,
                                dtype=np.float64, overlap="on")
    off = pmt.MPIFirstDerivative(dims, sampling=0.7, kind=kind,
                                 order=order, edge=edge,
                                 dtype=np.float64, overlap="off")
    x = DistributedArray.to_dist(rng.standard_normal(int(np.prod(dims))))
    assert on._apply_explicit(x, True) is not None
    for forward in (True, False):
        a = np.asarray((on.matvec(x) if forward
                        else on.rmatvec(x)).asarray())
        b = np.asarray((off.matvec(x) if forward
                        else off.rmatvec(x)).asarray())
        np.testing.assert_allclose(a, b, rtol=1e-13, atol=1e-13)
    if P > 1:
        # centered taps need both ghosts; one-sided (forward/backward)
        # kinds let XLA DCE the unused side's permute — never more
        # than the bulk pair, never a gather
        counts = count_collectives(jax.jit(on.matvec), x)
        assert 1 <= counts.get("collective-permute", 0) <= 2
        assert "all-gather" not in counts


@pytest.mark.slow
def test_second_derivative_overlap_matches(rng):
    dims = (8 * P, 4)
    for kw in (dict(kind="centered"), dict(kind="centered", edge=True),
               dict(kind="forward"), dict(kind="backward")):
        on = pmt.MPISecondDerivative(dims, sampling=1.3, dtype=np.float64,
                                     overlap="on", **kw)
        off = pmt.MPISecondDerivative(dims, sampling=1.3,
                                      dtype=np.float64, overlap="off",
                                      **kw)
        x = DistributedArray.to_dist(
            rng.standard_normal(int(np.prod(dims))))
        for forward in (True, False):
            a = np.asarray((on.matvec(x) if forward
                            else on.rmatvec(x)).asarray())
            b = np.asarray((off.matvec(x) if forward
                            else off.rmatvec(x)).asarray())
            np.testing.assert_allclose(a, b, rtol=1e-13, atol=1e-13)


@pytest.mark.slow
def test_halo_overlap_matches(rng):
    """Interior-select repack == bulk post-exchange repack, exactly,
    on 1-D and 2-D process grids (corner relay included)."""
    cases = [((3 * P,), None, 1), ((6, 4 * P), None, 2)]
    if P % 2 == 0 and P >= 4:
        cases.append(((12, 16), (2, P // 2), (1, 2)))
    for dims, grid, halo in cases:
        on = pmt.MPIHalo(dims, halo=halo, proc_grid_shape=grid,
                         dtype=np.float64, overlap="on")
        off = pmt.MPIHalo(dims, halo=halo, proc_grid_shape=grid,
                          dtype=np.float64, overlap="off")
        x = DistributedArray.to_dist(
            rng.standard_normal(int(np.prod(dims))),
            local_shapes=on.local_dim_sizes)
        a = np.asarray(on.matvec(x).asarray())
        b = np.asarray(off.matvec(x).asarray())
        assert np.array_equal(a, b)
        # adjoint is comm-free and unchanged
        ya = DistributedArray.to_dist(
            rng.standard_normal(on.shape[0]),
            local_shapes=on.local_extent_sizes)
        np.testing.assert_array_equal(
            np.asarray(on.rmatvec(ya).asarray()),
            np.asarray(off.rmatvec(ya).asarray()))
