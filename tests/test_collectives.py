"""Explicit collective primitive tests (shard_map layer) — the analog of
the reference's NCCL-primitive unit tests
(``tests_nccl/test_ncclutils_nccl.py``). The module holds only the
hand-scheduled primitives with production consumers: the pencil
transpose (FFTs), and the ring / Cartesian halo extends (stencil fast
path, MPIHalo)."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax import lax

from jax import shard_map
from jax.sharding import PartitionSpec as P

from pylops_mpi_tpu.parallel import collectives as C
from pylops_mpi_tpu.parallel.mesh import make_mesh


@pytest.fixture(scope="module")
def mesh():
    return make_mesh()


def test_all_to_all_resharding(mesh, rng):
    # raw primitive contract: both axes divisible by the mesh size
    n = int(mesh.devices.size)
    x = jnp.asarray(rng.standard_normal((n, 2 * n)))
    got = C.all_to_all_resharding(x, mesh, old_axis=0, new_axis=1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(x))


def test_all_to_all_resharding_3d(mesh, rng):
    n = int(mesh.devices.size)
    x = jnp.asarray(rng.standard_normal((2 * n, n, 3)))
    got = C.all_to_all_resharding(x, mesh, old_axis=1, new_axis=0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(x))


def _run_ring(mesh, x, front, back):
    name = mesh.axis_names[0]
    n = int(mesh.devices.size)

    def kernel(xb):
        return C.ring_halo_extend(xb, name, n, front, back)

    return np.asarray(shard_map(
        kernel, mesh=mesh, in_specs=P(name), out_specs=P(name),
        check_vma=False)(x))


def test_ring_halo_extend(mesh, rng):
    """Each shard's block is extended with the predecessor's last row
    and the successor's first row; zeros at the domain edges."""
    P = int(mesh.devices.size)
    x = jnp.asarray(rng.standard_normal((2 * P, 3)))
    got = _run_ring(mesh, x, 1, 1).reshape(P, 4, 3)
    xv = np.asarray(x).reshape(P, 2, 3)
    for i in range(P):
        exp_front = np.zeros(3) if i == 0 else xv[i - 1, -1]
        exp_back = np.zeros(3) if i == P - 1 else xv[i + 1, 0]
        np.testing.assert_allclose(got[i, 0], exp_front)
        np.testing.assert_allclose(got[i, 1:3], xv[i])
        np.testing.assert_allclose(got[i, 3], exp_back)


def test_ring_halo_extend_stencil(mesh, rng):
    """Ghosted blocks reproduce the global centered stencil on interior
    rows."""
    P = int(mesh.devices.size)
    x = jnp.asarray(rng.standard_normal(4 * P))
    got = _run_ring(mesh, x, 1, 1).reshape(P, 6)
    mid = (got[:, 2:] - got[:, :-2]) / 2
    expected = np.zeros(4 * P)
    expected[1:-1] = (np.asarray(x)[2:] - np.asarray(x)[:-2]) / 2
    np.testing.assert_allclose(mid.ravel()[1:-1], expected[1:-1],
                               rtol=1e-12)


def test_ring_halo_extend_emits_ppermute_only(mesh, rng):
    """The lowered exchange is collective-permute of boundary slabs —
    no all-gather."""
    name = mesh.axis_names[0]
    n = int(mesh.devices.size)

    def f(x):
        def kernel(xb):
            return C.ring_halo_extend(xb, name, n, 1, 1)
        return shard_map(kernel, mesh=mesh, in_specs=P(name),
                         out_specs=P(name), check_vma=False)(x)

    x = jnp.asarray(rng.standard_normal(8 * n))
    hlo = jax.jit(f).lower(x).compile().as_text()
    assert "collective-permute" in hlo
    assert "all-gather" not in hlo


def test_make_mesh_hybrid_single_host():
    """Single-process fallback: (1, n_devices) 2-level mesh with the
    DCN axis degenerate; ICI-axis sharding still works end to end."""
    from jax.sharding import NamedSharding
    from pylops_mpi_tpu import make_mesh_hybrid
    mesh = make_mesh_hybrid()
    assert mesh.axis_names == ("dcn", "sp")
    assert mesh.devices.shape == (1, len(jax.devices()))
    n = len(jax.devices())
    x = jnp.arange(4.0 * n).reshape(2 * n, 2)
    xs = jax.device_put(x, NamedSharding(mesh, P("sp", None)))
    np.testing.assert_allclose(np.asarray(jnp.sum(xs, axis=0)),
                               np.asarray(x).sum(axis=0))


def test_plane_all_to_all_matches_complex_transpose(mesh, rng):
    """The stacked plane-pair all-to-all produces exactly the re/im of
    the complex all-to-all it replaces (the planar pencil transpose),
    and each bin's plane pair stays paired through the split."""
    name = mesh.axis_names[0]
    n = int(mesh.devices.size)
    z = (rng.standard_normal((2 * n, 3 * n))
         + 1j * rng.standard_normal((2 * n, 3 * n))).astype(np.complex64)

    def planar(ar, ai):
        def kernel(br, bi):
            return C.plane_all_to_all(br, bi, name, split_axis=1,
                                      concat_axis=0)
        return shard_map(kernel, mesh=mesh, in_specs=(P(name), P(name)),
                         out_specs=(P(name), P(name)),
                         check_vma=False)(ar, ai)

    def cplx(zz):
        def kernel(b):
            return lax.all_to_all(b, name, split_axis=1, concat_axis=0,
                                  tiled=True)
        return shard_map(kernel, mesh=mesh, in_specs=P(name),
                         out_specs=P(name), check_vma=False)(zz)

    gr, gi = planar(jnp.asarray(z.real.copy()), jnp.asarray(z.imag.copy()))
    want = np.asarray(cplx(jnp.asarray(z)))
    np.testing.assert_allclose(np.asarray(gr), want.real, rtol=1e-7)
    np.testing.assert_allclose(np.asarray(gi), want.imag, rtol=1e-7)


def test_plane_all_to_all_single_collective(mesh, rng):
    """ONE all-to-all instruction for the pair (the stacked layout), no
    complex dtype, no gather."""
    import re
    from pylops_mpi_tpu.utils.hlo import complex_dtype_lines
    name = mesh.axis_names[0]
    n = int(mesh.devices.size)

    def f(ar, ai):
        def kernel(br, bi):
            return C.plane_all_to_all(br, bi, name, split_axis=1,
                                      concat_axis=0)
        return shard_map(kernel, mesh=mesh, in_specs=(P(name), P(name)),
                         out_specs=(P(name), P(name)),
                         check_vma=False)(ar, ai)

    ar = jnp.asarray(rng.standard_normal((n, 2 * n)).astype(np.float32))
    ai = jnp.asarray(rng.standard_normal((n, 2 * n)).astype(np.float32))
    hlo = jax.jit(f).lower(ar, ai).compile().as_text()
    starts = [ln for ln in hlo.splitlines()
              if re.search(r"\ball-to-all(-start)?\(", ln)]
    assert len(starts) == 1, starts
    assert not complex_dtype_lines(hlo)
    assert "all-gather" not in hlo
