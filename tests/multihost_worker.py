"""Worker for the 2-process ``jax.distributed`` smoke test.

Each of the two processes runs this script with 4 virtual CPU devices;
after ``initialize_multihost`` the global device count is 8 and the
dcn(2) x ici(4) hybrid mesh spans both processes — the pod-scale
bootstrap of ``parallel/mesh.py:98-137`` exercised for real (the
analog of the reference's mpiexec + NCCL-id handshake CI runs,
ref ``.github/workflows/build.yml``). Runs one fused CGLS solve on an
MPIBlockDiag and one SUMMA apply, checks both against NumPy, prints
``MULTIHOST OK`` on success.

Usage: python multihost_worker.py <coordinator_port> <process_id>
"""

import os
import sys

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4"
                           ).strip()
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
try:  # cross-process CPU collectives (name varies across jax versions)
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
except Exception:
    pass

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main() -> None:
    port, pid = int(sys.argv[1]), int(sys.argv[2])
    import pylops_mpi_tpu as pmt
    # under resilience.launch_job this starts the beat thread before
    # the gloo rendezvous (the phase a wedged peer hangs); standalone
    # it is a no-op (no PYLOPS_MPI_TPU_HEARTBEAT_FILE)
    from pylops_mpi_tpu.resilience.elastic import maybe_start_heartbeat
    maybe_start_heartbeat()
    pmt.initialize_multihost(coordinator_address=f"localhost:{port}",
                             num_processes=2, process_id=pid)
    assert jax.process_count() == 2, jax.process_count()
    assert len(jax.devices()) == 8, len(jax.devices())

    import numpy as np
    import jax.numpy as jnp
    from pylops_mpi_tpu.ops.local import MatrixMult

    mesh = pmt.make_mesh_hybrid(dcn_size=2)
    assert mesh.devices.shape == (2, 4), mesh.devices.shape
    pmt.set_default_mesh(mesh)

    rng = np.random.default_rng(0)  # identical data on both processes
    n = 64
    blocks = []
    for _ in range(8):
        b = (rng.standard_normal((n, n)) / np.sqrt(n)).astype(np.float32)
        np.fill_diagonal(b, b.diagonal() + 4.0)
        blocks.append(b)
    xt = rng.standard_normal(8 * n).astype(np.float32)
    y = np.concatenate([b @ xt[i * n:(i + 1) * n]
                        for i, b in enumerate(blocks)])

    Op = pmt.MPIBlockDiag([MatrixMult(b, dtype=np.float32) for b in blocks])
    dy = pmt.DistributedArray.to_dist(y, mesh=mesh)
    x0 = pmt.DistributedArray.to_dist(np.zeros_like(xt), mesh=mesh)
    # the PUBLIC solver: the fused loop receives the operator as a
    # pytree jit argument (linearoperator.py registry) — multi-process
    # JAX forbids closing over arrays spanning non-addressable devices
    xs, istop, iiter, *_ = pmt.cgls(Op, dy, x0=x0, niter=40, tol=0.0)
    # errors are computed ON device (psum-reduced to a replicated
    # scalar): host-gathering a multi-process array's non-addressable
    # shards is exactly what a real pod job must avoid
    err = float(jax.jit(
        lambda a: jnp.linalg.norm(a - jnp.asarray(xt))
        / np.linalg.norm(xt))(xs._arr))
    assert err < 1e-3, f"CGLS rel err {err}"

    # SUMMA apply across the hybrid mesh's flattened device order
    A = rng.standard_normal((48, 40)).astype(np.float32)
    M = 8
    S = pmt.MPIMatrixMult(A, M=M, kind="summa", dtype=np.float32)
    xs = rng.standard_normal(S.shape[1]).astype(np.float32)
    ys = S @ pmt.DistributedArray.to_dist(xs, mesh=S.mesh)
    want = (A @ xs.reshape(40, M)).ravel()
    serr = float(jax.jit(
        lambda a: jnp.linalg.norm(a - jnp.asarray(want))
        / np.linalg.norm(want))(ys._arr))
    assert serr < 1e-4, f"SUMMA rel err {serr}"

    # ISTA: drives power_iteration on the lazy Op.H @ Op composition —
    # the registered-wrapper pytree chain under multi-process jit
    xsp, nit_i, cost_i = pmt.ista(Op, dy, x0=x0, niter=8, eps=1e-4)
    ierr = float(jax.jit(
        lambda a: jnp.linalg.norm(a - jnp.asarray(xt))
        / np.linalg.norm(xt))(xsp._arr))
    assert np.isfinite(cost_i).all() and ierr < 0.5, \
        f"ISTA diverged: err={ierr} cost={cost_i[-3:]}"

    # explicit stencil on a FLAT 1-D mesh spanning both processes: the
    # boundary-slab ppermute halo exchange crosses the process boundary
    flat = pmt.make_mesh()

    # the one-sweep normal kernel (Pallas, interpreted on the CPU)
    # across processes: the fused loop runs it per shard. Needs the
    # FLAT 1-D mesh (has_fused_normal declines multi-axis meshes).
    Opf = pmt.MPIBlockDiag([MatrixMult(b, dtype=np.float32)
                            for b in blocks], mesh=flat)
    assert Opf.has_fused_normal, \
        "the Pallas normal kernel must engage on the flat mesh"
    dyf = pmt.DistributedArray.to_dist(y, mesh=flat)
    x0f = pmt.DistributedArray.to_dist(np.zeros_like(xt), mesh=flat)
    xn, *_ = pmt.cgls(Opf, dyf, x0=x0f, niter=40, tol=0.0,
                      normal=True)
    nerr = float(jax.jit(
        lambda a: jnp.linalg.norm(a - jnp.asarray(xt))
        / np.linalg.norm(xt))(xn._arr))
    assert nerr < 1e-3, f"CGLS(normal=True) rel err {nerr}"
    nD = 64
    Dop = pmt.MPIFirstDerivative((nD,), kind="centered", order=5,
                                 edge=True, mesh=flat, dtype=np.float32)
    xd_np = rng.standard_normal(nD).astype(np.float32)
    xd = pmt.DistributedArray.to_dist(xd_np, mesh=flat)
    yD = Dop._apply_explicit(xd, True)
    assert yD is not None, \
        "explicit stencil must engage on the flat multihost mesh"
    wD = np.zeros(nD, np.float32)
    wD[2:-2] = (xd_np[:-4] - 8 * xd_np[1:-3] + 8 * xd_np[3:-1]
                - xd_np[4:]) / 12.0
    wD[0] = xd_np[1] - xd_np[0]
    wD[1] = (xd_np[2] - xd_np[0]) / 2
    wD[-2] = (xd_np[-1] - xd_np[-3]) / 2
    wD[-1] = xd_np[-1] - xd_np[-2]
    derr = float(jax.jit(
        lambda a: jnp.linalg.norm(a - jnp.asarray(wD))
        / (np.linalg.norm(wD) + 1e-30))(yD._arr))
    assert derr < 1e-5, f"stencil rel err {derr}"

    # pencil FFT: the explicit all_to_all reshard crosses processes too
    Fop = pmt.MPIFFT2D((16, 8), mesh=flat, dtype=np.complex64)
    xf = (rng.standard_normal((16, 8))
          + 1j * rng.standard_normal((16, 8))).astype(np.complex64)
    yF = Fop @ pmt.DistributedArray.to_dist(xf.ravel(), mesh=flat)
    wF = np.fft.fft2(xf).ravel().astype(np.complex64)
    ferr = float(jax.jit(
        lambda a: jnp.linalg.norm(a - jnp.asarray(wF))
        / np.linalg.norm(wF))(yF._arr))
    assert ferr < 1e-4, f"FFT rel err {ferr}"

    # planar (complex-free) pencil FFT across processes: the stacked
    # plane-pair all_to_all (plane_all_to_all) crossing the process
    # boundary — the multihost dryrun of the mode auto-selected on TPU
    # runtimes without complex lowering. Plane-aware API first (zero
    # complex dtypes end to end), then the complex-facing dispatch.
    from pylops_mpi_tpu.ops import dft as _dft
    Pr = pmt.DistributedArray.to_dist(
        xf.real.ravel().astype(np.float32), mesh=flat)
    Pi = pmt.DistributedArray.to_dist(
        xf.imag.ravel().astype(np.float32), mesh=flat)
    pyr, pyi = Fop.matvec_planes(Pr, Pi)
    perr = float(jax.jit(
        lambda a, b: jnp.linalg.norm(
            jnp.stack([a - jnp.asarray(wF.real),
                       b - jnp.asarray(wF.imag)]))
        / np.linalg.norm(wF))(pyr._arr, pyi._arr))
    assert perr < 1e-4, f"planar plane-pair FFT rel err {perr}"
    _dft.set_fft_mode("planar")
    try:
        yP = Fop @ pmt.DistributedArray.to_dist(xf.ravel(), mesh=flat)
        pferr = float(jax.jit(
            lambda a: jnp.linalg.norm(a - jnp.asarray(wF))
            / np.linalg.norm(wF))(yP._arr))
    finally:
        _dft.set_fft_mode(None)
    assert pferr < 1e-4, f"planar FFT rel err {pferr}"

    # MPIHalo on a 2-D Cartesian grid spanning both processes: the
    # slab ppermutes AND the diagonal corner relay cross the process
    # boundary (round-4 VERDICT next #7). The halo adjoint is the
    # sandwich-inverse (crop, ref Halo.py:400-423), so the invariant
    # is the exact roundtrip Hᴴ(Hx) == x — and the ghost values H
    # brings in must be the NEIGHBOURS' data, which a relay that
    # failed across the process boundary would corrupt; the sandwich
    # conv below depends on exactly that. All checks on device.
    from pylops_mpi_tpu.ops.halo import halo_block_split
    gridH, dimsH = (2, 4), (8, 16)
    Hop = pmt.MPIHalo(dims=dimsH, halo=1, proc_grid_shape=gridH,
                      mesh=flat, dtype=np.float32)
    xh = rng.standard_normal(dimsH).astype(np.float32)
    parts = [xh[halo_block_split(dimsH, r, gridH)] for r in range(8)]
    dxh = pmt.DistributedArray.to_dist(
        np.concatenate([p.ravel() for p in parts]),
        local_shapes=[p.size for p in parts], mesh=flat)
    yH = Hop.matvec(dxh)
    zH = Hop.rmatvec(yH)
    herr = float(jax.jit(
        lambda a, b: jnp.linalg.norm(a - b)
        / (jnp.linalg.norm(b) + 1e-30))(zH._arr, dxh._arr))
    assert herr < 1e-6, f"halo crop-roundtrip mismatch: {herr}"
    # ghost correctness across the process boundary: the total energy
    # of Hx must equal ||x||² plus the energy of every ghost copy —
    # compare against the NumPy oracle computed from the same seed
    want_sq = 0.0
    for r in range(8):
        sl = halo_block_split(dimsH, r, gridH)
        i, j = np.unravel_index(r, gridH)
        lo0 = sl[0].start - (1 if i > 0 else 0)
        hi0 = sl[0].stop + (1 if i < gridH[0] - 1 else 0)
        lo1 = sl[1].start - (1 if j > 0 else 0)
        hi1 = sl[1].stop + (1 if j < gridH[1] - 1 else 0)
        want_sq += float((xh[lo0:hi0, lo1:hi1] ** 2).sum())
    got_sq = float(yH.dot(yH))
    henerr = abs(got_sq - want_sq) / want_sq
    assert henerr < 1e-5, f"halo ghost energy {got_sq} != {want_sq}"

    print(f"MULTIHOST OK p{pid} cgls_err={err:.2e} summa_err={serr:.2e} "
          f"ista_err={ierr:.2e} stencil_err={derr:.2e} "
          f"fft_err={ferr:.2e} planar_fft_err={pferr:.2e} "
          f"planes_fft_err={perr:.2e} halo_err={herr:.2e} "
          f"halo_energy_err={henerr:.2e}", flush=True)


if __name__ == "__main__":
    main()
