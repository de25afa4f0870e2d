"""Per-component benchmarks for the BASELINE.md driver configs beyond
the north star: halo/stencil derivative, SUMMA matmul, pencil FFT,
frequency-sharded Fredholm1 (the MDC core), poststack pipeline.

``run_components()`` returns one dict per config
(``{"bench": ..., "platform": ..., "value": ..., "unit": ...,
"shape": ...}``), each individually try/except-guarded so a single
failing config records an ``"error"`` entry instead of killing the
rest; ``bench.py`` embeds the list in its JSON artifact. Every config
runs in the calling process: one process holds the chip. Run
standalone: ``python benchmarks/bench_components.py [--quick]``.
Without a TPU that exits 2 naming the platform found, unless
``JAX_PLATFORMS=cpu`` asked for the CPU mesh — then each row says
``platform: cpu`` and keeps only what is not read off a clock.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

import numpy as np


def _timeit(f, *args, reps: int = 5, inner: int = 10):
    """Best-of-reps wall time of ``inner`` chained applications."""
    import jax
    out = f(*args)
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(inner):
            out = f(*args)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) / inner)
    return best


def _timeit_np(f, reps: int = 5, inner: int = 3):
    """Best-of-reps wall time of a host NumPy stand-in (the reference's
    per-rank engine): gives each component a ``vs_numpy`` ratio so the
    artifact compares against the reference's compute model per
    config, not just on the flagship."""
    f()
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        for _ in range(inner):
            f()
        best = min(best, (time.perf_counter() - t0) / inner)
    return best


def _progress(name):
    print(f"[bench] {name}...", file=sys.stderr, flush=True)


def _bench_first_derivative(pmt, rng, n_dev, scale):
    """Both stencil schedules: the explicit shard_map ring-halo
    (+Pallas on TPU) fast path vs the implicit GSPMD-partitioned
    formulation (PYLOPS_MPI_TPU_EXPLICIT_STENCIL=0)."""
    import jax
    nx, ny = 2048 * scale, 512
    x = pmt.DistributedArray.to_dist(
        rng.standard_normal(nx * ny).astype(np.float32))
    vals = {}
    prior = os.environ.get("PYLOPS_MPI_TPU_EXPLICIT_STENCIL")
    for tag, env in (("explicit", "1"), ("implicit", "0")):
        os.environ["PYLOPS_MPI_TPU_EXPLICIT_STENCIL"] = env
        try:
            D = pmt.MPIFirstDerivative((nx, ny), kind="centered",
                                       dtype=np.float32)
            fn = jax.jit(lambda v: D.matvec(v).array)
            dt = _timeit(fn, x)
            vals[tag] = round(nx * ny * 4 * 3 / dt / 1e9, 2)
        finally:
            if prior is None:
                os.environ.pop("PYLOPS_MPI_TPU_EXPLICIT_STENCIL", None)
            else:
                os.environ["PYLOPS_MPI_TPU_EXPLICIT_STENCIL"] = prior
    # reference-engine stand-in: NumPy centered stencil on the host
    g = rng.standard_normal((nx, ny)).astype(np.float32)
    buf = np.zeros_like(g)

    def np_stencil():
        buf[1:-1] = (g[2:] - g[:-2]) * 0.5
    np_gbps = nx * ny * 4 * 3 / _timeit_np(np_stencil) / 1e9

    return {"bench": "first_derivative_halo",
            "value": vals["explicit"],
            "implicit_gbps": vals["implicit"], "unit": "GB/s",
            "numpy_gbps": round(np_gbps, 2),
            "vs_numpy": round(vals["explicit"] / np_gbps, 2),
            "shape": f"{nx}x{ny}x{n_dev}dev"}


def _bench_summa(pmt, rng, n_dev, scale):
    """SUMMA with the attribution matrix the round-4 VERDICT asked
    for: how much of the deficit vs NumPy is (a) XLA-vs-BLAS GEMM
    speed (single-device row, no mesh), (b) the mesh carve +
    collectives (gather schedule on both grid shapes), (c) fixable
    scheduling (stationary-A — auto's pick at this skinny-RHS shape —
    vs forced gather)."""
    import jax
    import jax.numpy as jnp
    N = 1024 * scale
    flops = 2 * N * N * 64
    A = rng.standard_normal((N, N)).astype(np.float32)
    X = rng.standard_normal((N, 64)).astype(np.float32)
    xd = pmt.DistributedArray.to_dist(X.ravel())

    def _gf(op):
        fn = jax.jit(lambda v: op.matvec(v).array)
        return flops / _timeit(fn, xd, inner=5) / 1e9

    gf = _gf(pmt.MPIMatrixMult(A, M=64, kind="summa", dtype=np.float32))

    attrib = {}

    def _row(key, fn):
        # per-row guard: one failing variant must not cost the others
        try:
            attrib[key] = round(fn(), 1)
        except Exception as e:
            attrib[key] = None
            attrib.setdefault("errors", {})[key] = repr(e)[:120]

    # (a) one XLA device, no mesh, no collectives: pure XLA-vs-BLAS
    def _single():
        Ad = jax.device_put(jnp.asarray(A), jax.devices()[0])
        Xd = jax.device_put(jnp.asarray(X), jax.devices()[0])
        f1 = jax.jit(lambda a, x: a @ x)
        return flops / _timeit(f1, Ad, Xd, inner=5) / 1e9
    _row("single_dev_xla_gflops", _single)
    # (b) grid-shape sensitivity of the gather schedule (only grids
    # that tile the actual device count — n_dev=5 has none)
    grids = {g for g in ((2, n_dev // 2), (n_dev // 2, 2))
             if g[0] >= 2 and g[1] >= 2 and g[0] * g[1] == n_dev}
    for g in sorted(grids):
        _row(f"gather_grid_{g[0]}x{g[1]}_gflops",
             lambda g=g: _gf(pmt.MPIMatrixMult(
                 A, M=64, kind="summa", grid=g, dtype=np.float32,
                 schedule="gather")))
    # (c) stationary-A (zero bytes of A on the wire) vs gather
    _row("stat_a_gflops",
         lambda: _gf(pmt.MPIMatrixMult(A, M=64, kind="summa",
                                       dtype=np.float32,
                                       schedule="stat_a")))
    # partitioner-derived schedule for reference
    _row("auto_kind_gflops",
         lambda: _gf(pmt.MPIMatrixMult(A, M=64, kind="auto",
                                       dtype=np.float32)))

    # bf16 tile storage + f32 MXU accumulation (the TPU-native format)
    Mlo = pmt.MPIMatrixMult(A, M=64, kind="summa", dtype=np.float32,
                            compute_dtype=jnp.bfloat16)
    flo = jax.jit(lambda v: Mlo.matvec(v).array)
    dt_lo = _timeit(flo, xd, inner=5)
    np_gf = flops / _timeit_np(lambda: A @ X) / 1e9
    row = {"bench": "summa_matmul",
           "value": round(gf, 1), "unit": "GFLOP/s",
           "bf16_gflops": round(flops / dt_lo / 1e9, 1),
           "numpy_gflops": round(np_gf, 1),
           "vs_numpy": round(gf / np_gf, 2),
           "attribution": attrib,
           "shape": f"{N}x{N}@{N}x64"}
    if jax.default_backend() == "tpu":
        # GEMM-bound rows carry MFU; gf is the AGGREGATE rate of the
        # distributed apply, so normalise by all chips' peak like the
        # flagship does
        import bench as _bench
        peak = _bench._peak_flops_per_chip(jax.devices()[0], "f32_highest")
        row["mfu"] = _bench._sig3(gf * 1e9 / (peak * n_dev))
    return row


def _bench_summa_overlap(pmt, rng, n_dev, scale):
    """Bulk vs ring-pipelined SUMMA race (round 8,
    PYLOPS_MPI_TPU_OVERLAP), BOTH schedules. The headline `value` is
    the two-sided (gather) ratio: its ring form is a data-movement win
    even with nothing to hide — each A tile crosses the wire once
    instead of being replicated pc ways — so the CPU sim must hold
    `pipelined_vs_bulk ≥ 0.95` (measured ≥1.5 at landing; a dip means
    the ring rotted into a gather). The stationary-A ring's win is
    ICI-only (its per-chunk GEMMs are narrower — pure overhead on
    CPU), so its ratio is stamped alongside but not barred. TPU rows
    stamp ICI bytes/step and the ring step count from the compiled
    HLO."""
    import jax
    from pylops_mpi_tpu.utils.hlo import collective_report
    N = 1024 * scale
    flops = 2 * N * N * 64
    A = rng.standard_normal((N, N)).astype(np.float32)
    X = rng.standard_normal((N, 64)).astype(np.float32)
    xd = pmt.DistributedArray.to_dist(X.ravel())

    def _race(schedule):
        bulk = pmt.MPIMatrixMult(A, M=64, kind="summa", dtype=np.float32,
                                 overlap=False, schedule=schedule)
        ring = pmt.MPIMatrixMult(A, M=64, kind="summa", dtype=np.float32,
                                 overlap=True, schedule=schedule)
        fb = jax.jit(lambda v: bulk.matvec(v).array)
        fr = jax.jit(lambda v: ring.matvec(v).array)
        dt_b = _timeit(fb, xd, inner=5)
        dt_r = _timeit(fr, xd, inner=5)
        return dt_b, dt_r, ring

    dt_b, dt_r, ring = _race("gather")
    row = {"bench": "summa_overlap",
           "value": round(dt_b / dt_r, 3), "unit": "x (bulk/pipelined)",
           "bulk_gflops": round(flops / dt_b / 1e9, 1),
           "pipelined_gflops": round(flops / dt_r / 1e9, 1),
           "pipelined_vs_bulk": round(dt_b / dt_r, 3),
           "schedule": "gather",
           "shape": f"{N}x{N}@{N}x64,grid={ring.grid}"}
    try:
        sb, sr, _ = _race("stat_a")
        row["stat_a_pipelined_vs_bulk"] = round(sb / sr, 3)
    except Exception as e:  # secondary race must not kill the row
        row["stat_a_error"] = repr(e)[:150]
    try:
        rep = collective_report(jax.jit(ring._matvec), xd)
        cp = rep.get("collective-permute", {})
        row["ring_steps"] = cp.get("count", 0)
        if cp.get("count"):
            # bytes each ring hop moves over ICI per apply
            row["ici_bytes_per_step"] = cp["bytes"] // cp["count"]
    except Exception as e:  # schedule accounting must not kill the row
        row["hlo_error"] = repr(e)[:150]
    return row


def _bench_pencil_a2a_chunked(pmt, rng, n_dev, scale):
    """Bulk vs chunk-streamed pencil transpose race (round 8): the 2-D
    pencil FFT through ONE all-to-all per transpose vs K tiled chunks
    interleaved with the per-chunk axis-0 transforms. The chunked form
    pays a slice + concat copy of the pencil with NOTHING to hide on
    the CPU sim, so K=2 (the minimum that still streams) is raced
    there and `pipelined_vs_bulk` sits just under parity (~0.95±0.03
    at landing); a cliff means the chunked path started duplicating or
    gathering data. TPU rows stamp the chunk count and per-chunk ICI
    bytes from the compiled HLO."""
    import jax
    from pylops_mpi_tpu.utils.hlo import collective_report
    on_tpu = jax.default_backend() == "tpu"
    nf = (512, 512) if scale == 1 else (256 * scale, 512)
    n = int(np.prod(nf))
    flops = 5 * n * np.log2(n)
    chunks = 4 if on_tpu else 2
    bulk = pmt.MPIFFTND(nf, axes=(0, 1), dtype=np.complex64,
                        overlap=False)
    chk = pmt.MPIFFTND(nf, axes=(0, 1), dtype=np.complex64,
                       overlap=True, comm_chunks=chunks)
    x = (rng.standard_normal(nf) + 1j * rng.standard_normal(nf)
         ).astype(np.complex64).ravel()
    xb = pmt.DistributedArray.to_dist(x, local_shapes=bulk.model_local_shapes)
    fb = jax.jit(lambda v: bulk.matvec(v).array)
    fc = jax.jit(lambda v: chk.matvec(v).array)
    # interleaved best-of pairs: the ratio, not the absolute times, is
    # the banked number — pairing cancels thermal/contention drift
    dt_b = dt_c = float("inf")
    for _ in range(3):
        dt_b = min(dt_b, _timeit(fb, xb, reps=3, inner=5))
        dt_c = min(dt_c, _timeit(fc, xb, reps=3, inner=5))
    row = {"bench": "pencil_a2a_chunked",
           "value": round(dt_b / dt_c, 3), "unit": "x (bulk/pipelined)",
           "bulk_gflops": round(flops / dt_b / 1e9, 1),
           "pipelined_gflops": round(flops / dt_c / 1e9, 1),
           "pipelined_vs_bulk": round(dt_b / dt_c, 3),
           "comm_chunks": chunks,
           "shape": f"{nf[0]}x{nf[1]}"}
    try:
        rep = collective_report(jax.jit(chk._matvec), xb)
        a2a = rep.get("all-to-all", {})
        row["a2a_count"] = a2a.get("count", 0)
        if a2a.get("count"):
            row["ici_bytes_per_chunk"] = a2a["bytes"] // a2a["count"]
    except Exception as e:
        row["hlo_error"] = repr(e)[:150]
    return row


def _bench_fft(pmt, rng, n_dev, scale):
    import jax
    nf = (256 * scale, 256)
    F = pmt.MPIFFTND(nf, axes=(0, 1), dtype=np.complex64)
    xf = pmt.DistributedArray.to_dist(
        (rng.standard_normal(nf) + 1j * rng.standard_normal(nf)
         ).astype(np.complex64).ravel())
    fn = jax.jit(lambda v: F.matvec(v).array)
    dt = _timeit(fn, xf, inner=5)
    flops = 5 * np.prod(nf) * np.log2(np.prod(nf))
    xh = (rng.standard_normal(nf) + 1j * rng.standard_normal(nf)
          ).astype(np.complex64)
    np_gf = flops / _timeit_np(lambda: np.fft.fftn(xh)) / 1e9
    gf = flops / dt / 1e9
    return {"bench": "pencil_fft2d",
            "value": round(gf, 1), "unit": "GFLOP/s",
            "numpy_gflops": round(np_gf, 1),
            "vs_numpy": round(gf / np_gf, 2),
            "shape": f"{nf[0]}x{nf[1]}"}


def _bench_fft_planar(pmt, rng, n_dev, scale):
    """Planar (plane-pair) pencil FFT — the complex-free mode `auto`
    selects on TPU runtimes with no complex lowering (round-5 hardware
    finding). Times the real-input planar MPIFFTND forward (the MDC
    shape family) and accounts bytes moved by its all-to-alls from the
    compiled HLO: the half-spectrum rides as two f32 planes, ~half the
    bytes of the complex engine's full-spectrum c64 schedule at the
    same dims (the `pencil_fft2d` row's config —
    `a2a_bytes_vs_complex` ≲ 0.55; vs the complex engine's own
    real-input schedule the planes are byte-parity, reported as
    `a2a_bytes_vs_complex_rfft`)."""
    import jax
    from pylops_mpi_tpu.ops import dft
    from pylops_mpi_tpu.utils.hlo import collective_report

    nf = (256 * scale, 256)
    n = int(np.prod(nf))
    row = {"bench": "pencil_fft2d_planar", "unit": "GFLOP/s",
           "shape": f"{nf[0]}x{nf[1]}"}
    try:
        dft.set_fft_mode("planar")
        F = pmt.MPIFFTND(nf, axes=(0, 1), real=True, dtype=np.float32)
        xf = pmt.DistributedArray.to_dist(
            rng.standard_normal(n).astype(np.float32),
            local_shapes=F.model_local_shapes)
        fn = jax.jit(lambda v: F.matvec(v).array)
        dt = _timeit(fn, xf, inner=5)
        flops = 2.5 * n * np.log2(n)  # rfft flop convention
        row["value"] = round(flops / dt / 1e9, 1)
        # the plane-aware program is THE hardware path (zero complex
        # dtypes, boundary included): account its all-to-all bytes
        rep_p = collective_report(lambda v: F.matvec_planes(v)[0], xf)
        a2a_p = rep_p.get("all-to-all", {}).get("bytes", 0)
        row["a2a_bytes_planar"] = a2a_p
        xh = rng.standard_normal(nf).astype(np.float32)
        np_gf = flops / _timeit_np(
            lambda: np.fft.rfftn(xh, axes=(0, 1))) / 1e9
        row["numpy_gflops"] = round(np_gf, 1)
        row["vs_numpy"] = round(row["value"] / np_gf, 2)
    finally:
        dft.set_fft_mode(None)
    # complex-engine reference schedules, compiled only (may be
    # uncompilable-at-runtime on the no-complex runtime — that is the
    # point; compile-time byte accounting still works there)
    try:
        dft.set_fft_mode("matmul")
        Cop = pmt.MPIFFTND(nf, axes=(0, 1), dtype=np.complex64)
        xc = pmt.DistributedArray.to_dist(
            (rng.standard_normal(n)
             + 1j * rng.standard_normal(n)).astype(np.complex64),
            local_shapes=Cop.model_local_shapes)
        rep_c = collective_report(jax.jit(Cop._matvec), xc)
        a2a_c = rep_c.get("all-to-all", {}).get("bytes", 0)
        row["a2a_bytes_complex"] = a2a_c
        if a2a_c:
            row["a2a_bytes_vs_complex"] = round(a2a_p / a2a_c, 3)
        Rop = pmt.MPIFFTND(nf, axes=(0, 1), real=True, dtype=np.float32)
        xr = pmt.DistributedArray.to_dist(
            rng.standard_normal(n).astype(np.float32),
            local_shapes=Rop.model_local_shapes)
        rep_r = collective_report(jax.jit(Rop._matvec), xr)
        a2a_r = rep_r.get("all-to-all", {}).get("bytes", 0)
        if a2a_r:
            row["a2a_bytes_vs_complex_rfft"] = round(a2a_p / a2a_r, 3)
    except Exception as e:  # reference accounting must not kill the row
        row["complex_ref_error"] = repr(e)[:200]
    finally:
        dft.set_fft_mode(None)
    return row


def _bench_dft_engine(pmt, rng, n_dev, scale):
    """Local FFT engine seam (ops/dft.py): batched MDC-like 1-D
    transforms, matmul (MXU GEMM) engine vs XLA's native FFT. On
    runtimes without an FFT custom-call only the matmul number exists
    (xla_gflops: null)."""
    import os
    import jax
    import jax.numpy as jnp
    from pylops_mpi_tpu.ops import dft

    # two MDC-realistic regimes (round-3 VERDICT next #7): many small
    # batched transforms (the Fredholm/MDC frequency sweep) and one
    # long axis (where O(n·base) GEMM-DFT loses hardest to O(n log n))
    cases = {"batched_small": (128 * scale, 1024, False),
             "long_axis": (4, 65536 * scale, False),
             # MDC's transforms are REAL-input: the packed-real path
             # (one half-length complex FFT + untangle) vs jnp.fft.rfft
             "batched_rfft": (128 * scale, 1024, True)}
    out = {}
    try:
        for tag, (batch, n, real) in cases.items():
            if real:
                x = rng.standard_normal((batch, n)).astype(np.float32)
                flops = 2.5 * batch * n * np.log2(n)  # rfft convention
            else:
                x = (rng.standard_normal((batch, n))
                     + 1j * rng.standard_normal((batch, n))
                     ).astype(np.complex64)
                flops = 5 * batch * n * np.log2(n)  # FFT flop convention
            xd = jnp.asarray(x)
            row = {}
            for mode in ("matmul", "xla"):
                dft.set_fft_mode(mode)  # env is ignored after first use
                try:
                    if real:
                        fn = jax.jit(lambda v: dft.rfft(v, axis=-1))
                    else:
                        fn = jax.jit(lambda v: dft.fft(v, axis=-1))
                    jax.block_until_ready(fn(xd))  # compile + probe
                    dt = _timeit(fn, xd, inner=10)
                    row[mode] = round(flops / dt / 1e9, 1)
                    if mode == "matmul":
                        # actual GEMM work, not FFT-convention flops:
                        # the engine's utilisation is only meaningful
                        # against what it really computes
                        # packed-real rfft = one complex transform of
                        # half length; complex fft = full length
                        neff = n // 2 if real else n
                        sig = sum(dft.stage_radices(neff))
                        gemm_flops = 8.0 * batch * neff * sig
                        row["gemm_gflops"] = round(gemm_flops / dt / 1e9,
                                                   1)
                        if jax.default_backend() == "tpu":
                            import bench as _b
                            row["gemm_mfu"] = _b._sig3(
                                gemm_flops / dt / _b._peak_flops_per_chip(
                                    jax.devices()[0], "f32_highest"))
                except Exception:  # an engine this runtime refuses
                    row[mode] = None
            if row.get("matmul") and row.get("xla"):
                row["vs_xla"] = round(row["matmul"] / row["xla"], 2)
            row["shape"] = f"{batch}x{n}"
            out[tag] = row
        # a base sweep of the matmul engine: which radix cap the MXU
        # actually prefers (default 128 = MXU tile; 32 halves the
        # total GEMM work at these sizes)
        if jax.default_backend() == "tpu":
            sweep = {}
            xs = jnp.asarray((rng.standard_normal((32, 1024))
                              + 1j * rng.standard_normal((32, 1024))
                              ).astype(np.complex64))
            for b in (32, 128):
                try:
                    dft.set_fft_mode("matmul")
                    dft._base_cache = int(b)
                    fnb = jax.jit(lambda v: dft.fft(v, axis=-1))
                    jax.block_until_ready(fnb(xs))
                    sweep[str(b)] = round(
                        5 * 32 * 1024 * np.log2(1024)
                        / _timeit(fnb, xs, inner=10) / 1e9, 1)
                except Exception as e:
                    sweep[str(b)] = repr(e)[:80]
            out["tpu_base_sweep_gflops"] = sweep
    finally:
        dft.set_fft_mode(None)
    bs = out.get("batched_small", {})
    return {"bench": "dft_engine",
            "value": bs.get("matmul"), "unit": "GFLOP/s (matmul engine)",
            "xla_gflops": bs.get("xla"),
            "vs_xla": bs.get("vs_xla"),
            "cases": out,
            "shape": bs.get("shape")}


def _bench_fredholm(pmt, rng, n_dev, scale):
    import jax
    nsl, nx_, ny_, nz_ = 8 * n_dev * scale, 64, 64, 4
    G = rng.standard_normal((nsl, nx_, ny_)).astype(np.float32)
    Fr = pmt.MPIFredholm1(G, nz=nz_, dtype=np.float32)
    xr = pmt.DistributedArray.to_dist(
        rng.standard_normal(Fr.shape[1]).astype(np.float32),
        partition=pmt.Partition.BROADCAST)
    fn = jax.jit(lambda v: Fr.matvec(v).array)
    dt = _timeit(fn, xr, inner=5)
    # slice-aligned SCATTER model: zero-collective apply (the
    # beyond-reference layout, docs/design.md)
    xs = pmt.DistributedArray.to_dist(
        rng.standard_normal(Fr.shape[1]).astype(np.float32),
        local_shapes=Fr.model_local_shapes)
    dt_s = _timeit(fn, xs, inner=5)  # jit re-specializes per sharding
    flops = 2 * nsl * nx_ * ny_ * nz_
    xh = rng.standard_normal((nsl, ny_, nz_)).astype(np.float32)
    np_gf = flops / _timeit_np(
        lambda: np.einsum("sxy,syz->sxz", G, xh)) / 1e9
    gf = flops / dt / 1e9
    return {"bench": "fredholm1_batched",
            "value": round(gf, 1),
            "unit": "GFLOP/s",
            "sharded_model_gflops": round(flops / dt_s / 1e9, 1),
            "numpy_gflops": round(np_gf, 1),
            "vs_numpy": round(gf / np_gf, 2),
            "shape": f"{nsl}x{nx_}x{ny_}"}


def _bench_ragged_overhead(pmt, rng, n_dev, scale):
    """Cost of the specialization-contract cliffs (round-4 VERDICT
    weak #5, next #6): the batched BlockDiag GEMM needs
    ``nblocks % P == 0`` and Fredholm1's zero-collective path needs
    ``nsl % P == 0`` — both degrade gracefully to slower correct
    paths at non-dividing counts, and this row measures what the
    ragged layout actually costs a P=8 user (per-block normalised,
    so 9-vs-8 blocks is apples-to-apples)."""
    import jax

    out = {}
    # BlockDiag: n_dev blocks (batched GEMM path) vs n_dev+1 (ragged)
    nb = 256 * scale
    def _bd_per_block(nblocks):
        blocks = [rng.standard_normal((nb, nb)).astype(np.float32)
                  for _ in range(nblocks)]
        Op = pmt.MPIBlockDiag([pmt.ops.local.MatrixMult(b) for b in blocks])
        xd = pmt.DistributedArray.to_dist(
            rng.standard_normal(Op.shape[1]).astype(np.float32))
        fn = jax.jit(lambda v: Op.rmatvec(Op.matvec(v)).array)
        return _timeit(fn, xd, inner=5) / nblocks, Op

    t_even, op_even = _bd_per_block(n_dev)
    t_ragged, op_ragged = _bd_per_block(n_dev + 1)
    out["blockdiag"] = {
        "batched_path_even": op_even._batched is not None,
        "batched_path_ragged": op_ragged._batched is not None,
        "per_block_ms_even": round(t_even * 1e3, 3),
        "per_block_ms_ragged": round(t_ragged * 1e3, 3),
        "ragged_cost_x": round(t_ragged / t_even, 2),
        "shape": f"{n_dev}+1 blocks of {nb}^2, P={n_dev}"}

    # Fredholm1: at nsl % P == 0 the slice-aligned SCATTER model rides
    # the zero-collective path; at nsl % P != 0 that layout is
    # unavailable (the contract) and the user falls back to BROADCAST —
    # the cliff is the difference between those two real options.
    nx_, ny_, nz_ = 64, 64, 4
    def _fr_per_slice(nsl, aligned):
        G = rng.standard_normal((nsl, nx_, ny_)).astype(np.float32)
        Fr = pmt.MPIFredholm1(G, nz=nz_, dtype=np.float32)
        kw = (dict(local_shapes=Fr.model_local_shapes) if aligned
              else dict(partition=pmt.Partition.BROADCAST))
        xs = pmt.DistributedArray.to_dist(
            rng.standard_normal(Fr.shape[1]).astype(np.float32), **kw)
        fn = jax.jit(lambda v: Fr.matvec(v).array)
        return _timeit(fn, xs, inner=5) / nsl

    nsl0 = 8 * n_dev * scale
    t_even = _fr_per_slice(nsl0, True)
    t_ragged = _fr_per_slice(nsl0 + 1, False)
    out["fredholm1"] = {
        "per_slice_us_even": round(t_even * 1e6, 2),
        "per_slice_us_ragged": round(t_ragged * 1e6, 2),
        "ragged_cost_x": round(t_ragged / t_even, 2),
        "shape": f"nsl={nsl0}(+1) {nx_}x{ny_}x{nz_}, P={n_dev}"}

    worst = max(out["blockdiag"]["ragged_cost_x"],
                out["fredholm1"]["ragged_cost_x"])
    return {"bench": "ragged_overhead",
            "value": worst, "unit": "x (ragged/even per-item cost)",
            "cases": out}


def _bench_poststack(pmt, rng, n_dev, scale):
    import jax
    from pylops_mpi_tpu.models import ricker, poststack_inversion
    from pylops_mpi_tpu.solvers.basic import cgls
    nt0, nxs = 256, 64 * n_dev * scale
    wav = ricker(np.arange(31) * 0.004, f0=15)[0].astype(np.float32)
    d = rng.standard_normal((nxs, nt0)).astype(np.float32)
    # cold: the SHIPPED pipeline end to end, incl. operator build +
    # compile (the one-shot user experience)
    t0 = time.perf_counter()
    _, Op = poststack_inversion(d, wav, niter=10, dtype=np.float32)
    cold = time.perf_counter() - t0
    # warm: re-solve on the SAME operator (compiled executable reused —
    # the iterative-workflow rate); same solver settings as the pipeline
    dy = pmt.DistributedArray.to_dist(d.ravel(), mesh=Op.mesh,
                                      local_shapes=Op.local_shapes_n)
    x0 = pmt.DistributedArray(global_shape=Op.shape[1], mesh=Op.mesh,
                              local_shapes=Op.local_shapes_m,
                              dtype=np.float32)
    warm = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        x, *_ = cgls(Op, dy, x0, niter=10, damp=1e-4, tol=1e-10)
        jax.block_until_ready(x._arr)
        warm = min(warm, time.perf_counter() - t0)
    return {"bench": "poststack_inversion", "value": round(warm, 3),
            "unit": "s (warm, 10it)", "cold_s": round(cold, 3),
            "shape": f"{nxs}x{nt0},10it"}


def _bench_mdc(pmt, rng, n_dev, scale):
    """MDC apply (BASELINE config #5's composite chain: rFFT →
    frequency-sharded Fredholm batched GEMM → irFFT). Forward+adjoint
    sweep timed; flops ≈ the Fredholm core's complex batched matmuls
    (8 real flop per complex MAC), FFT work excluded."""
    import jax
    nt, ns, nr, nv = 65, 24, 24, 2 * scale
    nfmax = 16 * max(n_dev // 2, 1)
    G = (rng.standard_normal((nfmax, ns, nr))
         + 1j * rng.standard_normal((nfmax, ns, nr))
         ).astype(np.complex64)
    Op = pmt.MPIMDC(G, nt=nt, nv=nv, dt=0.004, dr=1.0, twosided=True)
    x = pmt.DistributedArray.to_dist(
        rng.standard_normal(Op.shape[1]).astype(np.float32),
        partition=pmt.Partition.BROADCAST)
    fwd = jax.jit(lambda v: Op.matvec(v).array)
    y = pmt.DistributedArray.to_dist(
        rng.standard_normal(Op.shape[0]).astype(np.float32),
        partition=pmt.Partition.BROADCAST)
    adj = jax.jit(lambda v: Op.rmatvec(v).array)
    dt_f = _timeit(fwd, x, inner=5)
    dt_a = _timeit(adj, y, inner=5)
    flops = 8 * nfmax * ns * nr * nv
    return {"bench": "mdc_apply",
            "value": round(flops / dt_f / 1e9, 2), "unit": "GFLOP/s",
            "adjoint_gflops": round(flops / dt_a / 1e9, 2),
            "shape": f"nt{nt}xns{ns}xnr{nr}xnv{nv},nf{nfmax}"}


def _bench_cgls_multirhs(pmt, rng, n_dev, scale):
    """GEMV → GEMM conversion: CGLS over ``nrhs`` right-hand sides at
    once (``MatrixMult(otherdims=(nrhs,))`` blocks). The single-RHS
    solve is HBM-bandwidth-bound (one matrix read per matvec); with
    batched RHS the same read feeds ``nrhs`` columns on the MXU, so
    per-RHS throughput should multiply on TPU. The reference's
    per-rank NumPy engine has no analogous lever (its GEMV and GEMM
    paths hit the same memory wall). Reports per-RHS iters/s for both
    and the batching speedup."""
    import jax
    from pylops_mpi_tpu.ops.local import MatrixMult
    from pylops_mpi_tpu.solvers.basic import _cgls_fused

    n = 512 * scale
    nrhs = 8
    niter = 10
    blocks = []
    for _ in range(n_dev):
        b = (rng.standard_normal((n, n)) / np.sqrt(n)).astype(np.float32)
        np.fill_diagonal(b, b.diagonal() + 4.0)
        blocks.append(b)

    def solve_rate(k):
        """Per-RHS iteration rate with k stacked right-hand sides."""
        dims = () if k == 1 else (k,)
        Op = pmt.MPIBlockDiag(
            [MatrixMult(b, otherdims=dims, dtype=np.float32)
             for b in blocks])
        y = pmt.DistributedArray.to_dist(
            rng.standard_normal(Op.shape[0]).astype(np.float32),
            local_shapes=Op.local_shapes_n)
        x0 = pmt.DistributedArray(global_shape=Op.shape[1],
                                  local_shapes=Op.local_shapes_m,
                                  dtype=np.float32)
        fn = jax.jit(lambda yy, xx: _cgls_fused(Op, yy, xx, 0.0, 0.0,
                                                niter=niter)[0]._arr)
        dt = _timeit(fn, y, x0, reps=3, inner=1)
        return niter * k / dt

    r1 = solve_rate(1)
    rk = solve_rate(nrhs)
    flops = 4.0 * n * n * n_dev * nrhs  # per batched iteration
    return {"bench": "cgls_multirhs",
            "value": round(rk, 2), "unit": "rhs-iters/s",
            "single_rhs_iters_per_sec": round(r1, 2),
            "batching_speedup": round(rk / r1, 2),
            "gflops_batched": round(flops * rk / nrhs / 1e9, 1),
            "shape": f"{n_dev}x{n}^2,nrhs={nrhs}"}


def _bench_precision_pin(pmt, rng, n_dev, scale):
    """What the package's ``jax_default_matmul_precision=highest`` pin
    costs (round-3 VERDICT weak #4): one representative f32 GEMM traced
    under ``highest`` (true f32: 3-pass bf16 decomposition on the MXU)
    vs ``default`` (1-pass bf16 on TPU, ~1e-3 rel err — the round-3
    SUMMA hardware failure) vs explicit bf16 inputs (the sanctioned
    fast path, ``compute_dtype=bfloat16``). Errors are against the f64
    NumPy product. On CPU the three speeds coincide (the flag is an MXU
    concern); the rows exist so a TPU window fills them with real
    ratios for the docs/tpu.md policy table."""
    import jax
    import jax.numpy as jnp
    m = 512 * scale
    A = rng.standard_normal((m, m)).astype(np.float32)
    B = rng.standard_normal((m, m)).astype(np.float32)
    ref = A.astype(np.float64) @ B.astype(np.float64)
    refn = np.linalg.norm(ref)
    Ad, Bd = jnp.asarray(A), jnp.asarray(B)
    flops = 2.0 * m ** 3
    rows = {}
    for mode in ("highest", "default"):
        with jax.default_matmul_precision(mode):
            fn = jax.jit(lambda a, b: a @ b)
            dt = _timeit(fn, Ad, Bd, inner=5)
            y = np.asarray(fn(Ad, Bd), dtype=np.float64)
        rows[mode] = {"gflops": round(flops / dt / 1e9, 1),
                      "rel_err": f"{np.linalg.norm(y - ref) / refn:.1e}"}
    fnb = jax.jit(lambda a, b: (a @ b).astype(jnp.float32))
    Ab, Bb = Ad.astype(jnp.bfloat16), Bd.astype(jnp.bfloat16)
    dtb = _timeit(fnb, Ab, Bb, inner=5)
    yb = np.asarray(fnb(Ab, Bb), dtype=np.float64)
    rows["bf16_inputs"] = {
        "gflops": round(flops / dtb / 1e9, 1),
        "rel_err": f"{np.linalg.norm(yb - ref) / refn:.1e}"}
    return {"bench": "precision_pin",
            "value": rows["highest"]["gflops"],
            "unit": "GFLOP/s (f32 GEMM @ highest)",
            "modes": rows,
            "pin_cost_x": round(rows["default"]["gflops"]
                                / max(rows["highest"]["gflops"], 1e-9), 2),
            "shape": f"{m}x{m}@{m}x{m}"}


_BENCHES = [("first_derivative_halo", _bench_first_derivative),
            ("summa_matmul", _bench_summa),
            ("summa_overlap", _bench_summa_overlap),
            ("pencil_fft2d", _bench_fft),
            ("pencil_fft2d_planar", _bench_fft_planar),
            ("pencil_a2a_chunked", _bench_pencil_a2a_chunked),
            ("fredholm1_batched", _bench_fredholm),
            ("poststack_inversion", _bench_poststack),
            ("mdc_apply", _bench_mdc),
            ("cgls_multirhs", _bench_cgls_multirhs),
            ("precision_pin", _bench_precision_pin),
            ("ragged_overhead", _bench_ragged_overhead),
            ("dft_engine", _bench_dft_engine)]

# what a row keeps when the run was not on a TPU: identity, sizes and
# correctness — nothing read off a clock
_CPU_KEYS = ("bench", "platform", "shape", "scale", "quick_mode", "error",
             "rel_err", "schedule", "comm_chunks",
             "batched_path_even", "batched_path_ragged")


def run_components(quick: bool = False, only=None):
    """Run component configs in-process; never raises — failures are
    recorded per-config as ``{"bench": name, "error": ...}``. Every
    row names its ``platform``; off a TPU it keeps only ``_CPU_KEYS``
    (a CPU time or rate is never printed under a device metric's
    name)."""
    import jax
    import pylops_mpi_tpu as pmt

    platform = jax.default_backend()
    mesh = pmt.make_mesh()
    pmt.set_default_mesh(mesh)
    n_dev = int(mesh.devices.size)
    scale = 1 if quick else 4
    rng = np.random.default_rng(0)
    results = []
    for name, fn in _BENCHES:
        if only is not None and name != only:
            continue
        _progress(name)
        try:
            r = fn(pmt, rng, n_dev, scale)
        except Exception as e:
            r = {"bench": name, "error": repr(e)[:300]}
        # record the size regime so quick-mode (scale=1) numbers cannot
        # be misread as full-size results
        r.setdefault("scale", scale)
        if quick:
            r.setdefault("quick_mode", True)
        r["platform"] = platform
        if platform != "tpu":
            r = {k: r[k] for k in _CPU_KEYS if k in r}
            r["device_metrics"] = "not measured"
        results.append(r)
    return results


def main(quick: bool = False, only=None) -> int:
    import bench
    if bench._refuse_without_chip("bench_components.py"):
        return 2
    from pylops_mpi_tpu import aot
    aot.maybe_enable_compile_cache(os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".jax_cache"))
    for r in run_components(quick=quick, only=only):
        print(json.dumps(r))
    return 0


if __name__ == "__main__":
    only = None
    if "--only" in sys.argv:
        only = sys.argv[sys.argv.index("--only") + 1]
    sys.exit(main(quick="--quick" in sys.argv, only=only))
