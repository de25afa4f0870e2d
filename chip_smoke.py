#!/usr/bin/env python3
"""chip_smoke.py — does the system still start on the chip?

One process, every device ``jax.devices()`` shows, public entry points
only. Three stages, each checked against a plain ``jax.numpy``/NumPy
reference that lives in this file and imports nothing from
``pylops_mpi_tpu.solvers``:

A. the flagship at a deployment's size — ``pmt.cgls`` on
   ``MPIBlockDiag([MatrixMult(b) ...])`` at N=4096 with 128 f32 blocks
   (8 GB) per chip: ``normal=False`` (two sweeps), ``normal=True``
   (one-sweep Pallas kernel), no ``normal`` at all (the operator's
   answer: the kernel on a TPU, two sweeps on the CPU) and
   ``normal=True`` over bf16 block storage;
B. the solve service — ``SolveDaemon`` over a ``WarmPool`` holding the
   Stage A operator, one full K=16 bucket and one ragged bucket;
C. the roll-call of every hand-scheduled or Pallas-backed operator,
   forward and adjoint, default overlap against ``overlap="off"``
   (the list ``__graft_entry__.dryrun_multichip`` runs tiny on the CPU
   mesh).

Standard output is two JSON lines: the report (versions, per-stage
sizes, seconds, errors, Mosaic counts, compile cache, native staging;
also written to ``chiprun_out/chip_smoke.json``), then the verdict,
exactly ``{"ok": true, "device": {"platform", "kind", "count"}}`` with
the device as JAX reports it. Exit code 0 and ``"ok": true`` only when
every stage passed on a TPU. Without a TPU it exits 2 naming the
platform it found and prints no result. ``--rehearse`` runs the same
stages at tiny sizes on the 8-virtual-device CPU mesh; its verdict says
``platform: cpu`` and ``"ok": false`` -- a rehearsal proves the script,
never the chip.

The stage seconds it prints are for orientation only — they are smoke
timings, not measurements.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

# per-stage sizes: what a deployment holds on one chip, and the tiny
# twin the CPU rehearsal (and the multichip dry run) uses
CHIP = dict(
    n=4096, blocks_per_chip=128, niter=30, buckets=(4, 16),
    vol_rows_per_chip=256, vol_inner=(250, 256),
    summa_n=4096, summa_m=256,
    vstack_block=(2048, 4096),
    fft_rows_per_chip=2048, fft_cols=2048,
    fred_slices_per_chip=64, fred_nx=512, fred_ny=512, fred_nz=16)
TINY = dict(
    n=32, blocks_per_chip=2, niter=30, buckets=(4, 16),
    vol_rows_per_chip=4, vol_inner=(5, 6),
    summa_n=16, summa_m=16,
    vstack_block=(4, 8),
    fft_rows_per_chip=2, fft_cols=16,
    fred_slices_per_chip=2, fred_nx=5, fred_ny=4, fred_nz=3)

F32_TOL = 1e-4      # two f32 implementations of the same arithmetic
BF16_TOL = 3e-3     # bf16 block storage against the f32 reference


class SmokeFailure(AssertionError):
    """A stage produced a wrong or missing result."""


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def log(msg):
    print(f"[chip_smoke] {msg}", file=sys.stderr, flush=True)


def rel_err(got, ref):
    got = np.asarray(got)
    ref = np.asarray(ref)
    check(got.shape == ref.shape, f"shape {got.shape} != {ref.shape}")
    check(np.all(np.isfinite(got)), "non-finite values in result")
    return float(np.linalg.norm((got - ref).ravel())
                 / max(float(np.linalg.norm(ref.ravel())), 1e-30))


# ------------------------------------------------------ compile accounting
class Compiles:
    """Counts, through ``jax.monitoring``, the programs that reached
    the compiler (``requests``) and those of them the persistent cache
    served (``hits``); the difference is what XLA compiled."""

    def __init__(self):
        import jax.monitoring as mon
        self.requests = self.hits = 0
        mon.register_event_listener(self._event)

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self):
        return (self.requests, self.hits)


class IrDump:
    """Collects the StableHLO of every program compiled inside the
    ``with`` block (``jax_dump_ir_to``) — how the smoke reads the
    program a PUBLIC solver call built, without reaching for the
    private fused loops."""

    def __init__(self, root):
        self.root = root
        self.texts = []

    def __enter__(self):
        import jax
        self._before = set(os.listdir(self.root))
        jax.config.update("jax_dump_ir_to", self.root)
        return self

    def __exit__(self, *exc):
        import jax
        jax.config.update("jax_dump_ir_to", "")
        for name in sorted(set(os.listdir(self.root)) - self._before):
            with open(os.path.join(self.root, name)) as f:
                self.texts.append(f.read())
        return False

    def count(self, needle, within=None):
        """Occurrences of ``needle`` over the dumped programs (only
        those containing ``within`` when given)."""
        return sum(t.count(needle) for t in self.texts
                   if within is None or within in t)


MOSAIC = "tpu_custom_call"


# ------------------------------------------------------------------- data
def make_blocks(nblk, n, seed):
    """Flagship blocks as ``bench.make_problem`` makes them:
    diagonally dominant (thirty iterations converge) and quantized to
    the bf16 grid (f32 and bf16 storage hold the identical system).
    One independent stream per block, generated on a thread pool."""
    import ml_dtypes
    streams = np.random.SeedSequence(seed).spawn(nblk)

    def one(ss):
        b = np.random.default_rng(ss).standard_normal(
            (n, n), dtype=np.float32)
        b *= np.float32(1.0 / np.sqrt(n))
        b[np.arange(n), np.arange(n)] += np.float32(4.0)
        return b.astype(ml_dtypes.bfloat16).astype(np.float32)

    with ThreadPoolExecutor(max_workers=os.cpu_count() or 1) as pool:
        return list(pool.map(one, streams))


def device_bytes():
    """Bytes in use per device: the allocator's own figure where the
    backend reports one, else the live shards' sizes."""
    import jax
    stats = [d.memory_stats() for d in jax.devices()]
    if all(s and "bytes_in_use" in s for s in stats):
        return [int(s["bytes_in_use"]) for s in stats]
    per = {d: 0 for d in jax.devices()}
    for arr in jax.live_arrays():
        for sh in arr.addressable_shards:
            per[sh.device] += sh.data.nbytes
    return [per[d] for d in jax.devices()]


# ------------------------------------------------------ plain references
def ref_cgls(A, Y, niter):
    """Textbook CGLS on the stacked blocks ``A (nblk, m, n)`` for the
    right-hand sides ``Y (nblk, m, K)``, zero start, ``niter``
    iterations, one recurrence per column: two einsums and five
    vector updates per iteration."""
    import jax
    import jax.numpy as jnp
    hi = jax.lax.Precision.HIGHEST

    def mv(X):
        return jnp.einsum("bmn,bnk->bmk", A, X, precision=hi,
                          preferred_element_type=jnp.float32)

    def rmv(R):
        return jnp.einsum("bmn,bmk->bnk", A, R, precision=hi,
                          preferred_element_type=jnp.float32)

    def dot(U):
        return jnp.sum(U * U, axis=(0, 1))

    s = Y
    r = rmv(s)
    c = r
    q = mv(c)
    x = jnp.zeros_like(r)

    def body(_, st):
        x, s, c, q, kold = st
        a = kold / dot(q)
        x = x + a * c
        s = s - a * q
        r = rmv(s)
        k = dot(r)
        c = r + (k / kold) * c
        return x, s, c, mv(c), k

    return jax.lax.fori_loop(0, niter, body, (x, s, c, q, dot(r)))[0]


def _cat0(parts):
    import jax.numpy as jnp
    return jnp.concatenate(parts, axis=0)


def ref_first_derivative(order):
    """Centered first derivative along axis 0 with ``edge=True``
    (sampling 1): pylops' stencils, written with slices."""
    def f3(v):
        return _cat0([v[1:2] - v[0:1], (v[2:] - v[:-2]) / 2,
                      v[-1:] - v[-2:-1]])

    def f5(v):
        core = (v[:-4] - 8 * v[1:-3] + 8 * v[3:-1] - v[4:]) / 12
        return _cat0([v[1:2] - v[0:1], (v[2:3] - v[0:1]) / 2, core,
                      (v[-1:] - v[-3:-2]) / 2, v[-1:] - v[-2:-1]])

    return f3 if order == 3 else f5


def ref_second_derivative(v, axis=0):
    """Centered second derivative along ``axis``, zero edge rows."""
    import jax.numpy as jnp
    v = jnp.moveaxis(v, axis, 0)
    core = v[:-2] - 2 * v[1:-1] + v[2:]
    z = jnp.zeros_like(v[:1])
    return jnp.moveaxis(_cat0([z, core, z]), 0, axis)


# --------------------------------------------------------------- Stage C
def roll_call(mesh, sz, seed=0):
    """Every path that is hand-scheduled (ring ``ppermute``,
    ``all_to_all``, ``psum``/reduce-scatter) or Pallas-backed, one
    entry each, at the sizes ``sz`` names. Shared by Stage C and
    ``__graft_entry__.dryrun_multichip``. An entry holds:

    ``make(overlap)`` the operator; ``overlap`` whether it takes the
    keyword at all; ``x``/``y`` host probes for the model/data space with their
    ``to_dist`` keywords; ``ref`` the forward reference — either
    ``jax.numpy``, linear in its first argument, taking the probe and
    the host arrays in ``consts`` (its transpose is the adjoint
    reference), or a ``(forward, adjoint)`` NumPy pair; ``nbytes`` the
    operator's or volume's size over all shards; ``stencil`` when the
    program must hold the ring ``ppermute`` and, on a TPU, the Mosaic
    call."""
    import jax
    import jax.numpy as jnp
    import pylops_mpi_tpu as pmt
    from pylops_mpi_tpu.ops.local import MatrixMult

    P = int(mesh.devices.size)
    rng = np.random.default_rng(seed + 1)
    hi = jax.lax.Precision.HIGHEST
    f32 = np.float32
    scatter, bcast = pmt.Partition.SCATTER, pmt.Partition.BROADCAST
    cases = []

    # 3-D volume, rows ragged over the mesh (P > 1) and the flattened
    # inner width no multiple of the stencil kernel's lane tile
    vol = (sz["vol_rows_per_chip"] * P + 3,) + tuple(sz["vol_inner"])
    inner = int(np.prod(vol[1:]))
    rows = pmt.local_split(vol, P, scatter, 0)
    vol_kw = dict(local_shapes=[(r[0] * inner,) for r in rows])
    xv = rng.standard_normal(int(np.prod(vol)), dtype=f32)

    def stencil(name, make, ref, **kw):
        cases.append(dict(
            name=name, make=make, x=xv, y=xv, x_kw=vol_kw, y_kw=vol_kw,
            ref=lambda v: ref(v.reshape(vol)).ravel(), consts=(),
            nbytes=xv.nbytes, tol=1e-5, **kw))

    for order in (3, 5):
        stencil(f"first_derivative_o{order}_edge",
                lambda ov, o=order: pmt.MPIFirstDerivative(
                    vol, kind="centered", order=o, edge=True, mesh=mesh,
                    dtype=f32, overlap=ov),
                ref_first_derivative(order), overlap=True,
                stencil=True)
    stencil("second_derivative",
            lambda ov: pmt.MPISecondDerivative(
                vol, kind="centered", mesh=mesh, dtype=f32, overlap=ov),
            ref_second_derivative, overlap=True, stencil=True)
    stencil("laplacian_3d",
            lambda ov: pmt.MPILaplacian(
                vol, axes=(0, 1, 2), weights=(1, 1, 1),
                sampling=(1, 1, 1), mesh=mesh, dtype=f32),
            lambda v: sum(ref_second_derivative(v, ax) for ax in range(3)),
            overlap=False, stencil=False)

    # SUMMA dense matmul on the 2-D process grid
    n = sz["summa_n"] * (2 if P >= 4 else 1)
    m = sz["summa_m"]
    A = rng.standard_normal((n, n), dtype=f32) / f32(np.sqrt(n))
    cases.append(dict(
        name="matrixmult_summa",
        make=lambda ov: pmt.MPIMatrixMult(A, M=m, kind="summa", mesh=mesh,
                                          dtype=f32, overlap=ov),
        overlap=True,
        x=rng.standard_normal(n * m, dtype=f32),
        y=rng.standard_normal(n * m, dtype=f32), x_kw={}, y_kw={},
        ref=lambda v, a: jnp.matmul(a, v.reshape(n, m),
                                    precision=hi).ravel(),
        consts=(A,), nbytes=A.nbytes, tol=F32_TOL, stencil=False))

    # batched VStack: block-sharded forward, reduced adjoint
    bm, bn = sz["vstack_block"]
    mats = [rng.standard_normal((bm, bn), dtype=f32) / f32(np.sqrt(bn))
            for _ in range(2 * P)]
    cases.append(dict(
        name="vstack",
        make=lambda ov: pmt.MPIVStack(
            [MatrixMult(a, dtype=f32) for a in mats], mesh=mesh,
            dtype=f32, overlap=ov),
        overlap=True,
        x=rng.standard_normal(bn, dtype=f32),
        y=rng.standard_normal(2 * P * bm, dtype=f32),
        x_kw=dict(partition=bcast), y_kw={},
        ref=lambda v, a: jnp.einsum("bmn,n->bm", a, v,
                                    precision=hi).ravel(),
        consts=(np.stack(mats),), nbytes=2 * P * bm * bn * 4,
        tol=F32_TOL, stencil=False))

    # pencil FFT with the engine resolved_mode() picks on this runtime
    fd = (sz["fft_rows_per_chip"] * P, sz["fft_cols"])
    nf = int(np.prod(fd))
    cplx = lambda k: (rng.standard_normal(k, dtype=f32)
                      + 1j * rng.standard_normal(k, dtype=f32)
                      ).astype(np.complex64)
    cases.append(dict(
        name="fft2d",
        make=lambda ov: pmt.MPIFFT2D(fd, axes=(0, 1), mesh=mesh,
                                     dtype=np.complex64, overlap=ov),
        overlap=True, x=cplx(nf), y=cplx(nf), x_kw={}, y_kw={},
        # norm="none": forward is the unscaled DFT, adjoint its
        # conjugate transpose (the unscaled inverse)
        ref=(lambda v: np.fft.fft2(v.reshape(fd)).ravel(),
             lambda v: (np.fft.ifft2(v.reshape(fd)) * nf).ravel()),
        nbytes=nf * 8, tol=F32_TOL, stencil=False))

    # Fredholm1: slice-sharded batched GEMM, replicated vectors
    nsl = sz["fred_slices_per_chip"] * P
    nx, ny, nz = sz["fred_nx"], sz["fred_ny"], sz["fred_nz"]
    G = rng.standard_normal((nsl, nx, ny), dtype=f32) / f32(np.sqrt(ny))
    cases.append(dict(
        name="fredholm1",
        make=lambda ov: pmt.MPIFredholm1(G, nz=nz, mesh=mesh, dtype=f32),
        overlap=False,
        x=rng.standard_normal(nsl * ny * nz, dtype=f32),
        y=rng.standard_normal(nsl * nx * nz, dtype=f32),
        x_kw=dict(partition=bcast), y_kw=dict(partition=bcast),
        ref=lambda v, g: jnp.einsum("kxy,kyz->kxz", g,
                                    v.reshape(nsl, ny, nz),
                                    precision=hi).ravel(),
        consts=(G,), nbytes=G.nbytes, tol=F32_TOL, stencil=False))
    return cases


def run_case(case, mesh, overlaps=(None, "off")):
    """Run one roll-call entry: forward and adjoint under each of
    ``overlaps`` (the operator's default and ``"off"``; the CPU dry
    run passes ``("on", "off")``, because ``auto`` is off there), each
    against the reference and the two variants against each other.
    Returns the row for the final line."""
    import jax
    import pylops_mpi_tpu as pmt

    on_tpu = jax.default_backend() == "tpu"
    P = int(mesh.devices.size)
    t0 = time.perf_counter()
    if isinstance(case["ref"], tuple):
        want = (case["ref"][0](case["x"]), case["ref"][1](case["y"]))
    else:
        # the big operands enter as arguments, never as constants
        consts = [jax.device_put(c) for c in case["consts"]]
        fwd_jit = jax.jit(case["ref"])
        fwd_ref = lambda v: fwd_jit(v, *consts)
        adj_ref, = jax.linear_transpose(fwd_ref, case["x"])(case["y"])
        want = (np.asarray(fwd_ref(case["x"])), np.asarray(adj_ref))
        del consts
    dx = pmt.DistributedArray.to_dist(case["x"], mesh=mesh, **case["x_kw"])
    dy = pmt.DistributedArray.to_dist(case["y"], mesh=mesh, **case["y_kw"])
    row = dict(name=case["name"],
               shard_mb=round(case["nbytes"] / P / 2 ** 20, 1),
               err={}, mosaic=0)
    if not case["overlap"]:
        overlaps = (None,)
    got = {}
    for ov in overlaps:
        Op = case["make"](ov)
        outs = []
        for which, vec in (("matvec", dx), ("rmatvec", dy)):
            # the operator travels as a pytree argument, as it does
            # into the fused solvers: its arrays are never constants
            fn = jax.jit(lambda op, v, w=which: getattr(op, w)(v))
            if case["stencil"]:
                text = fn.lower(Op, vec).as_text()
                n_mosaic = text.count(MOSAIC)
                check((n_mosaic > 0) == on_tpu,
                      f"{case['name']}.{which} overlap={ov}: {n_mosaic} "
                      f"Mosaic calls on {jax.default_backend()}")
                check(P == 1 or "collective_permute" in text,
                      f"{case['name']}.{which} overlap={ov}: no ring "
                      "ppermute — the explicit stencil path was not taken")
                row["mosaic"] += n_mosaic
            outs.append(np.asarray(fn(Op, vec).asarray()))
        got[ov] = outs
        for which, out, ref in zip(("matvec", "rmatvec"), outs, want):
            e = rel_err(out, ref)
            row["err"][f"{which}[{ov or 'auto'}]"] = e
            check(e <= case["tol"],
                  f"{case['name']}.{which} overlap={ov}: rel err {e:.2e} "
                  f"> {case['tol']:.0e}")
    if len(overlaps) == 2:
        a, b = (got[ov] for ov in overlaps)
        e = max(rel_err(u, v) for u, v in zip(a, b))
        row["err"][f"{overlaps[0] or 'auto'}_vs_{overlaps[1]}"] = e
        check(e <= case["tol"], f"{case['name']}: overlap={overlaps[0]} "
              f"and overlap={overlaps[1]} differ by {e:.2e}")
    row["seconds"] = round(time.perf_counter() - t0, 2)
    return row


# --------------------------------------------------------------- Stage A
def timed_solve(compiles, Op, y, **kw):
    """``pmt.cgls`` twice: the first call pays set-up (trace, compile
    or cache load), the repeat must reach the compiler with nothing."""
    import jax
    import pylops_mpi_tpu as pmt
    from pylops_mpi_tpu import aot
    t0 = time.perf_counter()
    x = pmt.cgls(Op, y, tol=0.0, **kw)[0]
    jax.block_until_ready(x.array)
    first = time.perf_counter() - t0
    before, aot_before = compiles.snapshot(), aot.compile_count()
    t0 = time.perf_counter()
    x = pmt.cgls(Op, y, tol=0.0, **kw)[0]
    jax.block_until_ready(x.array)
    run = time.perf_counter() - t0
    check(compiles.snapshot() == before
          and aot.compile_count() == aot_before,
          f"the repeated identical solve compiled again: "
          f"{before} -> {compiles.snapshot()}")
    return x.asarray(), max(first - run, 0.0), run


def serve(Op, sz, niter, Y_np, X_ref, compiles):
    """Stage B: the Stage A operator behind the solve service — one
    full bucket and one ragged one, every answer checked column by
    column against ``X_ref``."""
    from pylops_mpi_tpu.serving import FamilySpec, SolveDaemon, WarmPool

    k_full, k_small = max(sz["buckets"]), min(sz["buckets"])
    t0 = time.perf_counter()
    pool = WarmPool(buckets=sz["buckets"])
    pool.register(FamilySpec(name="flagship", operator=Op, solver="cgls",
                             niter=niter, tol=0.0))
    # a window long enough that the stragglers form ONE ragged batch
    daemon = SolveDaemon(pool, window_s=0.5).start(prewarm=True)
    prewarm_s = time.perf_counter() - t0
    before = compiles.snapshot()
    t0 = time.perf_counter()
    try:
        tickets = [daemon.submit("flagship", Y_np[:, j])
                   for j in range(Y_np.shape[1])]
        answers = [t.wait(timeout=600.0) for t in tickets]
    finally:
        drained = daemon.drain(timeout=60.0)
    serve_s = time.perf_counter() - t0
    check(drained, "the daemon did not drain")
    check(compiles.snapshot() == before,
          "a prewarmed bucket compiled again under traffic")
    fills = sorted({(a["batch_k"], a["bucket"]) for a in answers})
    check(fills == [(Y_np.shape[1] - k_full, k_small), (k_full, k_full)],
          f"expected one ragged and one full bucket, got {fills}")
    errs = [rel_err(a["x"], X_ref[:, j]) for j, a in enumerate(answers)]
    check(max(errs) <= F32_TOL, f"served answers: worst column rel err "
          f"{max(errs):.2e} > {F32_TOL:.0e}")
    return dict(requests=len(answers), fills=fills,
                prewarm_s=round(prewarm_s, 2), serve_s=round(serve_s, 3),
                err_max=max(errs), batches=daemon.stats()["batches"])


def stage_a_and_b(mesh, sz, seed, compiles, ir_root):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec
    import pylops_mpi_tpu as pmt
    from pylops_mpi_tpu.ops.local import MatrixMult

    on_tpu = jax.default_backend() == "tpu"
    P = int(mesh.devices.size)
    n, niter = sz["n"], sz["niter"]
    nblk = sz["blocks_per_chip"] * P
    N = nblk * n
    block_bytes = n * n * 4
    rng = np.random.default_rng(seed)

    t0 = time.perf_counter()
    blocks = make_blocks(nblk, n, seed)
    xtrue = rng.standard_normal(N, dtype=np.float32)
    y_np = np.concatenate([b @ xtrue[i * n:(i + 1) * n]
                           for i, b in enumerate(blocks)])
    # requests for Stage B: one full bucket plus one short of the small
    Y_np = rng.standard_normal(
        (N, max(sz["buckets"]) + min(sz["buckets"]) - 1), dtype=np.float32)
    gen_s = time.perf_counter() - t0
    log(f"A: {nblk} blocks of {n}x{n} f32 generated in {gen_s:.1f}s")

    base = device_bytes()
    t0 = time.perf_counter()
    Op = pmt.MPIBlockDiag([MatrixMult(b, dtype=np.float32) for b in blocks],
                          mesh=mesh, compute_dtype=np.float32)
    A, = jax.tree_util.tree_leaves(Op)
    jax.block_until_ready(A)
    build_s = time.perf_counter() - t0
    used = [u - b for u, b in zip(device_bytes(), base)]
    log(f"A: operator built in {build_s:.1f}s; bytes per device {used}")
    share = nblk // P * block_bytes
    check(min(used) >= share, f"a device holds {min(used)} bytes, less "
          f"than its {share}-byte share of the blocks")
    check(max(used) <= 1.1 * min(used), "operator bytes are not spread "
          f"evenly over the devices (10% bound): {used}")
    check(A.shape == (nblk, n, n) and A.dtype == jnp.float32
          and len({s.device for s in A.addressable_shards}) == P,
          f"stacked blocks: {A.shape} {A.dtype}")

    # plain reference on the operator's own stacked array (a second
    # 8 GB copy does not fit the chip); agreement with xtrue below is
    # what checks the stacking itself
    col = NamedSharding(mesh, PartitionSpec(mesh.axis_names[0]))
    ref = jax.jit(ref_cgls, static_argnums=2)
    to_cols = lambda v: jax.device_put(v.reshape(nblk, n, -1), col)
    x_ref = np.asarray(ref(A, to_cols(y_np), niter)).reshape(N)
    X_ref = np.asarray(ref(A, to_cols(Y_np), niter)).reshape(N, -1)
    e_true = rel_err(x_ref, xtrue)
    check(e_true <= F32_TOL, f"reference CGLS did not converge: {e_true}")

    y = pmt.DistributedArray.to_dist(y_np, mesh=mesh)
    rows = {}

    def solve(name, op, tol, normal):
        # normal=None is the caller who says nothing: on a TPU the
        # operator answers for the one-sweep kernel, on the CPU it
        # compiles the classic program. Each row lowers a program of
        # its own to read: the default must not borrow the one an
        # explicit row left in the solver cache
        pmt.clear_fused_cache()
        with IrDump(ir_root) as ir:
            x, setup_s, run_s = timed_solve(
                compiles, op, y, niter=niter,
                **({} if normal is None else {"normal": normal}))
        n_mosaic = ir.count(MOSAIC, within="stablehlo.while")
        check((n_mosaic > 0) == (normal is not False and on_tpu),
              f"{name}: {n_mosaic} Mosaic calls in the solver program on "
              f"{jax.default_backend()} (normal={normal})")
        e = rel_err(x, x_ref)
        check(e <= tol, f"{name}: rel err vs reference {e:.2e} > {tol:.0e}")
        rows[name] = dict(setup_s=round(setup_s, 2), run_s=round(run_s, 3),
                          err=e, err_true=rel_err(x, xtrue),
                          mosaic=n_mosaic)
        log(f"A: {name} {rows[name]}")

    solve("two_sweep_f32", Op, F32_TOL, normal=False)
    solve("normal_f32", Op, F32_TOL, normal=True)
    solve("default_f32", Op, F32_TOL, normal=None)

    stage_b = serve(Op, sz, niter, Y_np, X_ref, compiles)
    log(f"B: {stage_b}")

    # ---- bf16 block storage: free the f32 stack first (HBM holds one)
    del A, Op
    pmt.clear_fused_cache()
    t0 = time.perf_counter()
    Op16 = pmt.MPIBlockDiag(
        [MatrixMult(b, dtype=np.float32) for b in blocks], mesh=mesh,
        compute_dtype=jnp.bfloat16)
    jax.block_until_ready(jax.tree_util.tree_leaves(Op16))
    build16_s = time.perf_counter() - t0
    solve("normal_bf16", Op16, BF16_TOL, normal=True)
    del Op16
    pmt.clear_fused_cache()

    stage_a = dict(n=n, nblk=nblk, niter=niter,
                   block_gb_per_chip=round(share / 2 ** 30, 3),
                   bytes_in_use=used, gen_s=round(gen_s, 1),
                   build_s=round(build_s, 1), build_bf16_s=round(build16_s, 1),
                   ref_err_true=e_true, solves=rows)
    return stage_a, stage_b


# ------------------------------------------------------------------ main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on the 8-virtual-device CPU mesh; "
                         "proves the script, never the chip")
    args = ap.parse_args(argv)

    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        if "host_platform_device_count" not in os.environ.get(
                "XLA_FLAGS", ""):
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=8").strip()
    import jax
    platform = jax.default_backend()
    if platform != ("cpu" if args.rehearse else "tpu"):
        print(f"chip_smoke: JAX found platform {platform!r} "
              f"({len(jax.devices())} x {jax.devices()[0].device_kind}); "
              "this check needs a TPU (--rehearse runs the CPU twin)",
              file=sys.stderr)
        return 2

    import pylops_mpi_tpu as pmt
    from pylops_mpi_tpu import aot, native
    from pylops_mpi_tpu.ops import dft

    cache_dir = aot.maybe_enable_compile_cache(
        os.path.join(HERE, ".jax_cache"))
    compiles = Compiles()
    dev = jax.devices()[0]
    device = dict(platform=dev.platform, kind=dev.device_kind,
                  count=len(jax.devices()))
    log(f"device {device}; compile cache {cache_dir}")
    mesh = pmt.make_mesh()
    pmt.set_default_mesh(mesh)
    sz = TINY if args.rehearse else CHIP

    t_all = time.perf_counter()
    with tempfile.TemporaryDirectory() as ir_root:
        stage_a, stage_b = stage_a_and_b(mesh, sz, args.seed, compiles,
                                         ir_root)
    t0 = time.perf_counter()
    stage_c = []
    for case in roll_call(mesh, sz, args.seed):
        stage_c.append(run_case(case, mesh))
        log(f"C: {stage_c[-1]}")
    c_s = time.perf_counter() - t0

    import jaxlib
    from importlib import metadata
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = None
    requests, hits = compiles.snapshot()
    has_native = native.available()
    report = {
        **({"rehearsal": "passed", "chip": "not run"} if args.rehearse
           else {"chip": "passed"}),
        "platform": device["platform"], "device_kind": device["kind"],
        "n_devices": device["count"],
        "versions": {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                     "libtpu": libtpu},
        "compile_cache": {"dir": cache_dir, "programs": requests,
                          "hits": hits, "compiled": requests - hits},
        "native": {"available": has_native,
                   "staging": "native" if has_native else "numpy"},
        "fft_engine": dft.resolved_mode(),
        "seed": args.seed,
        "total_s": round(time.perf_counter() - t_all, 1),
        "A": stage_a, "B": stage_b,
        "C": {"seconds": round(c_s, 1), "ops": stage_c},
        "note": "stage seconds are smoke timings, not measurements",
    }
    out_dir = os.path.join(HERE, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "chip_smoke.json"), "w") as f:
        json.dump(report, f, indent=1)
    print(json.dumps(report))
    # the verdict, last: these keys and no others. A rehearsal never
    # says the chip passed
    print(json.dumps({"ok": not args.rehearse, "device": device}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
