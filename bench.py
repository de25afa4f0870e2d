"""Benchmark driver — BASELINE.json north-star config:
CGLS on a BlockDiag(MatrixMult) with N=4096, the analog of the
reference's ``examples/plot_cgls.py`` hot loop
(``pylops_mpi/optimization/cls_basic.py:370-404``).

``python bench.py`` measures in the calling process — one process
holds the chip — through the public ``pmt.cgls``, writes the full
artifact to ``bench_detail.json`` and prints ONE compact JSON line
(``{"metric": ..., "value": N, "unit": ..., "platform": ..., ...}``).

There is no fallback: without a TPU it exits 2 and says which platform
JAX found. ``JAX_PLATFORMS=cpu python bench.py`` is the one exception,
a request: it runs the same rows on the CPU mesh, labels every row
``platform: cpu`` and prints counts and correctness only — no time,
rate or utilisation from a CPU run appears under a device metric's
name (``value`` is null, ``device_metrics`` says "not measured").

This is the seed instrument, not yet the benchmark ROADMAP A0
describes (cells, workloads, traced runs): one block per device, the
small-shape race rows of earlier PRs, the component configs of
``benchmarks/bench_components.py``.

Keys beyond ``metric``/``value``/``unit`` (TPU only unless noted):

- ``mfu``: model FLOP utilisation of the solve's GEMMs vs the chip's
  dense peak FOR THE PRECISION USED — bf16 systolic peak for bf16
  storage, bf16/6 for f32 under the ``highest`` matmul-precision pin
  (3 products × 2 operand splits). An unknown ``device_kind`` is an
  error, never a default peak.
- ``f32`` / ``bf16`` / ``bf16_two_sweep``: one row per storage mode and
  schedule (``rel_err``, ``status`` and ``iterations`` on every
  platform).
- ``roofline`` per row: predicted-vs-measured placement from the
  diagnostics cost model (``pylops_mpi_tpu/diagnostics/costmodel.py``).
- ``components``: the per-config results of
  ``benchmarks/bench_components.py`` (every platform; clock-derived
  fields TPU only).
- ``platform`` / ``device_kind`` / ``n_devices``: where it ran (every
  platform).

With ``PYLOPS_MPI_TPU_TRACE`` on, a Chrome-trace JSONL
(``bench_trace.jsonl``) lands next to ``bench_detail.json``.
"""

import json
import os
import subprocess
import sys
import time

import numpy as np


def _platform() -> str:
    import jax
    return jax.default_backend()


def _refuse_without_chip(script: str) -> int:
    """0 to go ahead: JAX runs on a TPU, or ``JAX_PLATFORMS=cpu`` asked
    for the CPU. Otherwise say which platform was found and return the
    exit code 2 — a measurement never falls back to the CPU."""
    import jax
    platform = jax.default_backend()
    if platform == "tpu" or os.environ.get("JAX_PLATFORMS", "") == "cpu":
        return 0
    print(f"{script}: JAX found platform {platform!r} "
          f"({len(jax.devices())} x {jax.devices()[0].device_kind}), "
          "not a TPU; set JAX_PLATFORMS=cpu to run the CPU rows "
          "(counts and correctness, no device metric)", file=sys.stderr)
    return 2


def _device_metrics(**fields):
    """Fields read off a clock — times, rates, ratios of times. A run
    on a TPU keeps them; a CPU run (``JAX_PLATFORMS=cpu``) reports
    counts and correctness only: a CPU number is never printed under a
    device metric's name (ROADMAP aim 1)."""
    return fields if _platform() == "tpu" else {}


def _plan_provenance(op_family: str = "blockdiag") -> str:
    """``plan=`` column for bench rows: where the headline operator's
    schedule came from — ``tuned`` (measured plan replayed),
    ``costmodel`` (analytic seed under PYLOPS_MPI_TPU_TUNE=on), or
    ``default`` (tuner off — today's hand-set seams)."""
    try:
        from pylops_mpi_tpu.tuning.plan import applied_provenance
        return applied_provenance(op_family, default="default")
    except Exception:
        return "default"


def _tune_race_row():
    """Tuner-vs-default race (round 10 acceptance): on small SUMMA
    shapes, time every candidate with the tuner's own trial machinery
    and compare (a) the measured winner against (b) the default
    configuration and (c) the pure cost-model pick. CPU-sim sized so
    the compact line carries it every round; the acceptance bar is
    worst ``tuned_vs_default`` ≤ 1.05 and at least one shape with a
    measured win over the cost-model pick."""
    try:
        import numpy as _np
        import jax as _jax
        from pylops_mpi_tpu.tuning import (space as tspace,
                                           search as tsearch,
                                           plan as tplan)
        from pylops_mpi_tpu.tuning.__main__ import _summa_case
        from pylops_mpi_tpu.diagnostics.profiler import stage_budget
        from pylops_mpi_tpu.parallel.mesh import (default_mesh,
                                                  best_grid_2d)
        mesh = default_mesh()
        n_dev = int(mesh.devices.size)
        platform = _jax.default_backend()
        sp = tspace.space_for("matrixmult")
        grid = best_grid_2d(n_dev)
        rows = []
        for (N, K, M) in ((48, 64, 8), (64, 48, 32)):
            ctx = {"op": "matrixmult", "shape": (N, K, M),
                   "dtype": _np.float32, "n_dev": n_dev,
                   "axes": tuple(mesh.axis_names), "platform": platform,
                   "chip": tplan._chip_kind()[1],
                   "extra": {"grid": grid}}
            factory = _summa_case(N, K, M, mesh)
            winner, trials = tsearch.measure_candidates(
                sp, ctx, factory, repeats=3,
                budget_s=stage_budget("tune"))
            meas = {tuple(sorted(t["params"].items())): t["best_s"]
                    for t in trials if t.get("ok")}

            def t_of(p):
                return meas.get(tuple(sorted(p.items()))) if p else None

            dflt = tspace.default_params(sp, ctx)
            seed = tspace.rank(sp, ctx)[0]
            t_d, t_s, t_w = t_of(dflt), t_of(seed), t_of(winner)
            rows.append({
                "shape": [N, K, M], "default": dflt,
                "costmodel_pick": seed, "n_measured": len(meas),
                **_device_metrics(
                    winner=winner,
                    tuned_vs_default=(_sig3(t_w / t_d)
                                      if t_w and t_d else None),
                    tuned_vs_costmodel=(_sig3(t_w / t_s)
                                        if t_w and t_s else None))})
        r_def = [r["tuned_vs_default"] for r in rows
                 if r.get("tuned_vs_default")]
        r_cm = [r["tuned_vs_costmodel"] for r in rows
                if r.get("tuned_vs_costmodel")]
        return {"platform": platform, "shapes": rows,
                **_device_metrics(
                    worst_tuned_vs_default=max(r_def) if r_def else None,
                    best_tuned_vs_costmodel=min(r_cm) if r_cm else None)}
    except Exception as e:  # the race must never cost the headline
        return {"error": repr(e)[:300]}


def _batched_race_row(niter=20):
    """Batched-throughput race (the batching-PR acceptance bar): one
    Block-CGLS solve with K RHS columns vs K sequential single-RHS
    fused solves of the SAME systems, on the flagship block-diagonal
    family. ``tol=0`` pins both sides to exactly ``niter`` iterations
    so the race measures schedule amortization, not convergence luck.
    Stamps ``solves_per_sec@K`` (the serving-throughput headline) and
    ``batch_plan`` (plan provenance of the operator the block solve
    ran through). K comes from PYLOPS_MPI_TPU_BATCH when set, else
    16."""
    try:
        import numpy as _np
        import jax as _jax
        from pylops_mpi_tpu import DistributedArray, MPIBlockDiag
        from pylops_mpi_tpu.ops.local import MatrixMult
        from pylops_mpi_tpu.solvers import block_cgls, cgls
        from pylops_mpi_tpu.tuning.plan import applied_provenance
        from pylops_mpi_tpu.utils.deps import batch_default
        K = batch_default()
        if K <= 1:
            K = 16
        nblk, nblock = 8, 48
        blocks, _, _ = make_problem(nblk, nblock, seed=3)
        Op = MPIBlockDiag([MatrixMult(b, dtype=np.float32)
                           for b in blocks])
        N = nblk * nblock
        rng = _np.random.default_rng(7)
        Y = rng.standard_normal((N, K)).astype(_np.float32)
        yb = DistributedArray(global_shape=(N, K), dtype=_np.float32)
        yb[:] = Y
        ys = []
        for j in range(K):
            yj = DistributedArray(global_shape=N, dtype=_np.float32)
            yj[:] = Y[:, j]
            ys.append(yj)

        def run_block():
            out = block_cgls(Op, yb, niter=niter, tol=0.0)
            _jax.block_until_ready(out[0]._arr)
            return out

        def run_seq():
            outs = [cgls(Op, yj, niter=niter, tol=0.0) for yj in ys]
            _jax.block_until_ready(outs[-1][0]._arr)
            return outs

        run_block()   # compile both programs outside the timed region
        run_seq()
        t0 = time.perf_counter(); bout = run_block()
        t_blk = time.perf_counter() - t0
        t0 = time.perf_counter(); souts = run_seq()
        t_seq = time.perf_counter() - t0
        # the race only counts if both sides solved the same systems
        err = max(float(_np.max(_np.abs(
            _np.asarray(bout[0].array)[:, j]
            - _np.asarray(souts[j][0].array)))) for j in range(K))
        return {"platform": _platform(), "K": K, "niter": niter,
                "shape": [N, N], "nblk": nblk,
                **_device_metrics(**{
                    f"solves_per_sec@{K}": _sig3(K / t_blk),
                    "sequential_solves_per_sec": _sig3(K / t_seq),
                    "speedup_vs_sequential": _sig3(t_seq / t_blk)}),
                "block_vs_sequential_max_abs_diff": _sig3(err),
                "batch_plan": applied_provenance("blockdiag",
                                                 default="default")}
    except Exception as e:  # the race must never cost the headline
        return {"error": repr(e)[:300]}


def _serving_race_row(niter=20, n_requests=32):
    """Serving race (the serving-PR acceptance bar): 32 single-RHS
    requests through the continuous-batching daemon — packed into
    K=16 block solves against prewarmed executables — vs the same 32
    solved sequentially through the fused single-RHS path, on the
    flagship block-diagonal family. ``tol=0`` pins every solve to
    exactly ``niter`` iterations; the row reports the padded block
    answers' largest difference to the sequential oracles (rounding
    of the K-column products; ``FamilySpec`` says how close they are).
    Stamps ``solves_per_sec`` (wall basis, submit-to-last-result),
    ``speedup_vs_sequential``, and the daemon's p50/p99
    time-in-queue."""
    try:
        import numpy as _np
        from pylops_mpi_tpu import DistributedArray, MPIBlockDiag
        from pylops_mpi_tpu.ops.local import MatrixMult
        from pylops_mpi_tpu.solvers import cgls
        from pylops_mpi_tpu.serving import (FamilySpec, SolveDaemon,
                                            WarmPool)
        nblk, nblock = 8, 48
        blocks, _, _ = make_problem(nblk, nblock, seed=3)
        Op = MPIBlockDiag([MatrixMult(b, dtype=np.float32)
                           for b in blocks])
        N = nblk * nblock
        rng = _np.random.default_rng(7)
        Y = rng.standard_normal((N, n_requests)).astype(_np.float32)
        ys = []
        for j in range(n_requests):
            yj = DistributedArray(global_shape=N, dtype=_np.float32)
            yj[:] = Y[:, j]
            ys.append(yj)

        def run_seq():
            return [_np.asarray(
                cgls(Op, yj, niter=niter, tol=0.0)[0].array)
                for yj in ys]

        run_seq()     # compile the single-RHS program outside timing
        t0 = time.perf_counter()
        oracles = run_seq()
        t_seq = time.perf_counter() - t0

        pool = WarmPool(buckets=(16,))
        pool.register(FamilySpec(name="flagship", operator=Op,
                                 solver="cgls", niter=niter, tol=0.0))
        pool.prewarm(widths=[16])   # compile before the timed region
        daemon = SolveDaemon(pool, window_s=0.05).start()
        try:
            t0 = time.perf_counter()
            tickets = [daemon.submit("flagship", Y[:, j])
                       for j in range(n_requests)]
            results = [t.wait(timeout=120.0) for t in tickets]
            t_pack = time.perf_counter() - t0
            st = daemon.stats()
        finally:
            daemon.drain(timeout=10.0)
        # the race only counts if the daemon solved the same systems
        err = max(float(_np.max(_np.abs(results[j]["x"] - oracles[j])))
                  for j in range(n_requests))
        return {"platform": _platform(), "K": 16,
                "requests": n_requests, "niter": niter,
                "shape": [N, N], "nblk": nblk,
                **_device_metrics(
                    solves_per_sec=_sig3(n_requests / t_pack),
                    sequential_solves_per_sec=_sig3(n_requests / t_seq),
                    speedup_vs_sequential=_sig3(t_seq / t_pack),
                    wait_p50_s=_sig3(st["wait_p50_s"]),
                    wait_p99_s=_sig3(st["wait_p99_s"])),
                "fill_mean": _sig3(st["fill_mean"]),
                "batches": st["batches"],
                "daemon_vs_sequential_max_abs_diff": _sig3(err)}
    except Exception as e:  # the race must never cost the headline
        return {"error": repr(e)[:300]}


def _aot_provenance():
    """``aot=`` column for bench rows: how the bench process itself
    ran — ``off`` (the default, bit-identical pre-AOT build), ``on``/
    ``auto`` memory-only, or ``on+bank``/``auto+bank`` when an
    executable bank directory is armed."""
    try:
        from pylops_mpi_tpu import aot
        mode = aot.aot_mode()
        if aot.aot_enabled() and aot.bank_dir():
            return mode + "+bank"
        return mode
    except Exception:
        return "off"


def _hier_race_row():
    """Hierarchical-vs-flat race (round 11 acceptance): declare the 8
    virtual devices a 2x4 hybrid fabric and run one pencil transpose
    and one (1, 8)-grid ring SUMMA both ways. On the CPU sim both
    "fabrics" are the same silicon, so wall-clock is context only —
    the acceptance number is DCN bytes per apply (flat/hier ≥ 3),
    traced from the per-fabric collective counters and cross-checked
    against the cost model; the timing evidence lands via the
    ``tpu_hier`` cache merge on hardware harvests."""
    saved = {k: os.environ.get(k) for k in
             ("PYLOPS_MPI_TPU_FABRIC", "PYLOPS_MPI_TPU_METRICS",
              "PYLOPS_MPI_TPU_HIERARCHICAL")}
    try:
        import numpy as _np
        import jax as _jax
        from pylops_mpi_tpu import (DistributedArray, MPIFFTND,
                                    MPIMatrixMult)
        from pylops_mpi_tpu.parallel.mesh import make_mesh_hybrid
        from pylops_mpi_tpu.diagnostics import costmodel, metrics
        if len(_jax.devices()) != 8:
            return {"skipped": "needs 8 devices"}
        os.environ["PYLOPS_MPI_TPU_FABRIC"] = "2x4"
        os.environ["PYLOPS_MPI_TPU_METRICS"] = "on"
        os.environ.pop("PYLOPS_MPI_TPU_HIERARCHICAL", None)
        mesh_h = make_mesh_hybrid(dcn_size=2)
        rng = _np.random.default_rng(11)

        def _dcn(name):
            snap = metrics.snapshot()
            cnt = snap.get("counters", snap)
            return cnt.get(f"collective.{name}.bytes_dcn", 0)

        # --- pencil transpose: traced hier bytes vs the flat model
        dims = (16, 8, 4)
        x = (rng.standard_normal(dims)
             + 1j * rng.standard_normal(dims)).ravel()
        xd = DistributedArray.to_dist(x, mesh=mesh_h)
        itemsize = int(_np.dtype(xd._arr.dtype).itemsize)
        flat_cost = costmodel.pencil_transpose_cost(
            dims, 8, itemsize=itemsize, n_transposes=1,
            fabric_shape=(2, 4), hierarchical=False)
        metrics.clear_metrics()
        Oph = MPIFFTND(dims, axes=(0, 1), mesh=mesh_h, hierarchical="on")
        _jax.block_until_ready(Oph.matvec(xd)._arr)
        hier_dcn = _dcn("hier_pencil_transpose") / 2  # 2 per forward
        pencil_ratio = (_sig3(flat_cost.dcn_bytes / hier_dcn)
                        if hier_dcn else None)
        # wall-clock context: one jitted forward each way
        Opf = MPIFFTND(dims, axes=(0, 1), mesh=mesh_h,
                       hierarchical="off")
        fh = _jax.jit(lambda v: Oph.matvec(v)._arr)
        ff = _jax.jit(lambda v: Opf.matvec(v)._arr)
        for f in (fh, ff):
            _jax.block_until_ready(f(xd))
        t0 = time.perf_counter()
        _jax.block_until_ready(fh(xd))
        t_h = time.perf_counter() - t0
        t0 = time.perf_counter()
        _jax.block_until_ready(ff(xd))
        t_f = time.perf_counter() - t0

        # --- SUMMA ring on the slice-spanning (1, 8) axis: traced
        # flat vs traced hier, both through collective.ring_pass
        A = rng.standard_normal((24, 16))
        X = rng.standard_normal((16, 8))
        summa_dcn = {}
        for tag, hier in (("flat", "off"), ("hier", "on")):
            metrics.clear_metrics()
            Op = MPIMatrixMult(A, 8, kind="summa", dtype=_np.float64,
                               mesh=mesh_h, grid=(1, 8),
                               schedule="gather", overlap="on",
                               hierarchical=hier)
            _ = Op.matvec(DistributedArray.to_dist(X.ravel(),
                                                   mesh=mesh_h))
            summa_dcn[tag] = _dcn("ring_pass")
        summa_ratio = (_sig3(summa_dcn["flat"] / summa_dcn["hier"])
                       if summa_dcn.get("hier") else None)
        ratios = [r for r in (pencil_ratio, summa_ratio) if r]
        return {
            "platform": _platform(), "fabric": "2x4",
            "pencil": {"dims": list(dims), "itemsize": itemsize,
                       "model_flat_dcn_bytes": int(flat_cost.dcn_bytes),
                       "traced_hier_dcn_bytes": int(hier_dcn),
                       "dcn_reduction": pencil_ratio,
                       **_device_metrics(
                           time_hier_vs_flat=(_sig3(t_h / t_f)
                                              if t_f else None))},
            "summa": {"shape": [24, 16, 8], "grid": [1, 8],
                      "flat_ring_dcn_bytes": int(summa_dcn["flat"]),
                      "hier_ring_dcn_bytes": int(summa_dcn["hier"]),
                      "dcn_reduction": summa_ratio},
            "worst_dcn_reduction": min(ratios) if ratios else None}
    except Exception as e:  # the race must never cost the headline
        return {"error": repr(e)[:300]}
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        try:
            from pylops_mpi_tpu.diagnostics import metrics as _m
            _m.clear_metrics()
        except Exception:
            pass


def _spill_provenance() -> str:
    """``spill=`` column for bench rows: the host-staging mode the
    round ran under — ``auto`` (refusals drain through host RAM),
    ``on`` (every concrete move host-staged), or ``off`` (round-13
    refusals). From ``PYLOPS_MPI_TPU_SPILL`` via utils/deps.py."""
    try:
        from pylops_mpi_tpu.utils.deps import spill_mode
        return spill_mode()
    except Exception:
        return "auto"


def _spill_race_row():
    """Host-RAM spill race (round 14 acceptance): an oversized
    destination the device planner refuses drains through the
    host-staging tier instead. The row checks (a) bit-identity of the
    spilled result against the unbounded oracle, (b) the
    double-buffer's overlap on the staged D2H drain (``to_host`` with
    overlap on vs off, best-of-reps — wall-clock is context only on
    the CPU sim, where the "device", the copy engine, and the host are
    the same silicon; the >= 1.3x bar is a hardware number that lands
    via the cache merge, the round-8 overlap-race rule), and (c) the
    traced ``bytes_d2h``/``bytes_h2d`` counters against the plan
    totals with ``cost_model() <= budget``. CPU-sim sized so the
    compact line carries it every round;
    ``BENCH_SPILL_PYLOPS_MPI_TPU=1`` forces it on hardware too."""
    saved = {k: os.environ.get(k) for k in
             ("PYLOPS_MPI_TPU_SPILL", "PYLOPS_MPI_TPU_RESHARD_BUDGET",
              "PYLOPS_MPI_TPU_METRICS")}
    try:
        import numpy as _np
        import jax as _jax
        from pylops_mpi_tpu import DistributedArray
        from pylops_mpi_tpu.parallel import reshard as _rs
        from pylops_mpi_tpu.parallel import spill as _sp
        from pylops_mpi_tpu.parallel.partition import Partition as _P
        from pylops_mpi_tpu.parallel.mesh import default_mesh
        from pylops_mpi_tpu.diagnostics import metrics
        for k in saved:
            os.environ.pop(k, None)
        os.environ["PYLOPS_MPI_TPU_METRICS"] = "on"
        mesh = default_mesh()
        n_dev = int(mesh.devices.size)
        rng = _np.random.default_rng(14)
        rows, cols = 32 * max(n_dev, 1), 8192   # 16 MB f64 / 8 MB f32
        M = rng.standard_normal((rows, cols))
        x = DistributedArray.to_dist(M, mesh=mesh)
        # the bench child runs without x64, so size the budget from the
        # dtype the array actually landed with — one row of scratch
        itemsize = _np.dtype(x.dtype).itemsize
        row_bytes = cols * itemsize

        # (a) oversized gather: one row of budget is below the device
        # floor (an all_gather needs two live rows), so ``off``
        # refuses; ``auto`` converts the refusal into a host-staged
        # schedule, bit-identical to the unbounded oracle
        budget = row_bytes
        refused = False
        try:
            _rs.reshard(x, partition=_P.BROADCAST, budget=budget,
                        spill="off")
        except _rs.ReshardError:
            refused = True
        oracle = _np.asarray(_rs.reshard(
            x, partition=_P.BROADCAST, budget=None,
            spill="off").asarray())
        metrics.clear_metrics()
        spilled = _rs.reshard(x, partition=_P.BROADCAST, budget=budget)
        host_dst = isinstance(spilled, _sp.HostArray)
        got = (spilled.value if host_dst
               else _np.asarray(spilled.asarray()))
        bit_identical = bool(_np.array_equal(got, oracle))

        # (c) counters vs the plan: a device source draining to a host
        # destination is pure D2H — every byte lands in bytes_d2h and
        # nothing goes back up
        plan = _rs.plan_reshard(
            (rows, cols), itemsize, _rs.Layout.scatter(x._axis_sizes),
            _rs.Layout.replicated(n_dev), budget=budget, spill="auto")
        cnt = metrics.snapshot().get("counters", {})
        d2h = int(cnt.get("collective.reshard.bytes_d2h", 0))
        h2d = int(cnt.get("collective.reshard.bytes_h2d", 0))
        total = rows * cols * itemsize
        bytes_ok = (d2h == plan.nbytes_d2h == total
                    and h2d == plan.nbytes_h2d == 0)

        # (b) the double-buffer: chunk k+1's carve is dispatched before
        # chunk k's blocking host copy, so device work rides under the
        # D2H drain; off serializes with a block per chunk
        def _drain(ov):
            _jax.block_until_ready(x._arr)
            t0 = time.perf_counter()
            _sp.to_host(x, chunks=16, overlap=ov)
            return time.perf_counter() - t0
        for ov in ("on", "off"):    # warm both paths
            _drain(ov)
        t_on = min(_drain("on") for _ in range(5))
        t_off = min(_drain("off") for _ in range(5))
        return {
            "platform": _platform(),
            "shape": [rows, cols], "budget_bytes": int(budget),
            "chunks": len(plan.steps),
            "off_refuses": refused, "host_dst": host_dst,
            "bit_identical_vs_oracle": bit_identical,
            "bytes_accounting_ok": bytes_ok,
            "d2h_bytes": d2h, "h2d_bytes": h2d,
            "cost_model_bytes": int(plan.cost_model()),
            "cost_model_under_budget": plan.cost_model() <= budget,
            **_device_metrics(
                overlap_on_s=_sig3(t_on), overlap_off_s=_sig3(t_off),
                overlap_speedup=_sig3(t_off / t_on) if t_on else None)}
    except Exception as e:  # the race must never cost the headline
        return {"error": repr(e)[:300]}
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        try:
            from pylops_mpi_tpu.diagnostics import metrics as _m
            _m.clear_metrics()
        except Exception:
            pass


def _precond_race_row():
    """Preconditioned-solver race (preconditioner-PR acceptance bar):
    an ill-conditioned 2-D Laplacian-regularized CGLS solve, run
    unpreconditioned, with the block-Jacobi preconditioner, and with
    the 2-level V-cycle. Stamps iterations-to-tol for each arm plus
    the headline ratios — the acceptance gate is block-Jacobi
    ``iters_ratio <= 0.5`` with a wall-clock win. Error-isolated: a
    preconditioner failure reports itself, never costs the headline."""
    try:
        import numpy as _np
        import jax as _jax
        import jax.numpy as _jnp
        from pylops_mpi_tpu import DistributedArray
        from pylops_mpi_tpu.linearoperator import MPILinearOperator
        from pylops_mpi_tpu.ops.precond import make_precond
        from pylops_mpi_tpu.solvers import cgls

        dims = (24, 24)
        n = dims[0] * dims[1]
        eps = 0.05   # small regularization → large condition number

        def _lap_factory(d):
            """Dirichlet 5-point Laplacian on grid ``d`` (symmetric —
            one-sided boundary stencils would break CG/MG)."""
            class _Lap(MPILinearOperator):
                accepts_block = True
                dims_ = d

                def __init__(self):
                    nn = d[0] * d[1]
                    super().__init__(shape=(nn, nn),
                                     dtype=_np.dtype("float32"))

                def _apply(self, x):
                    arr = x._global() if hasattr(x, "_global") else x
                    g = arr.reshape(d)
                    p = _jnp.pad(g, 1)
                    out = (4.0 * g - p[:-2, 1:-1] - p[2:, 1:-1]
                           - p[1:-1, :-2] - p[1:-1, 2:])
                    flat = (eps * arr.reshape(-1)
                            + out.reshape(-1)).astype(arr.dtype)
                    if hasattr(x, "_global"):
                        return DistributedArray._wrap(
                            x._from_global(flat), x)
                    return flat

                _matvec = _apply
                _rmatvec = _apply
            return _Lap()

        Op = _lap_factory(dims)
        rng = _np.random.default_rng(11)
        xt = rng.standard_normal(n).astype(_np.float32)
        yv = _np.asarray(Op.matvec(
            DistributedArray.to_dist(xt)).asarray())
        y = DistributedArray.to_dist(yv)
        niter = 400
        rtol = 1e-3
        g0 = _np.asarray(Op.rmatvec(
            DistributedArray.to_dist(yv)).asarray())

        # exact diagonal blocks of the normal operator AᴴA (CGLS
        # preconditions the normal system; the mod-m probe would alias
        # the ±row couplings of the squared stencil into the blocks)
        from pylops_mpi_tpu.ops.precond import BlockJacobiPrecond
        Ad = _np.asarray(Op.todense(), dtype=_np.float64)
        Nd = Ad.T @ Ad
        m = dims[1]
        blocks = _np.stack([Nd[i * m:(i + 1) * m, i * m:(i + 1) * m]
                            for i in range(n // m)])
        bj = BlockJacobiPrecond(blocks.astype(_np.float32))
        vc = make_precond(Op, kind="mg", op_factory=_lap_factory,
                          dims=dims, levels=2)

        def _arm(M):
            # the fused stop test is absolute in the M-norm (kold =
            # g·Mg), so each arm's tol comes from its own kold0 — the
            # standard relative-residual PCG criterion, identical
            # reduction factor on every arm
            z0 = (g0 if M is None else _np.asarray(M.matvec(
                DistributedArray.to_dist(g0)).asarray()))
            tol = float(rtol ** 2 * _np.dot(g0, z0))

            def run():
                out = cgls(Op, y, niter=niter, tol=tol, M=M)
                _jax.block_until_ready(out[0]._arr)
                return out
            out = run()                      # compile outside timing
            t0 = time.perf_counter()
            out = run()
            t = time.perf_counter() - t0
            xs = _np.asarray(out[0].asarray())
            err = float(_np.linalg.norm(xs - xt)
                        / _np.linalg.norm(xt))
            return int(out[2]), t, err

        it0, t0s, e0 = _arm(None)
        itb, tbs, eb = _arm(bj)
        itv, tvs, ev = _arm(vc)
        def _arm_row(it, t, e):
            return {"iters": it, "rel_err": _sig3(e),
                    **_device_metrics(wall_s=_sig3(t),
                                      solves_per_sec=_sig3(1.0 / t))}

        return {
            "platform": _platform(),
            "problem": {"dims": list(dims), "eps": eps,
                        "niter_cap": niter},
            "unpreconditioned": _arm_row(it0, t0s, e0),
            "block_jacobi": _arm_row(itb, tbs, eb),
            "vcycle": _arm_row(itv, tvs, ev),
            "bj_iters_ratio": _sig3(itb / it0) if it0 else None,
            "vc_iters_ratio": _sig3(itv / it0) if it0 else None,
            **_device_metrics(
                bj_wall_speedup=_sig3(t0s / tbs) if tbs else None,
                vc_wall_speedup=_sig3(t0s / tvs) if tvs else None),
        }
    except Exception as e:  # the race must never cost the headline
        return {"error": repr(e)[:300]}


def _sparse_race_row():
    """Sparse-vs-dense matvec race (sparse-tier acceptance bar): at
    ≥90% sparsity the triplet operator's forward+adjoint sweep against
    the dense SUMMA/block operator on the same matrix. Stamps the byte
    ratio the tier-selection cost model reasons from and the measured
    wall ratio. Error-isolated like every race row."""
    try:
        import numpy as _np
        import jax as _jax
        from pylops_mpi_tpu import DistributedArray
        from pylops_mpi_tpu.ops.matrixmult import MPIMatrixMult
        from pylops_mpi_tpu.ops.sparse import MPISparseMatrixMult

        N = M = 512
        density = 0.05           # 95% sparse — well past the 90% gate
        rng = _np.random.default_rng(13)
        A = (rng.standard_normal((N, M))
             * (rng.random((N, M)) < density)).astype(_np.float32)
        Sp = MPISparseMatrixMult.from_dense(A)
        De = MPIMatrixMult(A, 1, dtype=_np.float32)
        x = DistributedArray.to_dist(
            rng.standard_normal(M).astype(_np.float32))
        y = DistributedArray.to_dist(
            rng.standard_normal(N).astype(_np.float32))

        def _sweep(op):
            def run():
                f = op.matvec(x)
                a = op.rmatvec(y)
                _jax.block_until_ready((f._arr, a._arr))
                return f, a
            run()                            # compile outside timing
            reps = 20
            t0 = time.perf_counter()
            for _ in range(reps):
                f, a = run()
            t = (time.perf_counter() - t0) / reps
            return t, f, a

        t_sp, f_sp, a_sp = _sweep(Sp)
        t_de, f_de, a_de = _sweep(De)
        err = max(
            float(_np.max(_np.abs(_np.asarray(f_sp.asarray())
                                  - _np.asarray(f_de.asarray())))),
            float(_np.max(_np.abs(_np.asarray(a_sp.asarray())
                                  - _np.asarray(a_de.asarray())))))
        it = _np.dtype(_np.float32).itemsize
        bytes_ratio = (Sp.nnz * (it + 8)) / (N * M * it)
        return {
            "platform": _platform(),
            "shape": [N, M], "density": _sig3(Sp.density),
            "nnz": int(Sp.nnz),
            **_device_metrics(
                sparse_sweep_s=_sig3(t_sp), dense_sweep_s=_sig3(t_de),
                sparse_vs_dense_wall=(_sig3(t_sp / t_de)
                                      if t_de else None)),
            "bytes_ratio": _sig3(bytes_ratio),
            "max_abs_diff": _sig3(err),
        }
    except Exception as e:  # the race must never cost the headline
        return {"error": repr(e)[:300]}


def _ca_race_row():
    """Communication-avoiding solver race (CA-PR acceptance bar): a
    fused CG solve under an injected per-collective latency floor
    (``PYLOPS_MPI_TPU_REDUCE_STALL`` — a serial dependency chain the
    compiler cannot elide, standing in for the all-reduce α-term the
    single-host CPU sim cannot produce), classic two-reduction engine
    vs the one-reduction pipelined engine on the same trajectory.
    Stamps the body all-reduce counts (pinned via ``utils/hlo.py``
    with the stall OFF — program truth, not timing), iteration parity
    and the wall ratio. Error-isolated like every race row."""
    saved = {k: os.environ.get(k) for k in
             ("PYLOPS_MPI_TPU_CA", "PYLOPS_MPI_TPU_REDUCE_STALL")}

    def _setenv(k, v):
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v

    try:
        import numpy as _np
        import jax as _jax
        from pylops_mpi_tpu import DistributedArray, MPIBlockDiag
        from pylops_mpi_tpu.ops.local import MatrixMult
        from pylops_mpi_tpu.solvers import cg, clear_fused_cache
        from pylops_mpi_tpu.solvers import ca as _camod
        from pylops_mpi_tpu.solvers.basic import _cg_fused
        from pylops_mpi_tpu.utils import hlo as _hlo

        rng = _np.random.default_rng(17)
        nblk = max(len(_jax.devices()), 2)
        nloc = 48
        mats = []
        for _ in range(nblk):
            m = rng.standard_normal((nloc, nloc)).astype(_np.float32)
            # conditioned to take a few dozen iterations — enough for
            # the per-iteration latency floor to dominate the wall
            mats.append((m @ m.T) * 0.5
                        + 2.0 * _np.eye(nloc, dtype=_np.float32))
        Op = MPIBlockDiag([MatrixMult(m, dtype=_np.float32)
                           for m in mats])
        n = nblk * nloc
        xt = rng.standard_normal(n).astype(_np.float32)
        yv = _np.asarray(Op.matvec(
            DistributedArray.to_dist(xt)).asarray())
        y = DistributedArray.to_dist(yv)
        niter = 80
        # the fused stop test is absolute on kold = r·r; with x0 = 0
        # the standard relative criterion is rel² x ‖y‖²
        tol = float(1e-4 ** 2 * _np.dot(yv.astype(_np.float64), yv))

        def _x0():
            return DistributedArray.to_dist(
                _np.zeros(n, dtype=_np.float32))

        # 1. program truth, stall OFF: all-reduces per while-body
        _setenv("PYLOPS_MPI_TPU_REDUCE_STALL", None)
        _setenv("PYLOPS_MPI_TPU_CA", "off")
        clear_fused_cache()

        def _classic_fn(y_, x_, t_):
            return _cg_fused(Op, y_, x_, t_, niter=niter)

        def _pipe_fn(y_, x_, t_):
            return _camod._pipe_cg_fused(Op, y_, x_, t_, niter=niter)

        red_classic = _hlo.count_reductions(
            _hlo.compiled_hlo(_classic_fn, y, _x0(), 0.0), scope="body")
        red_pipe = _hlo.count_reductions(
            _hlo.compiled_hlo(_pipe_fn, y, _x0(), 0.0), scope="body")

        # 2. the race, stall ON: every reduction pays the latency floor
        stall = os.environ.get("BENCH_CA_STALL_PYLOPS_MPI_TPU", "4096")
        _setenv("PYLOPS_MPI_TPU_REDUCE_STALL", stall)

        def _arm(mode):
            _setenv("PYLOPS_MPI_TPU_CA", mode)
            clear_fused_cache()

            def run():
                out = cg(Op, y, _x0(), niter=niter, tol=tol,
                         fused=True)
                _jax.block_until_ready(out[0]._arr)
                return out

            out = run()              # compile outside timing
            reps = 3
            t0 = time.perf_counter()
            for _ in range(reps):
                out = run()
            t = (time.perf_counter() - t0) / reps
            xs = _np.asarray(out[0].asarray())
            err = float(_np.linalg.norm(xs - xt)
                        / _np.linalg.norm(xt))
            return int(out[1]), t, err

        it0, t0s, e0 = _arm("off")
        itp, tps, ep = _arm("pipelined")
        parity = abs(itp - it0) <= max(2, int(round(0.1 * it0)))
        def _arm_row(it, t, e):
            return {"iters": it, "rel_err": _sig3(e),
                    **_device_metrics(wall_s=_sig3(t),
                                      solves_per_sec=_sig3(1.0 / t))}

        return {
            "platform": _platform(),
            "problem": {"nblk": nblk, "nloc": nloc, "niter_cap": niter},
            "host_stall_steps": int(stall),
            "reductions_per_iter": {"classic": red_classic,
                                    "pipelined": red_pipe},
            "classic": _arm_row(it0, t0s, e0),
            "pipelined": _arm_row(itp, tps, ep),
            "iters_parity": parity,
            **_device_metrics(
                wall_speedup=_sig3(t0s / tps) if tps else None),
        }
    except Exception as e:  # the race must never cost the headline
        return {"error": repr(e)[:300]}
    finally:
        for k, v in saved.items():
            _setenv(k, v)
        try:
            from pylops_mpi_tpu.solvers import clear_fused_cache
            clear_fused_cache()
        except Exception:
            pass


def _grad_race_row():
    """Gradient race (autodiff-PR acceptance bar): d loss/d y through a
    fused CGLS solve, the implicit fixed-point rule (backward = ONE
    more fused solve, ``pylops_mpi_tpu/autodiff/implicit.py``) vs the
    unrolled scan-tape oracle (what reverse-mode gives everyone else —
    O(niter·n) residency). Both arms compile ``jit(grad(loss))`` once,
    then time 3 post-compile reps; the compiler's own
    ``memory_analysis().temp_size_in_bytes`` stamps each program's
    scratch residency (None when the backend does not report it).
    Agreement between the two gradients is stamped as
    ``max_rel_diff`` — the wall/memory win only counts on matching
    numbers. Error-isolated like every race row."""
    try:
        import numpy as _np
        import jax as _jax
        import jax.numpy as _jnp
        from pylops_mpi_tpu import DistributedArray, MPIBlockDiag
        from pylops_mpi_tpu.ops.local import MatrixMult
        from pylops_mpi_tpu.autodiff import cgls_solve, unrolled_cgls
        from pylops_mpi_tpu.solvers import clear_fused_cache

        rng = _np.random.default_rng(23)
        nblk = max(len(_jax.devices()), 2)
        bm, bn, niter = 48, 32, 60
        mats = [rng.standard_normal((bm, bn)) for _ in range(nblk)]
        Op = MPIBlockDiag([MatrixMult(m, dtype=_np.float64)
                           for m in mats])
        y = DistributedArray.to_dist(
            rng.standard_normal(nblk * bm))
        x0 = DistributedArray.to_dist(_np.zeros(nblk * bn))
        w = _jnp.asarray(rng.standard_normal(nblk * bn))
        damp = 1e-3

        def loss_implicit(y_):
            x = cgls_solve(Op, y_, x0, niter=niter, damp=damp,
                           tol=0.0)
            return _jnp.vdot(w, x._arr.ravel()).real

        def loss_unrolled(y_):
            x = unrolled_cgls(Op, y_, x0, niter=niter, damp=damp)
            return _jnp.vdot(w, x._arr.ravel()).real

        clear_fused_cache()
        out, grads, walls = {}, {}, {}
        for name, fn in (("implicit", loss_implicit),
                         ("unrolled", loss_unrolled)):
            compiled = _jax.jit(_jax.grad(fn)).lower(y).compile()
            g = compiled(y)
            _jax.block_until_ready(g._arr)    # compile/warm outside
            reps = 3
            t0 = time.perf_counter()
            for _ in range(reps):
                g = compiled(y)
                _jax.block_until_ready(g._arr)
            t = (time.perf_counter() - t0) / reps
            temp = None
            try:  # CPU backends may not report a memory analysis
                ma = compiled.memory_analysis()
                v = getattr(ma, "temp_size_in_bytes", None)
                temp = int(v) if v is not None else None
            except Exception:
                temp = None
            grads[name] = _np.asarray(g.asarray())
            walls[name] = t
            out[name] = {"temp_bytes": temp,
                         **_device_metrics(
                             wall_s=_sig3(t),
                             grads_per_sec=_sig3(1.0 / t))}
        scale = max(1.0, float(_np.max(_np.abs(grads["unrolled"]))))
        diff = float(_np.max(_np.abs(grads["implicit"]
                                     - grads["unrolled"]))) / scale
        ti, tu = walls["implicit"], walls["unrolled"]
        mi = out["implicit"]["temp_bytes"]
        mu = out["unrolled"]["temp_bytes"]
        return {
            "platform": _platform(),
            "problem": {"nblk": nblk, "bm": bm, "bn": bn,
                        "niter": niter, "dtype": "float64"},
            **out,
            **_device_metrics(
                grads_per_sec=_sig3(1.0 / ti),
                wall_speedup=_sig3(tu / ti) if ti else None),
            "temp_bytes_ratio": (_sig3(mu / mi)
                                 if mi and mu else None),
            "max_rel_diff": _sig3(diff),
            "grads_match": diff <= 1e-5,
        }
    except Exception as e:  # the race must never cost the headline
        return {"error": repr(e)[:300]}
    finally:
        try:
            from pylops_mpi_tpu.solvers import clear_fused_cache
            clear_fused_cache()
        except Exception:
            pass


# dense matmul peak per chip, TFLOP/s (bf16 inputs, f32 accumulation on
# the MXU) — public spec-sheet numbers; most-specific key checked first
_PEAK_TFLOPS = [
    ("v6e", 918.0), ("v6 lite", 918.0), ("v6", 918.0),
    ("v5p", 459.0), ("v5e", 197.0), ("v5 lite", 197.0), ("v5", 459.0),
    ("v4", 275.0), ("v3", 123.0), ("v2", 45.0),
]

# HBM bandwidth peak per chip, GB/s — public spec-sheet numbers. The
# denominator every `hbm_gbps` claim must be divided by before calling
# anything "at the roofline": round 5 reported 1261 GB/s on a chip
# whose HBM peaks at ~819 GB/s, which is physically impossible for an
# HBM-streaming workload and was actually a VMEM-resident working set
# (docs/design.md round-7 correction).
_PEAK_HBM_GBPS = [
    ("v6e", 1640.0), ("v6 lite", 1640.0), ("v6", 1640.0),
    ("v5p", 2765.0), ("v5e", 819.0), ("v5 lite", 819.0), ("v5", 2765.0),
    ("v4", 1228.0), ("v3", 900.0), ("v2", 700.0),
]


def _peak_hbm_gbps(device):
    """Per-chip HBM bandwidth peak, GB/s; an unknown ``device_kind``
    is an error, as in :func:`_peak_flops_per_chip`."""
    kind = (getattr(device, "device_kind", "") or "").lower()
    for key, gb in _PEAK_HBM_GBPS:
        if key in kind:
            return gb
    raise ValueError(
        f"no peak HBM GB/s on record for device_kind "
        f"{getattr(device, 'device_kind', None)!r}; add it to "
        "_PEAK_HBM_GBPS with its source")


def _vmem_budget_bytes() -> int:
    """Per-core VMEM assumed for the on-chip-residency check
    (override: PYLOPS_MPI_TPU_VMEM_BYTES). A per-device working set at
    or under this streams from VMEM after the first iteration, so its
    measured GB/s is NOT an HBM number — the round-5 'roofline' artifact
    (4 MB/device blocks at N=1024 'achieving' 1261 GB/s on an 819 GB/s
    chip)."""
    try:
        return int(os.environ.get("PYLOPS_MPI_TPU_VMEM_BYTES",
                                  str(16 << 20)))
    except ValueError:
        return 16 << 20


def _peak_flops_per_chip(device, mode: str = "bf16"):
    """Per-chip dense-matmul peak for ``mode``. The spec-sheet figures
    are bf16-input/f32-accumulate; f32 GEMMs under the package's
    ``jax_default_matmul_precision=highest`` pin run as 6 bf16 MXU
    passes (3 products × 2 operand splits), so the f32 peak is bf16/6 —
    MFU must be reported against the precision actually used, never
    f32 throughput against the bf16 ceiling. A ``device_kind`` the
    table does not hold is an error: a wrong peak is worse than none."""
    kind = (getattr(device, "device_kind", "") or "").lower()
    for key, tf in _PEAK_TFLOPS:
        if key in kind:
            peak = tf * 1e12
            return peak / 6.0 if mode.startswith("f32") else peak
    raise ValueError(
        f"no peak FLOP/s on record for device_kind "
        f"{getattr(device, 'device_kind', None)!r}; add it to "
        "_PEAK_TFLOPS with its source")


def _sig3(x):
    """3 significant digits — NEVER a fixed decimal count: tiny MFUs
    (~3e-5 at GEMV-bound solve sizes) must survive serialization, they
    ARE the diagnostic story (round-4 VERDICT weak #3)."""
    return None if x is None else float(f"{x:.3g}")


def make_problem(nblk, nblock, seed=0):
    """The flagship linear system, shared by the headline measurement
    and the subprocess NumPy baseline so the two can never
    desynchronize: diagonally-dominant blocks (cond ≈ 1 + 2/√N, so the
    solve demonstrates convergence, not just throughput), a known
    model, and its exact data.

    Blocks are quantized to the bf16 grid (exactly representable at
    both storage precisions): the f32 and bf16-storage rows then solve
    the IDENTICAL system, so any rel_err gap between them measures
    recurrence contamination (the dtype-stability property the fused
    solvers pin), not the ~2⁻⁹ representation rounding of random f32
    entries — which would otherwise floor the bf16 row at ~2e-3 no
    matter how clean the solver is. Conditioning and the f32 numbers
    are unaffected (the quantized blocks are the same random
    diagonally-dominant family)."""
    import ml_dtypes
    rng = np.random.default_rng(seed)
    blocks_np = []
    for _ in range(nblk):
        b = (rng.standard_normal((nblock, nblock))
             / np.sqrt(nblock)).astype(np.float32)
        np.fill_diagonal(b, b.diagonal() + 4.0)
        blocks_np.append(b.astype(ml_dtypes.bfloat16).astype(np.float32))
    xtrue = rng.standard_normal(nblk * nblock).astype(np.float32)
    y_np = np.concatenate([b @ xtrue[i * nblock:(i + 1) * nblock]
                           for i, b in enumerate(blocks_np)])
    return blocks_np, xtrue, y_np


def numpy_cgls_iters_per_sec_subprocess(nblk, nblock, seed=0, niter=10,
                                        timeout=600, k=5):
    """The NumPy stand-in timed in a CLEAN, jax-free subprocess (this
    module imports only NumPy at the top, so the child never touches
    the chip its parent holds): measuring it in the bench process —
    after XLA has claimed the host's thread pools — penalizes BLAS
    unpredictably (observed: 13.5 vs 8.4 iters/s run to run for the
    identical problem). The subprocess regenerates the same seeded
    blocks, so nothing large crosses the pipe. ``(None, None)`` when
    the child fails; the caller then times in-process.

    Returns ``(median_ips, stats-dict)`` over ``k`` repeats: a point
    estimate hid a noise band wider than the signal, so the artifact
    carries the dispersion and ``vs_baseline`` is trustworthy (or
    visibly not)."""
    code = (
        "import json, sys\n"
        "import numpy as np\n"
        "sys.path.insert(0, %r)\n"
        "import bench\n"
        "blocks, xt, y = bench.make_problem(%d, %d, seed=%d)\n"
        "rs = sorted(bench.numpy_cgls_iters_per_sec(blocks, y, niter=%d)"
        " for _ in range(%d))\n"
        "print(json.dumps({'median': float(np.median(rs)),"
        " 'min': rs[0], 'max': rs[-1]}))\n"
    ) % (os.path.dirname(os.path.abspath(__file__)), nblk, nblock, seed,
         niter, k)
    env = {k_: v for k_, v in os.environ.items()
           if not k_.startswith(("XLA_", "JAX_"))}
    try:
        p = subprocess.run([sys.executable, "-c", code],
                           capture_output=True, text=True, env=env,
                           timeout=timeout)
        for line in reversed((p.stdout or "").strip().splitlines()):
            if line.startswith("{"):
                st = json.loads(line)
                med = float(st["median"])
                spread = ((st["max"] - st["min"]) / med * 100.0
                          if med else 0.0)
                return med, {"median": round(med, 2),
                             "min": round(st["min"], 2),
                             "max": round(st["max"], 2),
                             "spread_pct": round(spread, 1), "k": k}
    except Exception:
        pass
    return None, None


def numpy_cgls_iters_per_sec(blocks, y, niter=10):
    """Reference-style CGLS: per-iteration host scalars, NumPy matvecs —
    mirrors pylops_mpi/optimization/cls_basic.py:370-404."""
    def matvec(x):
        return np.concatenate([b @ x[i * b.shape[1]:(i + 1) * b.shape[1]]
                               for i, b in enumerate(blocks)])

    def rmatvec(x):
        return np.concatenate([b.T @ x[i * b.shape[0]:(i + 1) * b.shape[0]]
                               for i, b in enumerate(blocks)])

    x = np.zeros(sum(b.shape[1] for b in blocks), dtype=y.dtype)
    s = y - matvec(x)
    r = rmatvec(s)
    c = r.copy()
    q = matvec(c)
    kold = float(np.abs(r @ r))
    t0 = time.perf_counter()
    for _ in range(niter):
        a = kold / float(q @ q)
        x += a * c
        s -= a * q
        r = rmatvec(s)
        k = float(np.abs(r @ r))
        c = r + (k / kold) * c
        q = matvec(c)
        kold = k
    return niter / (time.perf_counter() - t0)


def _race_rows(on_tpu):
    """The small-shape race rows. Each runs by default off the chip
    (where it reports counts and correctness only) and on the chip
    only when its ``BENCH_<NAME>_PYLOPS_MPI_TPU=1`` switch asks."""
    rows = {}
    for key, env, fn in (
            ("tune_race", "BENCH_TUNE_RACE_PYLOPS_MPI_TPU", _tune_race_row),
            ("batched", "BENCH_BATCHED_PYLOPS_MPI_TPU", _batched_race_row),
            ("serving", "BENCH_SERVING_PYLOPS_MPI_TPU", _serving_race_row),
            ("hierarchical_vs_flat", "BENCH_HIER_PYLOPS_MPI_TPU",
             _hier_race_row),
            ("spill_oversized", "BENCH_SPILL_PYLOPS_MPI_TPU",
             _spill_race_row),
            ("precond", "BENCH_PRECOND_PYLOPS_MPI_TPU", _precond_race_row),
            ("sparse_vs_dense", "BENCH_SPARSE_PYLOPS_MPI_TPU",
             _sparse_race_row),
            ("ca_vs_classic", "BENCH_CA_PYLOPS_MPI_TPU", _ca_race_row),
            ("grad_race", "BENCH_GRAD_PYLOPS_MPI_TPU", _grad_race_row)):
        want = os.environ.get(env, "")
        if want != "0" and (not on_tpu or want == "1"):
            print(f"[bench] {key}", file=sys.stderr, flush=True)
            rows[key] = fn()
    return rows


def main() -> int:
    """The measurement, in the calling process (one process holds the
    chip). Exits 2, naming the platform it found, when there is no TPU
    — unless ``JAX_PLATFORMS=cpu`` asked for the CPU, in which case
    every row says ``platform: cpu`` and carries counts and
    correctness only."""
    if _refuse_without_chip("bench.py"):
        return 2
    import jax
    import jax.numpy as jnp
    platform = jax.default_backend()
    on_tpu = platform == "tpu"

    here = os.path.dirname(os.path.abspath(__file__))
    sys.path.insert(0, here)
    # tracing on (PYLOPS_MPI_TPU_TRACE=spans|full) with no explicit
    # sink: land the Chrome-trace JSONL next to bench_detail.json so
    # the run always leaves an openable artifact
    if os.environ.get("PYLOPS_MPI_TPU_TRACE", "off") not in ("", "off"):
        os.environ.setdefault("PYLOPS_MPI_TPU_TRACE_FILE",
                              os.path.join(here, "bench_trace.jsonl"))
    import pylops_mpi_tpu as pmt
    from pylops_mpi_tpu import aot
    from pylops_mpi_tpu.ops.local import MatrixMult
    aot.maybe_enable_compile_cache(os.path.join(here, ".jax_cache"))

    def _progress(msg):
        print(f"[bench] {msg}", file=sys.stderr, flush=True)

    n_dev = len(jax.devices())
    mesh = pmt.make_mesh()
    pmt.set_default_mesh(mesh)

    nblk = max(n_dev, 1)
    nblock = int(os.environ.get("BENCH_NBLOCK_PYLOPS_MPI_TPU", "4096"))
    niter = int(os.environ.get("BENCH_NITER_PYLOPS_MPI_TPU", "50"))

    blocks_np, xtrue, y_np = make_problem(nblk, nblock, seed=0)
    dy = pmt.DistributedArray.to_dist(y_np, mesh=mesh)

    def measure(bf16: bool, fused_normal: bool):
        """One mode through the public ``pmt.cgls`` (the operator
        travels into the fused program as a pytree argument, never as
        a baked-in constant). Marginal-cost timing: solves of ``niter``
        and ``3*niter`` iterations, per-iteration time = slope between
        them, which cancels the per-solve dispatch and host-sync cost.
        Returns a dict with the correctness fields always and the
        clock-derived ones only on a TPU."""
        # explicit dtype: the env-level precision policy must not
        # silently flip the f32 row's storage (both modes are measured)
        Op = pmt.MPIBlockDiag(
            [MatrixMult(b, dtype=np.float32) for b in blocks_np],
            compute_dtype=jnp.bfloat16 if bf16 else np.float32)
        use_normal = bool(fused_normal and Op.has_fused_normal)
        reps = int(os.environ.get("BENCH_REPS_PYLOPS_MPI_TPU", "5"))

        def solve(nit):
            out = pmt.cgls(Op, dy, niter=nit, tol=0.0, normal=use_normal)
            jax.block_until_ready(out[0].array)
            return out

        def timed(nit):
            out = solve(nit)  # compile outside the timed window
            dts = []
            for _ in range(reps):
                t0 = time.perf_counter()
                out = solve(nit)
                dts.append(time.perf_counter() - t0)
            return min(dts), round((max(dts) - min(dts))
                                   / min(dts) * 100.0, 1), out

        t1, spread, out = timed(niter)
        t3, _, _ = timed(3 * niter)
        per_iter = (t3 - t1) / (2 * niter)
        if per_iter <= 0:  # noise swamped the slope: absolute timing
            per_iter = t3 / (3 * niter)
        rel_err = float(np.linalg.norm(out[0].asarray() - xtrue)
                        / np.linalg.norm(xtrue))
        itemsize = 2 if bf16 else 4
        sweeps = 1 if use_normal else 2
        storage = "bf16-storage" if bf16 else "f32"
        pmt.clear_fused_cache()  # drop the operator's device buffers
        return {
            "mode": f"{storage} "
                    f"{'fused-normal' if use_normal else 'two-sweep'}",
            "platform": platform,
            "rel_err": f"{rel_err:.1e}",
            "iterations": int(out[2]),
            "status": ("breakdown" if not np.isfinite(rel_err)
                       else "maxiter"),
            "restarts": 0,
            "itemsize": itemsize, "sweeps": sweeps,
            **_device_metrics(
                iters_per_sec=round(1.0 / per_iter, 2),
                # 2 GEMMs (matvec+rmatvec) per iteration, 2*N^2 flops
                # each per block
                gflops=round(4.0 * nblock * nblock * nblk
                             / per_iter / 1e9, 1),
                hbm_gbps=round(sweeps * nblock * nblock * nblk * itemsize
                               / per_iter / 1e9, 1),
                spread_pct=spread)}

    # Component configs run in this process, before the headline (one
    # process holds the chip; there is no isolated retry)
    components = []
    if os.environ.get("BENCH_COMPONENTS_PYLOPS_MPI_TPU", "1") != "0":
        from benchmarks.bench_components import run_components
        _progress("components")
        components = run_components(quick=not on_tpu)
        pmt.clear_fused_cache()

    # f32 is the primary row: vs_baseline compares against an f32 NumPy
    # solve and the BASELINE target is bit-meaningful CGLS convergence.
    # bf16 block storage (half the HBM traffic) is measured beside it,
    # fused-normal and two-sweep, so the bf16 two-sweep cliff of the
    # early rounds can never return unseen. BENCH_BF16_PYLOPS_MPI_TPU=0
    # skips bf16.
    _progress(f"headline f32 two-sweep (N={nblock}, {niter} iters)")
    f32 = measure(bf16=False, fused_normal=False)
    rows = {"f32": f32}
    if os.environ.get("BENCH_BF16_PYLOPS_MPI_TPU", "1") != "0":
        if on_tpu:
            _progress("bf16 fused-normal")
            rows["bf16"] = measure(bf16=True, fused_normal=True)
        _progress("bf16 two-sweep")
        rows["bf16_two_sweep"] = measure(bf16=True, fused_normal=False)

    result = {
        "metric": (f"CGLS iters/sec (BlockDiag MatrixMult, "
                   f"{nblk}x{nblock}^2, {n_dev} dev {platform}, "
                   f"{f32['mode']}, fused while_loop, marginal "
                   f"per-iter timing; rel_err={f32['rel_err']})"),
        "value": f32.get("iters_per_sec"),
        "unit": "iters/s",
        "platform": platform,
        "device_kind": jax.devices()[0].device_kind,
        "n_devices": n_dev,
        "nblock": nblock,
        "plan": _plan_provenance("blockdiag"),
        "spill": _spill_provenance(),
        "aot": _aot_provenance(),
        "status": f32["status"], "restarts": 0,
        **rows,
        "components": components,
        **_race_rows(on_tpu),
    }
    if on_tpu:
        # NumPy single-process stand-in for the reference CPU engine,
        # timed in a clean jax-free subprocess (fair BLAS threading)
        _progress("numpy baseline (jax-free subprocess, median-of-k)")
        cpu_ips, cpu_stats = numpy_cgls_iters_per_sec_subprocess(
            nblk, nblock, seed=0, niter=10)
        if cpu_ips is None:
            cpu_ips = numpy_cgls_iters_per_sec(blocks_np, y_np, niter=10)
            cpu_stats = {"note": "in-process fallback, single run"}
        from pylops_mpi_tpu.diagnostics import costmodel
        from pylops_mpi_tpu.solvers.ca import classic_reductions_per_iter
        dev = jax.devices()[0]
        peak_hbm = _peak_hbm_gbps(dev)
        ws_dev = nblk * nblock * nblock / max(n_dev, 1)
        for row in rows.values():
            peak = _peak_flops_per_chip(
                dev, "bf16" if row["itemsize"] == 2 else "f32_highest")
            row["mfu"] = _sig3(row["gflops"] * 1e9 / (peak * n_dev))
            row["vs_baseline"] = round(row["iters_per_sec"] / cpu_ips, 2)
            cost = costmodel.OpCost(
                flops=4.0 * nblock * nblock * nblk / n_dev,
                hbm_bytes=row["sweeps"] * ws_dev * row["itemsize"],
                ici_bytes=0.0, notes=("cgls.per_iteration",),
                reductions_per_iter=classic_reductions_per_iter("cgls"))
            rl = costmodel.roofline(
                cost, costmodel.device_peaks(
                    dev, mode="bf16" if row["itemsize"] == 2
                    else "f32_highest"),
                n_dev=n_dev, measured_s=1.0 / row["iters_per_sec"])
            row["roofline"] = _pick(rl, (
                "bound", "predicted_s", "regime", "implied_hbm_gbps",
                "hbm_pct"))
            if ws_dev * row["itemsize"] <= _vmem_budget_bytes():
                # a working set that fits VMEM streams from it after
                # the first iteration: its GB/s is not an HBM number
                row["on_chip_resident"] = \
                    "on-chip-resident — not an HBM measurement"
            else:
                row["hbm_pct"] = round(
                    100.0 * row["hbm_gbps"] / (peak_hbm * n_dev), 1)
        result.update(
            vs_baseline=f32["vs_baseline"], mfu=f32["mfu"],
            gflops=f32["gflops"], hbm_gbps=f32["hbm_gbps"],
            numpy_baseline_iters_per_sec=round(cpu_ips, 2),
            numpy_baseline_stats=cpu_stats,
            peak_tflops={
                "bf16": _peak_flops_per_chip(dev, "bf16") / 1e12,
                "f32_highest": round(
                    _peak_flops_per_chip(dev, "f32_highest") / 1e12, 1)},
            peak_hbm_gbps={"per_chip": peak_hbm,
                           "aggregate": round(peak_hbm * n_dev, 1)})
        for k in ("hbm_pct", "on_chip_resident"):
            if k in f32:
                result[k] = f32[k]
    else:
        result["device_metrics"] = "not measured (platform cpu)"
    _emit_final(result)
    return 0


def _emit_final(result):
    """Write the FULL artifact to ``bench_detail.json`` and print a
    compact (≤2 KB) summary as the LAST stdout line."""
    root = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(root, "bench_detail.json"), "w") as f:
        json.dump(result, f, indent=1)
    print(json.dumps(_compact_line(result)))


def _pick(row, keys):
    return {k: row[k] for k in keys if row.get(k) is not None}


def _compact_line(result):
    """The ≤2 KB summary dict for the one stdout line: the headline,
    then per row the few fields that say whether it ran and was right
    (and, on a TPU, how fast)."""
    compact = _pick(result, (
        "metric", "value", "unit", "platform", "device_kind",
        "n_devices", "nblock", "status", "restarts", "plan", "spill",
        "aot", "vs_baseline", "mfu", "hbm_gbps", "gflops", "hbm_pct",
        "on_chip_resident", "numpy_baseline_iters_per_sec",
        "device_metrics"))
    compact["detail_file"] = "bench_detail.json"
    for name in ("f32", "bf16", "bf16_two_sweep"):
        if name in result:
            compact[name] = _pick(result[name], (
                "mode", "rel_err", "status", "iters_per_sec",
                "vs_baseline", "hbm_gbps", "hbm_pct",
                "on_chip_resident"))
    brief = {
        "tune_race": ("worst_tuned_vs_default",
                      "best_tuned_vs_costmodel"),
        "batched": ("K", "block_vs_sequential_max_abs_diff",
                    "batch_plan", "speedup_vs_sequential"),
        "serving": ("K", "daemon_vs_sequential_max_abs_diff",
                    "batches", "solves_per_sec", "wait_p99_s"),
        "hierarchical_vs_flat": ("worst_dcn_reduction",),
        "spill_oversized": ("off_refuses", "bit_identical_vs_oracle",
                            "bytes_accounting_ok",
                            "cost_model_under_budget",
                            "overlap_speedup"),
        "precond": ("bj_iters_ratio", "vc_iters_ratio",
                    "bj_wall_speedup", "vc_wall_speedup"),
        "sparse_vs_dense": ("density", "bytes_ratio", "max_abs_diff",
                            "sparse_vs_dense_wall"),
        "ca_vs_classic": ("reductions_per_iter", "iters_parity",
                          "wall_speedup"),
        "grad_race": ("temp_bytes_ratio", "max_rel_diff", "grads_match",
                      "wall_speedup"),
    }
    for name, keys in brief.items():
        row = result.get(name)
        if row:
            compact[name] = ({"error": row["error"][:120]}
                             if row.get("error") else _pick(row, keys))
    comps = [c for c in result.get("components", [])
             if isinstance(c, dict)]
    if comps:
        failed = [c.get("bench") for c in comps if c.get("error")]
        compact["components"] = {"n": len(comps),
                                 **({"failed": failed} if failed else {})}
    # hard ≤2KB guarantee: shed optional detail, most-expendable first
    for victim in ("components", *brief, "bf16_two_sweep", "bf16", "f32"):
        if len(json.dumps(compact)) <= 2000:
            break
        compact.pop(victim, None)
    return compact


if __name__ == "__main__":
    sys.exit(main())
