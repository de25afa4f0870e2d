"""Offline tuning sweep: ``python -m pylops_mpi_tpu.tuning``.

Measures the flagship plan spaces shape-by-shape and banks the
winners into a JSON plan cache (``--out``, or
``PYLOPS_MPI_TPU_TUNE_CACHE``), so later sessions with
``PYLOPS_MPI_TPU_TUNE=on`` replay measured plans for free. The CI
tuning leg seeds its cache with ``--quick`` before running the suites.

Output contract: progress goes to stderr; the LAST stdout line is one
compact JSON summary, stamped per-family with the winning params and their
provenance. ``--defaults`` banks cost-model picks without timing a
single trial (a cheap way to pre-seed a cache that exactly matches
today's behavior).
"""

from __future__ import annotations

import argparse
import json
import sys
import time


def _eprint(msg: str) -> None:
    print(f"[tune] {msg}", file=sys.stderr, flush=True)


def _block(x):
    import jax
    return jax.block_until_ready(x)


# ------------------------------------------------------------- factories
def _summa_case(N, K, M, mesh):
    import numpy as np
    from ..distributedarray import DistributedArray
    from ..ops.matrixmult import _MPISummaMatrixMult

    A = np.linspace(-1.0, 1.0, N * K, dtype=np.float32).reshape(N, K)
    x = np.linspace(-1.0, 1.0, K * M, dtype=np.float32)

    def factory(params):
        op = _MPISummaMatrixMult(A, M, mesh=mesh, dtype=np.float32,
                                 schedule=params["schedule"],
                                 overlap=params["overlap"])
        dx = DistributedArray.to_dist(x, mesh=mesh)
        return lambda: _block(op.matvec(dx).array)

    return factory


def _fft_case(dims, mesh):
    import numpy as np
    from ..distributedarray import DistributedArray
    from ..ops.fft import MPIFFT2D

    x = np.linspace(-1.0, 1.0, int(np.prod(dims)), dtype=np.float64)

    def factory(params):
        op = MPIFFT2D(dims, mesh=mesh, overlap=params["overlap"],
                      comm_chunks=max(1, int(params["comm_chunks"])))
        dx = DistributedArray.to_dist(
            x, mesh=mesh, local_shapes=op.model_local_shapes)
        return lambda: _block(op.matvec(dx).array)

    return factory


def _blockdiag_case(nblk, n, mesh):
    import numpy as np
    from ..distributedarray import DistributedArray
    from ..ops.blockdiag import MPIBlockDiag
    from ..ops.local import MatrixMult

    mats = [np.linspace(-1.0, 1.0, n * n, dtype=np.float32)
            .reshape(n, n) + np.eye(n, dtype=np.float32) * (i + 1)
            for i in range(nblk)]
    x = np.linspace(-1.0, 1.0, nblk * n, dtype=np.float32)

    def factory(params):
        op = MPIBlockDiag([MatrixMult(m) for m in mats], mesh=mesh,
                          normal_path=params["normal_path"])
        dx = DistributedArray.to_dist(x, mesh=mesh)
        return lambda: _block(op.normal_matvec(dx)[0].array)

    return factory


def _stack_case(nblk, n, mesh):
    import numpy as np
    from ..distributedarray import DistributedArray, Partition
    from ..ops.stack import MPIVStack
    from ..ops.local import MatrixMult

    mats = [np.linspace(-1.0, 1.0, n * n, dtype=np.float32).reshape(n, n)
            for _ in range(nblk)]
    y = np.linspace(-1.0, 1.0, nblk * n, dtype=np.float32)

    def factory(params):
        op = MPIVStack([MatrixMult(m) for m in mats], mesh=mesh,
                       overlap=params["overlap"])
        dy = DistributedArray.to_dist(y, mesh=mesh)
        return lambda: _block(op.rmatvec(dy).array)

    return factory


def _derivative_case(dims, mesh):
    import numpy as np
    from ..distributedarray import DistributedArray
    from ..ops.derivatives import MPIFirstDerivative

    x = np.linspace(-1.0, 1.0, int(np.prod(dims)))

    def factory(params):
        op = MPIFirstDerivative(dims, mesh=mesh,
                                overlap=params["overlap"])
        dx = DistributedArray.to_dist(x, mesh=mesh)
        return lambda: _block(op.matvec(dx).array)

    return factory


def _halo_case(dims, mesh):
    import numpy as np
    from ..distributedarray import DistributedArray
    from ..ops.halo import MPIHalo

    x = np.linspace(-1.0, 1.0, int(np.prod(dims)))

    def factory(params):
        op = MPIHalo(dims, 2, mesh=mesh, overlap=params["overlap"])
        dx = DistributedArray.to_dist(x, mesh=mesh)
        return lambda: _block(op.matvec(dx).array)

    return factory


# --------------------------------------------------------------- the sweep
def _shape_sets(quick: bool):
    """(family, shape-label, context-shape, factory-builder, extras).
    Quick = CPU-sim-sized (CI seeding, ladder rehearsal); full = the
    flagship-adjacent sizes worth a TPU window's time."""
    if quick:
        return {
            "matrixmult": [(48, 64, 8), (64, 48, 32)],
            "fft": [(64, 32)],
            "blockdiag": [(8, 32)],
            "stack": [(8, 32)],
            "derivative": [(64, 16)],
            "halo": [(64, 16)],
        }
    return {
        "matrixmult": [(2048, 2048, 64), (4096, 4096, 64),
                       (1024, 4096, 64)],
        "fft": [(512, 512), (1024, 256)],
        "blockdiag": [(8, 1024), (8, 2048)],
        "stack": [(8, 1024)],
        "derivative": [(4096, 512)],
        "halo": [(4096, 512)],
    }


def run_sweep(out_path, quick=False, defaults_only=False,
              families=None, repeats=3):
    from ..utils.deps import apply_environment
    apply_environment()
    import jax
    from ..parallel.mesh import default_mesh
    from . import cache, plan, search, space

    mesh = default_mesh()
    n_dev = int(mesh.devices.size)
    platform = jax.default_backend()
    shapes = _shape_sets(quick)
    families = families or list(shapes)
    summary = {"bench": "tune_sweep", "platform": platform,
               "n_devices": n_dev, "quick": bool(quick),
               "defaults_only": bool(defaults_only), "plans": []}

    for fam in families:
        sp = space.space_for(fam)
        if sp is None:
            continue
        for shape in shapes.get(fam, []):
            t0 = time.time()
            try:
                entry = _tune_one(fam, shape, mesh, n_dev, platform, sp,
                                  out_path, defaults_only, repeats)
            except Exception as e:  # one bad case must not end the sweep
                entry = {"family": fam, "shape": list(shape),
                         "error": repr(e)[:300]}
            entry["seconds"] = round(time.time() - t0, 2)
            summary["plans"].append(entry)
            _eprint(f"{fam} {shape}: "
                    f"{entry.get('params', entry.get('error'))} "
                    f"[{entry.get('provenance', '-')}] "
                    f"{entry['seconds']}s")
    summary["cache"] = out_path or cache.cache_path() or "(memory only)"
    return summary


def _tune_one(fam, shape, mesh, n_dev, platform, sp, out_path,
              defaults_only, repeats):
    import numpy as np
    from . import cache, plan, search, space

    extra = {}
    if fam == "matrixmult":
        from ..parallel.mesh import best_grid_2d
        grid = best_grid_2d(n_dev)
        extra = {"grid": grid}
        factory = _summa_case(*shape, mesh)
        ctx_shape, dtype = shape, np.float32
    elif fam == "fft":
        factory = _fft_case(shape, mesh)
        ctx_shape, dtype = shape, np.complex128
    elif fam == "blockdiag":
        nblk, n = shape
        factory = _blockdiag_case(nblk, n, mesh)
        ctx_shape, dtype = (nblk * n, nblk * n), np.float32
        extra = {"fused_available": True,
                 "a_bytes": float(nblk * n * n * 4)}
    elif fam == "stack":
        nblk, n = shape
        factory = _stack_case(nblk, n, mesh)
        ctx_shape, dtype = (nblk * n, n), np.float32
    elif fam == "derivative":
        factory = _derivative_case(shape, mesh)
        ctx_shape, dtype = shape, np.float64
    elif fam == "halo":
        factory = _halo_case(shape, mesh)
        ctx_shape, dtype = shape, np.float64
    else:
        raise ValueError(f"unknown family {fam!r}")

    key = plan.plan_key(fam, ctx_shape, dtype, n_dev,
                        tuple(mesh.axis_names), extra)
    ctx = {"op": fam, "shape": tuple(int(s) for s in ctx_shape),
           "dtype": dtype, "n_dev": n_dev,
           "axes": tuple(mesh.axis_names), "platform": platform,
           "chip": plan._chip_kind()[1], "extra": extra}
    if defaults_only:
        params = space.rank(sp, ctx)[0]
        provenance, trials = "costmodel", []
    else:
        params, trials = search.measure_candidates(sp, ctx, factory,
                                                   repeats=repeats)
        provenance = "tuned"
        if params is None:
            params = space.rank(sp, ctx)[0]
            provenance = "costmodel"
    cache.store(key, {"params": params, "provenance": provenance,
                      "trials": trials, "created_s": time.time()},
                path=out_path)
    return {"family": fam, "shape": list(shape), "key": key,
            "params": params, "provenance": provenance,
            "n_trials": sum(1 for t in trials if t.get("ok"))}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m pylops_mpi_tpu.tuning",
        description="Offline autotuning sweep; banks a plan-cache "
                    "artifact (see docs/tuning.md)")
    ap.add_argument("--out", default=None,
                    help="cache file to bank plans into (default: "
                         "$PYLOPS_MPI_TPU_TUNE_CACHE)")
    ap.add_argument("--quick", action="store_true",
                    help="small CPU-sim shapes (CI seeding)")
    ap.add_argument("--defaults", action="store_true",
                    help="bank cost-model picks without measuring")
    ap.add_argument("--ladder", action="store_true",
                    help="quick shapes off-TPU, full shapes on a TPU")
    ap.add_argument("--family", action="append", default=None,
                    help="limit to one family (repeatable)")
    ap.add_argument("--repeats", type=int, default=3)
    args = ap.parse_args(argv)

    quick = args.quick
    if args.ladder and not quick:
        from ..utils.deps import apply_environment
        apply_environment()
        import jax
        quick = jax.default_backend() != "tpu"
    summary = run_sweep(args.out, quick=quick,
                        defaults_only=args.defaults,
                        families=args.family, repeats=args.repeats)
    print(json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
