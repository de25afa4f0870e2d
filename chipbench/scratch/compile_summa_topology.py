#!/usr/bin/env python3
"""Compile the SUMMA cell's solver program for a DESCRIBED v5e:2x2
topology, here, without the chip (on-chip-measurement guide, section 2,
rehearsal 3). A scratch script run by hand, never imported:

    JAX_PLATFORMS=cpu python3 chipbench/scratch/compile_summa_topology.py \
        --n 8192 [--m 64]

It builds ``pmt.MPIMatrixMult(kind="summa")`` over a mesh of the
described devices, lowers the program ``pmt.cgls`` runs for it (the
fused CGLS loop, 30 iterations) with abstract right-hand sides, compiles
it with the TPU compiler and prints ``memory_analysis()``, the seconds
it took and the collectives in ``as_text()``.

Two things are steered here, in the script, because nothing runs and
the process sees the CPU: ``jax.device_put`` inside the operator's
constructor is made the identity (an array cannot be placed on a
described device), and ``overlap=True`` is passed (``overlap=auto``
resolves to ON on a real TPU backend, ``utils/deps.py``). A compile
that passes is not a chip run.
"""

from __future__ import annotations

import argparse
import os
import re
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--n", type=int, required=True, help="N = K of A")
    ap.add_argument("--m", type=int, default=64, help="columns of X")
    ap.add_argument("--niter", type=int, default=30)
    args = ap.parse_args(argv)

    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    jax.config.update("jax_enable_compilation_cache", False)

    N = K = args.n
    M = args.m
    grid = (2, 2)

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = Mesh(np.array(topo.devices).reshape(-1), ("sp",))

    import pylops_mpi_tpu as pmt
    from pylops_mpi_tpu.solvers import basic
    from pylops_mpi_tpu.distributedarray import DistributedArray

    t0 = time.perf_counter()
    rng = np.random.default_rng(0)
    A = rng.standard_normal((N, K), dtype=np.float32)
    A *= np.float32(1 / np.sqrt(K))
    real_put = jax.device_put
    jax.device_put = lambda x, *a, **k: x
    try:
        Op = pmt.MPIMatrixMult(A, M=M, kind="summa", mesh=mesh,
                               dtype=np.float32, grid=grid, overlap=True)
    finally:
        jax.device_put = real_put
    print(f"operator: schedule={Op.schedule} overlap={Op.overlap} "
          f"A {N}x{K} f32 ({A.nbytes} bytes), "
          f"built in {time.perf_counter() - t0:.1f}s", flush=True)

    def abstract(n):
        tpl = DistributedArray.__new__(DistributedArray)
        aux = (mesh, pmt.Partition.SCATTER, 0, (n,),
               pmt.local_split((n,), 4, pmt.Partition.SCATTER, 0), None)
        return DistributedArray.tree_unflatten(aux, [jax.ShapeDtypeStruct(
            (n,), jnp.float32, sharding=NamedSharding(mesh, P("sp")))])

    fn = jax.jit(lambda y, x0, damp, tol: basic._cgls_fused(
        Op, y, x0, damp, tol, niter=args.niter))
    t0 = time.perf_counter()
    lowered = fn.lower(abstract(N * M), abstract(K * M),
                       jnp.float32(0), jnp.float32(0))
    t_lower = time.perf_counter() - t0
    print(f"lowered in {t_lower:.1f}s", flush=True)
    if A.nbytes <= 1 << 26:       # the text of a gigabyte literal is two
        text = lowered.as_text()
        big = re.findall(r"dense<\"0x[0-9A-F]{1000000,}\"> : "
                         r"tensor<(\d+x\d+xf32)>", text)
        print(f"StableHLO text {len(text)} bytes; literal constants of "
              f"half a megabyte or more: {big}", flush=True)
        del text
    t0 = time.perf_counter()
    compiled = lowered.compile()
    t_comp = time.perf_counter() - t0
    print(f"compiled for {topo.devices[0].device_kind} x4 in "
          f"{t_comp:.1f}s")
    print("memory_analysis:", compiled.memory_analysis())
    hlo = compiled.as_text()
    names = re.findall(r"\b(all-reduce|all-gather|reduce-scatter|"
                       r"collective-permute|all-to-all)(-start)?\b", hlo)
    count = {}
    for n, _ in names:
        count[n] = count.get(n, 0) + 1
    print("collectives in the compiled program:", count)
    return 0


if __name__ == "__main__":
    sys.exit(main())
