#!/usr/bin/env python3
"""Compile the ``lsm_kirchhoff_line`` cell's solver for the four chips of a
described v5e:2x2 topology on a host without TPUs (ahead-of-time compile
from a topology description). A scratch script run by hand, never imported:

    JAX_PLATFORMS=cpu python3 chipbench/scratch/compile_lsm_line_topology.py \
        [--what solver|reference] [--ns 32 --nr 256 ...] [--dump FILE]

``solver``: the program ``pmt.cgls`` runs for ``MPILSM`` on the four
chips — the fused CGLS loop over a sharded ``MPIVStack`` (one block a
chip, the stacked tables a pytree ARGUMENT sharded over the mesh, each
block under ``shard_map``) with a ``SCATTER`` data carry and a
``BROADCAST`` model carry. The stack is built as ``MPILSM`` builds it,
from abstract tables: the two calls that need real buffers (the check
that leaves are arrays, the in-place assembly of the per-chip tables)
are answered abstractly. ``reference``: the builder's plain solve
sharded over the chips (``builders/lsm_line.py::line_solve``). Prints
``memory_analysis()`` a chip (in one chip's tables and in data vectors),
every collective of the loop body with its operand shapes and scope,
and the largest constant. A compile that passes is not a chip run.
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

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")


def loop_body(hlo: str) -> str:
    """The text of the computations a ``while``'s ``body=`` names."""
    bodies = set(re.findall(r"body=%?([\w.\-]+)", hlo))
    out, keep = [], False
    for line in hlo.split("\n"):
        if line and not line.startswith(" "):
            name = line.split(" ")[0].lstrip("%")
            keep = name in bodies
        if keep:
            out.append(line)
    return "\n".join(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--what", default="solver",
                    choices=("solver", "reference"))
    for k, v in (("ns", 32), ("nr", 256), ("nz", 512), ("nx", 1024),
                 ("nt", 1024), ("niter", 10), ("width", 40)):
        ap.add_argument("--" + k, type=int, default=v)
    ap.add_argument("--dump", default=None, help="write the HLO here")
    a = ap.parse_args(argv)

    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    jax.config.update("jax_enable_compilation_cache", False)

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = Mesh(np.array(topo.devices[:4]), ("sp",))
    chips = 4
    rep, rows = NamedSharding(mesh, P()), NamedSharding(mesh, P("sp"))

    import importlib
    import pylops_mpi_tpu as pmt
    from pylops_mpi_tpu import linearoperator
    from pylops_mpi_tpu.distributedarray import DistributedArray
    from pylops_mpi_tpu.ops import local, pallas_kernels
    from pylops_mpi_tpu.ops.stack import MPIVStack
    from pylops_mpi_tpu.parallel import mesh as pmesh
    from pylops_mpi_tpu.solvers import basic
    from chipbench.builders import lsm as B, lsm_line
    M = importlib.import_module("pylops_mpi_tpu.models.lsm")
    pallas_kernels._interpret = lambda: False     # as on a TPU

    sizes = dict(B.DEFAULT_SIZES, ns=a.ns, nr=a.nr, nz=a.nz, nx=a.nx,
                 nt=a.nt)
    geo = B.geometry(sizes)
    pairs, npix = a.ns * a.nr, a.nz * a.nx
    order = M._BlockOrder((a.nz, a.nx))
    npad = order.shape[0]
    share = pairs // chips
    tables = 8 * share * npad                    # one chip's
    vec = 4 * share * a.nt                       # one chip's data

    def S(shape, dtype=jnp.float32, sharding=rep):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    if a.what == "solver":
        packed = jax.eval_shape(
            lambda i, w, ok: M._pack(i, w, ok, last=a.nt - 2),
            S((share, npad), jnp.int32), S((share, npad)), S((npad,), bool))
        it, wt, lohi = (S(p.shape, p.dtype) for p in packed[:3])

        def block():
            spray = M.TravelTimeSpray._from_packed(
                (it, wt, lohi, 0, 0), share, npad, a.nt, 2, np.float32)
            conv = local.Conv1D(spray.dimsd, geo.wav, axis=-1,
                                offset=geo.wavc, dtype=np.float32)
            return conv * spray * order

        # abstract tables: the stack's two calls that need buffers
        linearoperator.operator_is_jit_arg = lambda op: True
        pmesh.concat_sharded = lambda parts, m: S(
            (len(parts) * parts[0].shape[0],) + tuple(parts[0].shape[1:]),
            parts[0].dtype, rows)
        Op = MPIVStack([block() for _ in range(chips)], mesh=mesh)
        assert Op.form == "sharded", Op.form

        def vector(n, part):
            aux = (mesh, part, 0, (n,),
                   pmt.local_split((n,), chips, part, 0), None)
            return DistributedArray.tree_unflatten(aux, [S(
                (n,), sharding=rows if part == pmt.Partition.SCATTER
                else rep)])

        fn = jax.jit(lambda op, y, x0, damp, tol: basic._cgls_fused(
            op, y, x0, damp, tol, niter=a.niter))
        args = (Op, vector(pairs * a.nt, pmt.Partition.SCATTER),
                vector(npix, pmt.Partition.BROADCAST), jnp.float32(0),
                jnp.float32(0))
    else:
        fn = lsm_line.line_solve(sizes, mesh, a.niter, a.width).solve
        nruns = int(np.prod(B.padded(sizes))) // B.RUN
        times = {"ts": S((a.ns, nruns, B.RUN), sharding=rows),
                 "tr": S((a.nr, nruns, B.RUN)),
                 "inside": S((nruns, B.RUN), bool), "dt": S(())}
        args = (times, S((pairs * a.nt,), sharding=rows))

    t0 = time.perf_counter()
    compiled = fn.lower(*args).compile()
    print(f"{a.what}: compiled for {chips} x {topo.devices[0].device_kind} "
          f"in {time.perf_counter() - t0:.1f}s; a chip's tables are "
          f"{tables} bytes, its data vector {vec}")
    ma = compiled.memory_analysis()
    print("memory_analysis (a chip):", ma)
    for unit, size in (("a chip's tables", tables), ("data vectors", vec)):
        print("in %s: arguments %.4f, outputs %.4f, aliased %.4f, "
              "temporaries %.4f" % ((unit,) + tuple(
                  b / size for b in (ma.argument_size_in_bytes,
                                     ma.output_size_in_bytes,
                                     ma.alias_size_in_bytes,
                                     ma.temp_size_in_bytes))))
    hlo = compiled.as_text()
    if a.dump:
        with open(a.dump, "w") as f:
            f.write(hlo)
    body = loop_body(hlo)
    for line in body.split("\n"):
        m = re.match(r"\s*(?:ROOT )?%?[\w.\-]+ = (.*?) ("
                     + "|".join(COLLECTIVES) + r")(?:-start)?\(", line)
        if m:
            scope = re.search(r'op_name="([^"]*)"', line)
            print(f"loop body: {m.group(2)} {m.group(1)} "
                  f"{scope.group(1).split('/')[-2:] if scope else ''}")
    consts = [int(np.prod([int(d) for d in dims.split(",")]))
              for dims in re.findall(r"= \w+\[([\d,]+)\]\S* constant\(", hlo)]
    print("largest constant, elements:", max(consts, default=0))
    return 0


if __name__ == "__main__":
    sys.exit(main())
