#!/usr/bin/env python3
"""Compile the ``lsm_kirchhoff`` cell's programs for ONE chip of a
DESCRIBED v5e:2x2 topology, here, without the chip (on-chip-measurement
guide, section 2, rehearsal 3). A scratch script run by hand, never
imported:

    JAX_PLATFORMS=cpu python3 chipbench/scratch/compile_lsm_topology.py \
        [--what solver|tables|kernels|reference|control] [--ns 8 --nr 256 ...]

``tables``: the program ``models/lsm.py::_tables`` that makes the packed
per-pair tables from the per-point travel times (8.59 GB of outputs:
any temporary of a quarter of that is a fault). ``kernels``: one
forward and one adjoint apply of ``TravelTimeSpray`` alone
(``pmt_kirchhoff`` / ``pmt_kirchhoff_adj``). ``solver``: the program
``pmt.cgls`` runs for ``MPILSM`` (the fused two-sweep CGLS loop, ``--niter``
iterations, a caller's zero ``x0``) with the operator as a pytree
ARGUMENT whose tables are abstract, as in the real program; the text
may hold no table-sized constant. ``reference`` / ``control``: the
builder's plain solve on its own per-point travel times
(``banded_spray`` of ``--width``; 0: the scatter form) and its
bfloat16-product twin. Prints
``memory_analysis()`` in tables and in data vectors, the kernels in the
program, and every instruction outside the fusions whose result is a
quarter of the tables or more. A compile that passes is not a chip run.
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

ITEM = {"f32": 4, "bf16": 2, "s32": 4, "pred": 1, "u32": 4}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--what", default="solver",
                    choices=("solver", "tables", "kernels", "reference",
                             "control"))
    for k, v in (("ns", 8), ("nr", 256), ("nz", 512), ("nx", 1024),
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
    mesh = Mesh(np.array(topo.devices[:1]), ("sp",))
    rep = NamedSharding(mesh, P())

    import pylops_mpi_tpu as pmt
    from pylops_mpi_tpu.distributedarray import DistributedArray
    import importlib
    # the package's name ``lsm`` is the function; this is the module
    M = importlib.import_module("pylops_mpi_tpu.models.lsm")
    from pylops_mpi_tpu.ops import local, pallas_kernels
    from pylops_mpi_tpu.ops.stack import MPIVStack
    from pylops_mpi_tpu.solvers import basic
    from chipbench.builders import lsm as B
    pallas_kernels._interpret = lambda: False     # as on a TPU

    sizes = dict(B.DEFAULT_SIZES, ns=a.ns, nr=a.nr, nz=a.nz, nx=a.nx,
                 nt=a.nt)
    geo = B.geometry(sizes)
    pairs, npix = a.ns * a.nr, a.nz * a.nx
    order = M._BlockOrder((a.nz, a.nx))
    npad = order.shape[0]
    tables = 8 * pairs * npad
    vec = 4 * pairs * a.nt

    def S(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=rep)

    # the packed tables' shapes, from the packing itself
    packed = jax.eval_shape(
        lambda i, w, ok: M._pack(i, w, ok, last=a.nt - 2),
        S((pairs, npad), jnp.int32), S((pairs, npad)), S((npad,), bool))
    it, wt, lohi = (S(p.shape, p.dtype) for p in packed[:3])

    def vector(n, part):
        aux = (mesh, part, 0, (n,), pmt.local_split((n,), 1, part, 0), None)
        return DistributedArray.tree_unflatten(aux, [S((n,))])

    def operator():
        spray = M.TravelTimeSpray._from_packed(
            (it, wt, lohi, 0, 0), pairs, npad, a.nt, 2, np.float32)
        conv = local.Conv1D(spray.dimsd, geo.wav, axis=-1, offset=geo.wavc,
                            dtype=np.float32)
        return MPIVStack([conv * spray * order], mesh=mesh), spray

    if a.what == "tables":
        fn = jax.jit(lambda s, r, pix, ok, v, dt: M._tables(
            s, r, pix, ok, v, dt, nt=a.nt))
        args = (S((a.ns, 2)), S((a.nr, 2)), S((npad, 2)), S((npad,), bool),
                S(()), S(()))
    elif a.what == "kernels":
        _, spray = operator()
        fn = jax.jit(lambda op, m, z: (op.matvec(m), op.rmatvec(z)))
        args = (spray, S((npad,)), S((pairs * a.nt,)))
    elif a.what == "solver":
        Op, _ = operator()
        fn = jax.jit(lambda op, y, x0, damp, tol: basic._cgls_fused(
            op, y, x0, damp, tol, niter=a.niter))
        args = (Op, vector(pairs * a.nt, pmt.Partition.SCATTER),
                vector(npix, pmt.Partition.BROADCAST), jnp.float32(0),
                jnp.float32(0))
    else:
        fn = B.plain_solve(sizes, a.niter, width=a.width or None, **(
            B.CONTROLS["bf16"] if a.what == "control" else {})).solve
        nruns = int(np.prod(B.padded(sizes))) // B.RUN
        times = {"ts": S((a.ns, nruns, B.RUN)), "tr": S((a.nr, nruns, B.RUN)),
                 "inside": S((nruns, B.RUN), bool), "dt": S(())}
        args = (times, S((pairs * a.nt,)))

    t0 = time.perf_counter()
    compiled = fn.lower(*args).compile()
    print(f"{a.what}: compiled for {topo.devices[0].device_kind} in "
          f"{time.perf_counter() - t0:.1f}s; the tables are {tables} "
          f"bytes, a data vector {vec}")
    ma = compiled.memory_analysis()
    print("memory_analysis:", ma)
    for unit, size in (("tables", tables), ("data vectors", vec)):
        print("in %s: arguments %.3f, outputs %.3f, aliased %.3f, "
              "temporaries %.3f" % ((unit,) + tuple(
                  b / size for b in (ma.argument_size_in_bytes,
                                     ma.output_size_in_bytes,
                                     ma.alias_size_in_bytes,
                                     ma.temp_size_in_bytes))))
    hlo = compiled.as_text()
    if a.dump:
        with open(a.dump, "w") as f:
            f.write(hlo)
    print("kernels:", sorted(set(re.findall(
        r'kernel_name[\\"=:\s]+(pmt_\w+)', hlo))) or len(re.findall(
            r"tpu_custom_call", hlo)))
    big, fused = {}, False
    for line in hlo.split("\n"):
        if line and not line.startswith(" "):
            fused = "fused_computation" in line \
                or "fusion" in line.split("(")[0]
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = (\w+)\[([\d,]+)\]\S* "
                     r"(\w[\w\-]*)\(", line)
        if not m or m.group(2) not in ITEM or fused:
            continue
        size = ITEM[m.group(2)] * int(np.prod(
            [int(d) for d in m.group(3).split(",")]))
        if size >= tables // 8 and m.group(4) not in (
                "parameter", "get-tuple-element", "bitcast"):
            key = f"{m.group(4)} {m.group(2)}[{m.group(3)}]"
            big[key] = big.get(key, 0) + 1
    print("instructions whose result is an eighth of the tables or more:",
          big or "none")
    consts = [int(np.prod([int(d) for d in dims.split(",")]))
              for dims in re.findall(r"= \w+\[([\d,]+)\]\S* constant\(", hlo)]
    print("largest constant, elements:", max(consts, default=0))
    return 0


if __name__ == "__main__":
    sys.exit(main())
