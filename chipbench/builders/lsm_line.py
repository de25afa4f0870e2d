"""Deployment builder ``lsm_line``: the whole 2-D line of ``lsm`` (upstream
``tutorials/lsm.py``) on a mesh of several chips, its shots dealt over
them in order — ``pmt.models.MPILSM(z, x, t, sources, recs, vel, wav,
wavc)`` with upstream's arguments and nothing else, the reflectivity
``Partition.BROADCAST``, the data ``Partition.SCATTER`` over shots, CGLS
from zero.

Everything of the survey, the operator written out, the family, the
limits and the plain reference's arithmetic is ``builders/lsm.py``'s,
imported. This module adds three things:

- **the plain reference sharded by shots** (:func:`line_system`,
  :func:`line_solve`): ``lsm``'s plain forward and adjoint under
  ``shard_map``, each chip on its own shots' per-point travel times
  (the sources' sharded, the receivers' replicated: ``(ns + nr) x npix``
  float32 in all, made from the geometry alone), the partial images
  summed by a ``psum``; textbook CGLS around them. It reads nothing of
  the program: a shot the program put on the wrong chip, or tables made
  for another shot, are on one side only;
- **the costs of ONE chip's share** (``ns / chips`` shots): every
  ``*_roofline_pct`` divides one chip's floor by one chip's device time
  (the readers take the mean over the devices); with the line's count
  they would read ``chips`` times too high;
- **an immediate refusal** of a program whose ``MPILSM`` on several
  devices does not lay each shard's tables on its own chip and run the
  shards' blocks there (``MPIVStack``'s ``sharded`` form, read from a
  tiny operator before anything of size is allocated): such a program
  would make every chip's 8.6 GB of tables on one chip.
"""

from __future__ import annotations

import time
from types import SimpleNamespace

import numpy as np

from chipbench.builders import lsm as L

CONTROLS = L.CONTROLS
# the survey of the probe that asks the program for its form: one shot
# a device, a few receivers over a few blocks of pixels
PROBE = {"nz": 32, "nx": 64, "nr": 8, "nt": 256, "dshot": 32.0}


def one_chip(sizes: dict, chips: int) -> dict:
    """The sizes of one chip's share of the line: ``ns / chips`` shots,
    which every cost of the cell counts."""
    return dict(sizes, ns=int(sizes["ns"]) // chips)


def shard_times(times: dict, mesh) -> dict:
    """``lsm.point_times``' arrays placed for :func:`line_system`: the
    sources' travel times sharded over the mesh by shot, the rest
    replicated."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    ax = mesh.axis_names[0]
    spec = {"ts": P(ax), "tr": P(), "inside": P(), "dt": P()}
    return {k: jax.device_put(v, NamedSharding(mesh, spec[k]))
            for k, v in times.items()}


def line_system(sizes: dict, mesh, width: int, cast=None):
    """``(mv, rmv)`` of the whole line on flat vectors, each taking the
    sharded travel times (:func:`shard_times`) first: every chip runs
    ``lsm.plain_system`` (``banded_spray`` of ``width``) on its own
    shots, the data ``(pairs * nt,)`` sharded over shots, the image
    ``(nz * nx,)`` replicated; the adjoint's partial images summed by
    one ``psum``."""
    from jax import lax, shard_map
    from jax.sharding import PartitionSpec as P
    mv1, rmv1 = L.plain_system(sizes, cast, width)
    ax = mesh.axis_names[0]
    tspec = {"ts": P(ax), "tr": P(), "inside": P(), "dt": P()}

    def mv(times, m):
        return shard_map(mv1, mesh=mesh, in_specs=(tspec, P()),
                         out_specs=P(ax), check_vma=False)(times, m)

    def rmv(times, d):
        return shard_map(lambda t, z: lax.psum(rmv1(t, z), ax), mesh=mesh,
                         in_specs=(tspec, P(ax)), out_specs=P(),
                         check_vma=False)(times, d)
    return mv, rmv


def line_solve(sizes: dict, mesh, niter: int, width: int, cast=None):
    """``lsm.plain_solve`` on :func:`line_system`: ``f(times, d) -> (x,
    drop)``, with ``f.solve`` and ``f.drop``; the residual made by one
    program and its norm taken by another, for ``lsm``'s reason."""
    import jax
    import jax.numpy as jnp
    from chipbench import reference

    mv, rmv = line_system(sizes, mesh, width, cast)

    @jax.jit
    def solve(times, d):
        with jax.default_matmul_precision("highest"):
            return reference.cgls(lambda c: mv(times, c),
                                  lambda s: rmv(times, s),
                                  lambda u: jnp.sum(u * u), d, niter)

    @jax.jit
    def residual(times, d, x):
        with jax.default_matmul_precision("highest"):
            return d - mv(times, x)

    share = jax.jit(lambda r, d: jnp.sqrt(jnp.sum(r * r) / jnp.sum(d * d)))

    def drop(times, d, x):
        return share(residual(times, d, x), d)

    def both(times, d):
        x = solve(times, d)
        return x, drop(times, d, x)

    both.solve, both.drop = solve, drop
    return both


def refuse_unless_sharded(pmt, sizes: dict, mesh) -> None:
    """``SystemExit`` unless the program's ``MPILSM`` on this mesh takes
    ``MPIVStack``'s sharded form (a tiny survey, one shot a device)."""
    P_ = int(mesh.devices.size)
    try:
        probe = pmt.models.MPILSM(*L.geometry(
            dict(sizes, **PROBE, ns=P_)).args, mesh=mesh)
        form = getattr(probe, "form", None)
    except Exception as e:      # noqa: BLE001 - any failure refuses
        form = f"an error ({type(e).__name__}: {e})"
    if form != "sharded":
        raise SystemExit(
            f"chipbench: this checkout's pmt.models.MPILSM on {P_} devices "
            f"is not a stack whose shards' tables lie and run on their own "
            f"chips (MPIVStack form: {form}); lsm_kirchhoff_line cannot run "
            "on it")


def build(cfg: dict, sizes: dict, seed: int, mesh, log) -> SimpleNamespace:
    import jax
    import pylops_mpi_tpu as pmt
    from chipbench import costs_lsm

    nz, nx, nr, ns, nt = (int(sizes[k])
                          for k in ("nz", "nx", "nr", "ns", "nt"))
    chips = int(mesh.devices.size)
    pairs, npix = ns * nr, nz * nx
    if pairs * nt == npix:
        raise ValueError("data and model of one length: dep.vector tells "
                         "them apart by it")
    if ns % chips:
        raise ValueError(f"{ns} shots are not dealt evenly over {chips} "
                         "chips")
    refuse_unless_sharded(pmt, sizes, mesh)
    share = one_chip(sizes, chips)
    geo = L.geometry(sizes)

    t0 = time.perf_counter()
    # upstream's arguments and nothing else: every chip makes its own
    # shots' tables, on itself
    Op = pmt.models.MPILSM(*geo.args, mesh=mesh)
    held = jax.block_until_ready(jax.tree_util.tree_leaves(Op))
    table_bytes = sum(int(a.nbytes) for a in held)
    construct_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    # the reference's own travel times, from the geometry alone; the
    # band its indexed part needs is read over every pair before they
    # are dealt out
    times = L.point_times(sizes)
    width = L.band_width(sizes, times)
    times = jax.block_until_ready(shard_times(times, mesh))
    times_s = time.perf_counter() - t0
    log(f"program: {table_bytes} bytes held by the operator "
        f"({table_bytes // chips} a chip, form {Op.form}); reference: "
        f"per-point travel times {times['ts'].nbytes + times['tr'].nbytes} "
        f"bytes, band {width} samples a run of {L.RUN} pixels")

    reflectivity = L.make_reflectivity(sizes)
    mv, _ = line_system(sizes, mesh, width)
    model = jax.jit(mv)

    def rhs(j: int, seed_: int):
        """Pool member ``j`` of the seed: the data ``d``, flat, sharded
        over shots: the plain modelling of a seeded reflectivity."""
        k = jax.random.fold_in(jax.random.key(int(seed_)), 1 + j)
        with jax.default_matmul_precision("highest"):
            return model(times, reflectivity(k))

    solves = {}

    def plain(niter_: int, kind=None):
        key = (int(niter_), kind)
        if key not in solves:
            solves[key] = line_solve(sizes, mesh, int(niter_), width,
                                     **(CONTROLS[kind] if kind else {}))
        return solves[key]

    def reference(d, niter_: int) -> SimpleNamespace:
        x, drop = plain(niter_)(times, d)
        return SimpleNamespace(x=x, drop=drop)

    def drop(d, x):
        return plain(cfg["guarantees"]["niter"]).drop(times, d, x)

    def vector(n: int, a=None):
        """The data ``Partition.SCATTER`` over shots, the model
        ``Partition.BROADCAST``; told apart by their length."""
        part = pmt.Partition.SCATTER if n == pairs * nt \
            else pmt.Partition.BROADCAST
        out = pmt.DistributedArray(global_shape=n, mesh=mesh,
                                   partition=part, dtype=np.float32)
        if a is not None:
            out[:] = a
        return out

    def control(kind: str):
        def solve(y, x0, niter_):
            return vector(npix, plain(niter_, kind).solve(times, y.array))
        return solve

    return SimpleNamespace(
        op=Op, mesh=mesh, nrows=pairs * nt, ncols=npix,
        rhs=rhs, reference=reference, drop=drop, vector=vector,
        control=control, stand_in=None, times=times, width=width,
        cost=lambda k=1: costs_lsm.iteration(share),
        kirchhoff_cost=lambda: costs_lsm.kirchhoff(share),
        dtype="float32", resid_ratio=float(cfg["guarantees"]["resid_ratio"]),
        repeat_tol=float(cfg["guarantees"]["repeat_tol"]),
        split={"construct_s": construct_s, "reference_times_s": times_s},
        describe=f"image {nz}x{nx} ({4 * npix} bytes) BROADCAST, {ns} "
                 f"shots x {nr} receivers = {pairs} pairs over {chips} "
                 f"chips, data {pairs}x{nt} float32 SCATTER "
                 f"({4 * pairs * nt} bytes), tables {pairs}x{npix} x 8 B "
                 f"({table_bytes} bytes held, {table_bytes // chips} a "
                 f"chip), dt {sizes['dt']} s, {sizes['vel']} m/s, Ricker "
                 f"{sizes['f0']} Hz of {sizes['nwav']}, {type(Op).__name__} "
                 f"({Op.form}) on {chips} device(s)")
