"""The plan seam: what operators consult at construction.

``get_plan()`` is the ONE entry point the operator stack calls
(``ops/matrixmult.py``, ``ops/fft.py``, ``ops/blockdiag.py``,
``ops/stack.py``, ``ops/derivatives.py``, ``ops/halo.py``,
``ops/sparse.py``).
Resolution order:

1. ``PYLOPS_MPI_TPU_TUNE=off`` (the default) → ``None``: the caller
   keeps its hand-set/env defaults and the compiled HLO is
   bit-identical to a tuner-free build (pinned by
   ``tests/test_tuning.py``, same pattern as the overlap pin).
2. A cached plan for this key (``cache.py`` —
   ``PYLOPS_MPI_TPU_TUNE_CACHE``) → provenance ``"tuned"``; replayed
   without any timing trial. Cached params are validated against the
   declared space first — a stale axis value after a code change is a
   logged miss, never a crash.
3. Cost-model pick (``space.rank``) → provenance ``"costmodel"`` —
   by construction equal to today's defaults (see ``space.py``).
4. Under ``PYLOPS_MPI_TPU_TUNE=auto``, a caller that supplies a
   ``factory`` gets measurement on a cache miss: the top-ranked
   candidates are timed (``search.measure_candidates``, always inside
   a ``DeadlineRunner`` budget) and the winner is banked to the cache
   → provenance ``"tuned"``.

**Explicit kwargs always beat the tuner**: operators only consult
``get_plan`` for parameters the user left at their ``None``/``auto``
sentinels, so a hand-pinned ``schedule="gather"`` or ``overlap=False``
can never be overridden by a cache entry.

Keys are ``(op family, logical shape bucket, dtype, mesh axes+size,
chip kind)`` — :func:`plan_key`. Shapes bucket to the next power of
two per dim so a 4000² problem replays the 4096² plan; topology and
chip are exact (a v5e plan must not replay on a v6e). Hybrid meshes
(round 11) additionally key on the fabric layout
(:func:`~pylops_mpi_tpu.parallel.topology.topology_key`): a plan
measured on a ``2x4`` slice decomposition must not replay on ``4x2``
— while flat meshes contribute an EMPTY component, so every
pre-round-11 cache entry keeps its key byte-for-byte.
"""

from __future__ import annotations

import os
import threading
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..diagnostics import trace as _trace
from . import cache as _cache
from . import space as _space

__all__ = ["Plan", "tune_mode", "tune_enabled", "plan_key",
           "shape_bucket", "get_plan", "applied_provenance",
           "reset_applied"]

_MODES = ("off", "on", "auto")
_warned_mode = False

# reentrancy guard: candidate operators built DURING a measurement must
# never consult the tuner themselves (their kwargs are explicit anyway;
# this is the belt to that suspender)
_tls = threading.local()

# last applied provenance per op family
_APPLIED: Dict[str, str] = {}
_APPLIED_LOCK = threading.Lock()


def tune_mode() -> str:
    """``PYLOPS_MPI_TPU_TUNE`` resolved to ``off``/``on``/``auto``
    (unknown values fall back to ``off`` with a one-time warning — a
    typo in a CI matrix must not silently flip schedules; same
    convention as the overlap/trace seams)."""
    global _warned_mode
    m = os.environ.get("PYLOPS_MPI_TPU_TUNE", "off").strip().lower()
    if m in ("", "0", "none", "default"):
        m = "off"
    if m in ("1", "true"):
        m = "on"
    if m not in _MODES:
        if not _warned_mode:
            import warnings
            warnings.warn(
                f"PYLOPS_MPI_TPU_TUNE={m!r} is not one of {_MODES}; "
                "tuning stays off", stacklevel=2)
            _warned_mode = True
        m = "off"
    return m


def tune_enabled() -> bool:
    return tune_mode() != "off"


@dataclass
class Plan:
    """A resolved plan: the params the operator should apply, where
    they came from (``tuned`` = measured, ``costmodel`` = analytic
    seed, ``default`` = tuner off/no space), and the trial records
    when measured this process."""

    op: str
    key: str
    params: Dict
    provenance: str
    trials: List[Dict] = field(default_factory=list)

    def get(self, name: str, default=None):
        return self.params.get(name, default)

    def as_dict(self) -> Dict:
        return {"op": self.op, "key": self.key, "params": self.params,
                "provenance": self.provenance, "trials": self.trials}


def shape_bucket(shape) -> Tuple[int, ...]:
    """Next-power-of-two bucket per dim: nearby shapes share a plan
    (a 4000x4000 apply replays the 4096x4096 measurement)."""
    out = []
    for s in np.atleast_1d(shape):
        s = max(1, int(s))
        out.append(1 << (s - 1).bit_length())
    return tuple(out)


def _chip_kind() -> Tuple[str, str]:
    """(platform, device_kind) of device 0 — the topology half of the
    key. Guarded: a jax-less/odd environment tunes under a generic
    key rather than crashing."""
    try:
        import jax
        d = jax.devices()[0]
        return (getattr(d, "platform", "") or "unknown",
                getattr(d, "device_kind", "") or "unknown")
    except Exception:
        return "unknown", "unknown"


def plan_key(op: str, shape, dtype=None, n_dev: Optional[int] = None,
             axes=None, extra: Optional[Dict] = None) -> str:
    """Canonical cache key for one tuned plan. Note for the autodiff
    tier: the implicit backward solve (autodiff/implicit.py) runs the
    SAME fused engine on the transposed system, so it deliberately
    shares the forward solve's plan key — there is no ``|grad``
    segment. A plan measured on the forward pass is optimal for its
    backward pass too (same shapes, same collectives, same schedule)."""
    platform, chip = _chip_kind()
    try:
        dt = np.dtype(dtype).name if dtype is not None else "f32"
    except TypeError:
        dt = str(dtype)
    bucket = "x".join(str(b) for b in shape_bucket(shape))
    ax = ",".join(str(a) for a in (axes or ()))
    nd = int(n_dev or 1)
    key = f"{op}|s{bucket}|{dt}|mesh[{ax}]x{nd}|{platform}:{chip}"
    if extra and extra.get("grid"):
        key += f"|grid{tuple(int(g) for g in extra['grid'])}"
    # block width changes the measured regime (K columns per GEMM /
    # ring step); K=1 keeps the historical key so existing caches hit
    if extra and extra.get("batch") and int(extra["batch"]) != 1:
        key += f"|b{int(extra['batch'])}"
    # fabric layout (round 11): only hybrid meshes carry one — a flat
    # mesh appends NOTHING, so pre-round-11 cache keys stay verbatim
    if extra and extra.get("topology"):
        key += f"|t{extra['topology']}"
    return key


def _context(op: str, shape, dtype, n_dev, axes, extra) -> Dict:
    platform, chip = _chip_kind()
    return {"op": op, "shape": tuple(int(s) for s in np.atleast_1d(shape)),
            "dtype": dtype, "n_dev": int(n_dev or 1),
            "axes": tuple(axes or ()), "platform": platform,
            "chip": chip, "extra": dict(extra or {})}


def _note_applied(op: str, provenance: str) -> None:
    with _APPLIED_LOCK:
        _APPLIED[op] = provenance


def applied_provenance(op: Optional[str] = None, default: str = "default"):
    """Provenance of the last plan applied for ``op`` this process
    (``"default"`` when the tuner never ran). Without ``op``: the
    whole table (a copy)."""
    with _APPLIED_LOCK:
        if op is None:
            return dict(_APPLIED)
        return _APPLIED.get(op, default)


def reset_applied() -> None:
    with _APPLIED_LOCK:
        _APPLIED.clear()


def get_plan(op: str, *, shape, dtype=None, mesh=None,
             n_dev: Optional[int] = None, axes=None,
             extra: Optional[Dict] = None, factory=None) -> Optional[Plan]:
    """Resolve the plan for one operator construction (see module
    docstring for the resolution order). Returns ``None`` when tuning
    is off, no space is declared for ``op``, or the call is reentrant
    (a measurement candidate under construction).

    ``factory(params) -> callable`` (optional): builds a candidate
    configuration and returns a zero-arg apply for timing; only
    consulted under mode ``auto`` on a cache miss. ``mesh`` is a
    convenience source for ``n_dev``/``axes``.
    """
    mode = tune_mode()
    if mode == "off":
        return None
    if getattr(_tls, "active", False):
        return None
    sp = _space.space_for(op)
    if sp is None:
        return None
    if mesh is not None:
        n_dev = n_dev if n_dev is not None else int(mesh.devices.size)
        axes = axes if axes is not None else tuple(mesh.axis_names)
        if not (extra or {}).get("topology"):
            from ..parallel import topology as _topo
            tk = _topo.topology_key(mesh)
            if tk:
                extra = dict(extra or {})
                extra["topology"] = tk
    key = plan_key(op, shape, dtype, n_dev, axes, extra)
    ctx = _context(op, shape, dtype, n_dev, axes, extra)

    entry = _cache.lookup(key)
    if entry is not None:
        params = entry.get("params")
        if isinstance(params, dict) and sp.validate(params):
            plan = Plan(op, key, dict(params), "tuned")
            _note_applied(op, "tuned")
            _trace.event("tuning.plan", cat="tuning", op=op, key=key,
                         provenance="tuned", params=params, replay=True)
            return plan
        _trace.event("tuning.cache_error", cat="tuning", key=key,
                     why="cached params fail space validation")

    if mode == "auto" and factory is not None:
        from . import search as _search
        _tls.active = True
        try:
            params, trials = _search.measure_candidates(
                sp, ctx, factory)
        finally:
            _tls.active = False
        if params is not None:
            entry = {"params": params, "provenance": "tuned",
                     "trials": trials}
            _cache.store(key, entry)
            plan = Plan(op, key, dict(params), "tuned", trials)
            _note_applied(op, "tuned")
            _trace.event("tuning.plan", cat="tuning", op=op, key=key,
                         provenance="tuned", params=params,
                         trials=len(trials))
            return plan

    ranked = _space.rank(sp, ctx)
    params = ranked[0] if ranked else {}
    plan = Plan(op, key, dict(params), "costmodel")
    _note_applied(op, "costmodel")
    _trace.event("tuning.plan", cat="tuning", op=op, key=key,
                 provenance="costmodel", params=params)
    return plan
