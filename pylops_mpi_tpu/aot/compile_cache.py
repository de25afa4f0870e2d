"""JAX persistent compilation cache wiring — the fallback layer.

The executable bank (:mod:`~pylops_mpi_tpu.aot.store`) serializes
only the programs whose operators enter as jit arguments; everything
else — closure-captured operators, preconditioned solves, ISTA/FISTA,
one-off jits across the package — still pays XLA compile on first
trace. JAX's own persistent compilation cache pays those compiles
once per (program, jax version, backend) ACROSS processes.

Where the cache lives is decided here and nowhere else, by one rule:

1. ``JAX_COMPILATION_CACHE_DIR`` set: JAX's own reading of it stands
   and nothing in this repository touches ``jax_compilation_cache_dir``
   (the machine that runs the chip places the cache from outside);
2. else ``PYLOPS_MPI_TPU_COMPILE_CACHE=<dir>`` (CI legs share a per-job
   dir, the tier-1 command keeps one under ``/tmp``);
3. else the ``default`` the caller passes — the repository's entry
   scripts (``chip_smoke.py``, ``benchmarks/*``) pass
   ``<checkout>/.jax_cache``; the package import passes none, so a
   library user with neither variable set gets no cache.

The directory is part of the cache key's lookup path, so it is always a
fixed path: never a temp name, a pid or a time.

The key holds the program's metadata too
(``jax_compilation_cache_include_metadata_in_key``): the names
``diagnostics/trace.py`` puts into ``op_name`` are what a profile of
this program is read by, and JAX's default key leaves them out, so a
cache filled before a scope existed served the executable without it
(``mdd_obc.cgls_nv16``'s solver came back from the chip machine's
cache without the ``pmt.solver.*`` scopes: PERF.md section 6, PR 36).
The price is one compile a program when its source moves.

Multi-host contract: rank 0 writes, other ranks read — every rank
lowers the same SPMD program, so one writer suffices and NFS cache
dirs see no cross-rank write races. Non-zero ranks get the read-only
behavior by an effectively-infinite ``min_compile_time`` floor (JAX
has no explicit read-only switch; a cache write only happens for
compiles slower than the floor).
"""

from __future__ import annotations

import os
import threading
from typing import Optional

from ..diagnostics import trace as _trace
from .store import rank_writes

__all__ = ["compile_cache_dir", "maybe_enable_compile_cache"]

_LOCK = threading.Lock()
_enabled_dir: Optional[str] = None


def compile_cache_dir(default: Optional[str] = None) -> Optional[str]:
    """The persistent-cache directory by the module's rule:
    ``JAX_COMPILATION_CACHE_DIR``, else
    ``PYLOPS_MPI_TPU_COMPILE_CACHE``, else ``default``, else ``None``."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.environ.get("PYLOPS_MPI_TPU_COMPILE_CACHE")
            or default or None)


def maybe_enable_compile_cache(default: Optional[str] = None
                               ) -> Optional[str]:
    """Arm JAX's persistent compilation cache at
    :func:`compile_cache_dir` (idempotent; process-wide) and lower the
    write thresholds so fast compiles are banked too. Called at package
    import (no default) so tests, supervised workers and the serving
    daemon share the job's cache without per-call wiring, and by the
    entry scripts with their checkout default. Returns the directory in
    use, or ``None`` when the rule selects none."""
    global _enabled_dir
    path = compile_cache_dir(default)
    if not path:
        return None
    with _LOCK:
        if _enabled_dir == path:
            return path
        import jax
        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            jax.config.update("jax_compilation_cache_dir", path)
        # a cached executable has to carry the names it is traced by
        jax.config.update(
            "jax_compilation_cache_include_metadata_in_key", True)
        if rank_writes():
            # bank every compile, however fast: CPU-sim programs
            # compile in ms and the defaults would skip them all
            jax.config.update(
                "jax_persistent_cache_min_compile_time_secs", 0.0)
            jax.config.update(
                "jax_persistent_cache_min_entry_size_bytes", 0)
        else:
            # read-only rank: reads always hit; a write would need
            # a compile slower than this floor
            jax.config.update(
                "jax_persistent_cache_min_compile_time_secs", 1e9)
        _enabled_dir = path
        _trace.event("aot.compile_cache", cat="aot", path=path,
                     writer=rank_writes())
        return path
