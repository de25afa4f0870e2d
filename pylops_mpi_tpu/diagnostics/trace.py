"""Structured span tracer — Chrome-trace-event JSONL.

The runtime-observability entry layer (round 9): a lightweight span
tracer with a context-manager API, nested spans, monotonic timestamps
and a thread-safe ring buffer. Every operator ``matvec``/``rmatvec``
(``linearoperator.py``), every hand-scheduled collective
(``parallel/collectives.py``: ``ring_pass``,
``chunked_pencil_transpose``, ``plane_all_to_all``, the halo
exchanges) and every solver call (``solvers/*``) opens a span tagged
with shapes, dtypes, overlap mode and mesh axes. One-shot notes that
previously went to stdout/logging (``resolve_chunks`` fallbacks, SUMMA
schedule selection) land here as instant events, so they ride in the
JSONL artifact instead of scrolling away.

Two sinks, one rule (:func:`span`, which :func:`op_span` ends in):

1. **The profiler's clock, always.** Under a jax trace the span enters
   ``jax.named_scope("pmt." + name)``: trace-time only, nothing at run
   time, and every op lowered inside carries the name in its HLO
   ``op_name``, which a device trace prints (``pmt.MPIBlockDiag.matvec``
   inside a fused solver's ``while_loop``, ``pmt.collective.ring_pass``
   …). Outside a jax trace it enters
   ``jax.profiler.TraceAnnotation("pmt." + name, **ids)`` (``ids``: the
   ``int``/``str`` tags), a host span in whatever ``jax.profiler``
   session is running and a flag test in C++ when none is. Named scopes
   are provenance (``metadata={op_name=…}``), never instructions:
   compare programs through ``utils/hlo.py::strip_provenance``. JAX's
   compilation-cache key ignores metadata by default, and a cache
   filled before a scope existed then serves the program without it:
   ``aot/compile_cache.py`` puts the metadata into the key.
2. **The ring buffer, gated** by ``PYLOPS_MPI_TPU_TRACE``:

- ``off`` (default): no event is recorded and no flush handler is
  installed; the cost per call is one env lookup and one annotation
  object (sink 1).
- ``spans``: operator / collective / solver spans and structured
  events are recorded (same names without the ``pmt.`` prefix).
- ``full``: additionally enables the in-loop solver telemetry
  (:mod:`.telemetry` — per-iteration residual norms via
  ``jax.debug.callback``; the only mode that changes compiled
  programs).

Timestamp semantics of the ring buffer: spans record HOST wall-clock
(``perf_counter_ns`` relative to process start). A span around code
running under a ``jit`` trace measures *trace time*, not device time —
such spans are tagged ``"jax_tracing": true``; they still carry the
schedule metadata (shapes, chunk counts, byte estimates), which is
their real payload. Device-side timing comes from sink 1: take a
``jax.profiler.trace(dir)`` around the region.

Events are Chrome trace-event dicts (``ph`` ``X``/``i``/``C``), one
JSON object per line when dumped (``dump(path)``); set
``PYLOPS_MPI_TPU_TRACE_FILE`` to auto-dump at process exit. Open in
Perfetto via ``dump(path, fmt="chrome")`` (a single JSON array) or
``jq -s . trace.jsonl > trace.json``.

Post-mortem flush: when ``PYLOPS_MPI_TPU_TRACE_FILE`` is set, the
flush is registered for ``atexit`` AND ``SIGTERM`` (a supervised
worker's usual death is a signal, which skips atexit entirely), and it
is installed at the FIRST span *entry*, not just the first completed
event — a worker killed inside its very first span still leaves a
parseable artifact. Spans still open at flush time are emitted as
Chrome ``ph="B"`` (begin-without-end) events, so the post-mortem shows
exactly which phase the process died in. The SIGTERM handler chains
any previously-installed handler, then re-raises the default so the
exit status still says "killed by SIGTERM".
"""

from __future__ import annotations

import json
import os
import threading
import time
from collections import deque
from typing import Dict, List, Optional

__all__ = ["trace_mode", "trace_enabled", "span", "op_span", "event",
           "counter", "get_events", "clear_events", "dump", "span_tree",
           "open_span_events"]

_MODES = ("off", "spans", "full")
_warned_mode = False


def trace_mode() -> str:
    """``PYLOPS_MPI_TPU_TRACE`` resolved to ``off``/``spans``/``full``
    (unknown values fall back to ``off`` with a one-time warning — a
    typo in a CI matrix must not silently flip tracing on). Read per
    call (a dict lookup) so tests and long-lived sessions can flip the
    env without a cache to reset."""
    global _warned_mode
    m = os.environ.get("PYLOPS_MPI_TPU_TRACE", "off").strip().lower()
    if m in ("", "0", "none", "default"):
        m = "off"
    if m not in _MODES:
        if not _warned_mode:
            import warnings
            warnings.warn(
                f"PYLOPS_MPI_TPU_TRACE={m!r} is not one of {_MODES}; "
                "tracing stays off", stacklevel=2)
            _warned_mode = True
        m = "off"
    return m


def trace_enabled() -> bool:
    return trace_mode() != "off"


def _buffer_size() -> int:
    try:
        return max(1024, int(os.environ.get(
            "PYLOPS_MPI_TPU_TRACE_BUFFER", str(1 << 16))))
    except ValueError:
        return 1 << 16


# Ring buffer of completed Chrome events. A deque with maxlen drops the
# OLDEST events on overflow — a long solve can never grow host memory
# unboundedly; raise PYLOPS_MPI_TPU_TRACE_BUFFER to keep more.
_LOCK = threading.Lock()
_BUF: deque = deque(maxlen=_buffer_size())
_EPOCH_NS = time.perf_counter_ns()
_tls = threading.local()  # per-thread open-span stack (nesting depth)
_atexit_registered = False
# Cross-thread registry of OPEN spans (id(span) → span): the flush
# handlers read it to emit ph="B" events for phases cut short by a
# kill. Distinct from _tls.stack, which only the owning thread sees.
_OPEN: Dict[int, "_Span"] = {}


def _now_us() -> float:
    return (time.perf_counter_ns() - _EPOCH_NS) / 1e3


def _jsonable(v):
    """Best-effort JSON-safe value: tuples/lists recurse, numpy/jax
    scalars go through float/int, everything else falls back to
    ``str`` — a span tag must never be able to crash the traced
    workload."""
    if v is None or isinstance(v, (bool, int, float, str)):
        return v
    if isinstance(v, (tuple, list)):
        return [_jsonable(x) for x in v]
    if isinstance(v, dict):
        return {str(k): _jsonable(x) for k, x in v.items()}
    try:
        import numpy as np
        if isinstance(v, np.integer):
            return int(v)
        if isinstance(v, (np.floating, np.number)):
            return float(v)
    except Exception:
        pass
    return str(v)


def _jax_tracing() -> bool:
    """True when called under an active jax trace (jit/shard_map/vmap
    tracing pass) — ring-buffer spans recorded there measure trace
    time, and are tagged so readers never mistake them for device
    time; on the profiler's clock they become named scopes."""
    import jax
    return not jax.core.trace_ctx.is_top_level()


def _ensure_flush_handlers() -> None:
    """Register the exit-flush (atexit + SIGTERM) once, iff
    ``PYLOPS_MPI_TPU_TRACE_FILE`` is set. Called from both span entry
    and event recording, so a process killed inside its FIRST span
    (nothing completed yet) still flushes. Caller holds ``_LOCK``."""
    global _atexit_registered
    if _atexit_registered or not os.environ.get(
            "PYLOPS_MPI_TPU_TRACE_FILE"):
        return
    import atexit
    atexit.register(_atexit_dump)
    try:  # signal handlers only install from the main thread
        import signal
        prev = signal.getsignal(signal.SIGTERM)

        def _on_sigterm(signum, frame):
            _atexit_dump()
            if callable(prev) and prev not in (signal.SIG_IGN,
                                               signal.SIG_DFL):
                prev(signum, frame)
            else:  # die with the honest "killed by SIGTERM" status
                signal.signal(signal.SIGTERM, signal.SIG_DFL)
                os.kill(os.getpid(), signal.SIGTERM)

        signal.signal(signal.SIGTERM, _on_sigterm)
    except (ValueError, OSError):
        pass  # non-main thread / exotic platform: atexit still covers us
    _atexit_registered = True


def _record(ev: Dict) -> None:
    with _LOCK:
        _BUF.append(ev)
        _ensure_flush_handlers()


def _atexit_dump() -> None:
    path = os.environ.get("PYLOPS_MPI_TPU_TRACE_FILE")
    if path:
        try:
            dump(path)
        except Exception:
            pass  # a failed flush must never mask the real exit status


PREFIX = "pmt."


def _annotation(name: str, tags: Dict, tracing: bool):
    """Sink 1: the context manager that puts ``name`` on the
    profiler's clock — a named scope under a jax trace, a
    ``TraceAnnotation`` carrying the ``int``/``str`` tags outside it."""
    import jax
    if tracing:
        return jax.named_scope(PREFIX + name)
    return jax.profiler.TraceAnnotation(
        PREFIX + name,
        **{k: v for k, v in tags.items() if type(v) in (int, str)})


class _ProfilerSpan:
    """What :func:`span` returns in ``off`` mode: sink 1 alone."""

    __slots__ = ("_prof",)

    def __init__(self, prof):
        self._prof = prof

    def __enter__(self):
        self._prof.__enter__()
        return self

    def __exit__(self, *exc):
        self._prof.__exit__(*exc)
        return False

    def tag(self, **tags):
        return self


class _Span(_ProfilerSpan):
    """One open span: besides sink 1, records a Chrome ``ph="X"``
    (complete) event on exit, carrying its nesting depth and parent
    name so span trees can be rebuilt from the flat buffer
    (``span_tree``)."""

    __slots__ = ("name", "args", "t0", "_depth", "_parent", "_tid")

    def __init__(self, name: str, args: Dict, prof):
        super().__init__(prof)
        self.name = name
        self.args = args
        self.t0 = 0.0
        self._depth = 0
        self._parent = None
        self._tid = 0

    def tag(self, **tags) -> "_Span":
        """Attach tags discovered mid-span (e.g. a resolved chunk
        count) to the event that will be emitted at exit."""
        self.args.update({k: _jsonable(v) for k, v in tags.items()})
        return self

    def __enter__(self):
        stack = getattr(_tls, "stack", None)
        if stack is None:
            stack = _tls.stack = []
        self._depth = len(stack)
        self._parent = stack[-1].name if stack else None
        stack.append(self)
        self.t0 = _now_us()
        self._tid = threading.get_ident()
        with _LOCK:
            _OPEN[id(self)] = self
            _ensure_flush_handlers()  # flush even if we never close
        self._prof.__enter__()
        return self

    def __exit__(self, *exc):
        self._prof.__exit__(*exc)
        t1 = _now_us()
        stack = getattr(_tls, "stack", ())
        if stack and stack[-1] is self:
            stack.pop()
        with _LOCK:
            _OPEN.pop(id(self), None)
        args = dict(self.args)
        args["depth"] = self._depth
        if self._parent is not None:
            args["parent"] = self._parent
        _record({"name": self.name, "ph": "X", "ts": round(self.t0, 3),
                 "dur": round(t1 - self.t0, 3), "pid": os.getpid(),
                 "tid": threading.get_ident(), "cat": args.pop(
                     "cat", "span"), "args": args})
        return False


def span(name: str, cat: str = "span", **tags):
    """Open a span (context manager) on the profiler's clock — the
    module docstring's rule — and, unless tracing is ``off``, in the
    ring buffer. ``tags`` become the Chrome event's ``args``; tags are
    JSON-sanitized so arbitrary shapes/dtypes/meshes are safe to
    pass. Spans nest: each records its depth and parent name."""
    return _open(name, cat, tags, trace_mode())


def _open(name: str, cat: str, tags: Dict, mode: str):
    tracing = _jax_tracing()
    prof = _annotation(name, tags, tracing)
    if mode == "off":
        return _ProfilerSpan(prof)
    args = {k: _jsonable(v) for k, v in tags.items()}
    if tracing:
        args["jax_tracing"] = True
    args["cat"] = cat
    return _Span(name, args, prof)


def op_span(op, which: str):
    """Span for one operator apply — the wiring point used by
    ``MPILinearOperator.matvec``/``rmatvec``. Tags: operator class,
    operator shape, dtype, mesh axis names, and (when the operator
    carries them) overlap mode / schedule / grid. With tracing off
    the tags have no reader and are not gathered."""
    mode = trace_mode()
    tags = {}
    if mode != "off":
        tags = {"op": type(op).__name__,
                "shape": getattr(op, "shape", None),
                "dtype": getattr(op, "dtype", None)}
        mesh = getattr(op, "mesh", None)
        if mesh is not None:
            tags["mesh_axes"] = getattr(mesh, "axis_names", None)
        for extra in ("overlap", "schedule", "grid", "compute_dtype"):
            v = getattr(op, extra, None)
            if v is not None:
                tags[extra] = v
    return _open(f"{type(op).__name__}.{which}", "operator", tags, mode)


def event(name: str, cat: str = "event", **tags) -> None:
    """Instant event (Chrome ``ph="i"``): the structured replacement
    for one-shot stdout/log notes — ``resolve_chunks`` fallbacks,
    SUMMA schedule selection — so they land in the JSONL artifact."""
    if trace_mode() == "off":
        return
    args = {k: _jsonable(v) for k, v in tags.items()}
    if _jax_tracing():
        args["jax_tracing"] = True
    _record({"name": name, "ph": "i", "s": "t", "ts": round(_now_us(), 3),
             "pid": os.getpid(), "tid": threading.get_ident(),
             "cat": cat, "args": args})


def counter(name: str, values: Dict[str, float],
            cat: str = "telemetry") -> None:
    """Counter sample (Chrome ``ph="C"``): Perfetto renders these as
    time-series tracks — the shape the per-iteration solver telemetry
    lands in (:mod:`.telemetry`)."""
    if trace_mode() == "off":
        return
    _record({"name": name, "ph": "C", "ts": round(_now_us(), 3),
             "pid": os.getpid(), "tid": threading.get_ident(),
             "cat": cat, "args": {k: _jsonable(v)
                                  for k, v in values.items()}})


def get_events() -> List[Dict]:
    """Snapshot of the ring buffer (oldest first)."""
    with _LOCK:
        return list(_BUF)


def clear_events() -> None:
    """Drop buffered events AND forget open-span registrations (a test
    that leaked a span must not haunt later dumps; a leaked span's own
    ``__exit__`` pops nothing and stays harmless)."""
    with _LOCK:
        _BUF.clear()
        _OPEN.clear()


def open_span_events() -> List[Dict]:
    """Chrome ``ph="B"`` events for every span currently OPEN, across
    all threads — the post-mortem's "died while doing X" lines. Safe
    from signal/atexit context (one lock, no allocation surprises)."""
    with _LOCK:
        spans = list(_OPEN.values())
    out = []
    for s in spans:
        args = dict(s.args)
        args["open"] = True
        args["depth"] = s._depth
        if s._parent is not None:
            args["parent"] = s._parent
        out.append({"name": s.name, "ph": "B", "ts": round(s.t0, 3),
                    "pid": os.getpid(), "tid": s._tid,
                    "cat": args.pop("cat", "span"), "args": args})
    out.sort(key=lambda ev: ev["ts"])
    return out


def dump(path: str, fmt: str = "jsonl") -> int:
    """Write the buffered events to ``path``: ``fmt="jsonl"`` (one
    Chrome event object per line — the artifact format) or
    ``fmt="chrome"`` (a single JSON array Perfetto/chrome://tracing
    open directly). Spans still open at dump time are appended as
    ``ph="B"`` (begin) events so a killed process's in-flight phase
    survives to the artifact. Returns the number of events written."""
    events = get_events() + open_span_events()
    if fmt == "chrome":
        with open(path, "w") as f:
            json.dump(events, f)
    elif fmt == "jsonl":
        with open(path, "w") as f:
            for ev in events:
                f.write(json.dumps(ev) + "\n")
    else:
        raise ValueError(f"fmt={fmt!r}: expected 'jsonl' or 'chrome'")
    return len(events)


def span_tree(events: Optional[List[Dict]] = None) -> List[Dict]:
    """Rebuild the span nesting from a flat event list: returns the
    roots, each ``{"name", "dur", "args", "children": [...]}`` — the
    verification handle for the nesting/ordering tests. Chrome ``X``
    events carry explicit ``depth``; reconstruction scans per-thread in
    END-time order (a parent's event is recorded after its
    children's), pushing each span under the most recent deeper-or-
    equal-depth run.

    Hardened for POST-MORTEM artifacts (ISSUE 10): the input may be a
    killed worker's flush, so non-dict entries, events with missing or
    mistyped fields, and unclosed ``ph="B"`` spans must all degrade
    gracefully instead of raising. ``B`` events (one still-open
    ancestry chain per thread) become nodes with ``dur=None``; spans
    whose parent never closed are adopted under the deepest open span
    shallower than them."""
    if events is None:
        events = get_events()
    roots: List[Dict] = []
    by_tid: Dict = {}
    for ev in events:
        if not isinstance(ev, dict) or ev.get("ph") not in ("X", "B"):
            continue
        if not isinstance(ev.get("name"), str) \
                or not isinstance(ev.get("ts"), (int, float)):
            continue  # a garbage line must not crash the post-mortem
        by_tid.setdefault(ev.get("tid"), []).append(ev)
    for tid_events in by_tid.values():
        stack: List = []  # (depth, node) of spans awaiting a parent
        open_chain: List = []  # (depth, node) of ph="B" open spans
        for ev in tid_events:  # buffer order == end-time order
            args = ev.get("args") if isinstance(ev.get("args"),
                                                dict) else {}
            depth = args.get("depth", 0)
            if not isinstance(depth, int) or depth < 0:
                depth = 0
            dur = ev.get("dur")
            node = {"name": ev["name"], "ts": ev["ts"],
                    "dur": dur if isinstance(dur, (int, float)) else None,
                    "args": args, "children": []}
            if ev.get("ph") == "B":
                open_chain.append((depth, node))
                continue
            while stack and stack[-1][0] > depth:
                node["children"].append(stack.pop()[1])
            node["children"].reverse()  # recorded youngest-first
            if depth == 0:
                roots.append(node)
            else:
                stack.append((depth, node))
        if open_chain:
            # the open spans of one thread form a single ancestry
            # chain (outermost first after the depth sort); completed
            # spans still awaiting a parent were inside the deepest
            # open span shallower than them
            open_chain.sort(key=lambda p: p[0])
            for i in range(len(open_chain) - 1):
                open_chain[i][1]["children"].append(open_chain[i + 1][1])
            for d, n in stack:
                host = None
                for bd, bn in open_chain:
                    if bd < d:
                        host = bn
                (host["children"].append(n) if host is not None
                 else roots.append(n))
            stack = []
            roots.append(open_chain[0][1])
        # orphans (parent span still open at snapshot time)
        roots.extend(n for _, n in stack)
    roots.sort(key=lambda n: n["ts"])
    return roots
