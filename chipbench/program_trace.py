"""The program's own names in a profiler trace (``.xplane.pb``).

``trace_reduce`` keeps the benchmark's ``cb.*`` spans and XLA's op
names. The program (``pylops_mpi_tpu/diagnostics/trace.py``) puts its
spans on the same clock under the prefix ``pmt.``, and this module
reads them from the same file:

- **host spans**: ``jax.profiler.TraceAnnotation("pmt.<name>", ...)``
  events of the host plane, with the thread (line) they ran on and
  their integer/string stats (the dispatcher's ``batch`` number);
- **scopes**: ``jax.named_scope("pmt.<name>")`` becomes part of an
  op's HLO ``op_name``. On the v5e the trace carries it as the stat
  ``tf_op`` of the op's *event metadata* on the ``XLA Ops`` line
  (``jit(<lambda>)/while/body/pmt.MPIBlockDiag.matvec/.../dot_general:``;
  found by PR 25's first chip call with ``scratch/program_trace_tool.py
  dump``); the device planes have no ``Framework Name Scope`` line.
  ``jax.profiler.ProfileData`` shows an event's own stats only, so the
  file is read here on the wire (the ``XSpace`` message of
  ``tests/xplane_writer.py``, with its stats), lines and events that
  hold nothing of interest skipped unread.

It also checks the clocks: in every ``pmt.serve.solve`` and
``pmt.solver.cgls`` span of the slice the first device op must start
inside the span. The program waits for the answer inside both, so a
span without a device op, or one whose first op began before it, means
the host's and the device's clocks are not aligned. The profiler aligns
them to a few tenths of a millisecond and no better (PR 25 saw ops
start up to 255 us before the span that dispatched them), so a lead of
up to ``CLOCK_SLACK_NS`` is said on the log and let pass: what is
attributed across the clocks are stages of ten milliseconds and more.
Beyond it nothing is attributed.

Times are nanoseconds on the trace's clock, as in ``trace_reduce``.
"""

from __future__ import annotations

import functools
import os
import re
import struct
from typing import Dict, Iterator, List, Optional, Tuple

from chipbench import trace_reduce as T

PREFIX = "pmt."
OUT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "out")
STAGING = ("pack", "stage_in", "pull", "resolve")
CLOCKED = (PREFIX + "serve.solve", PREFIX + "solver.cgls")
CLOCK_SLACK_NS = 1e6
SCOPE_STAT = "tf_op"
# scopes that are not an operator's: a solver wrapper traced whole
NOT_OPERATOR = (PREFIX + "solver.", PREFIX + "serve.")
_SCOPE = re.compile(r"(?:^|/)(pmt\.[^/]+)")

HostSpan = Tuple[float, float, str, int, dict]   # start, end, name, line, stats
Op = Tuple[float, float, str, Tuple[str, ...]]   # start, end, name, scopes


# ------------------------------------------------------------- the wire
def _varint(buf, i: int) -> Tuple[int, int]:
    v = shift = 0
    while True:
        b = buf[i]
        i += 1
        v |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return v, i


def _fields(buf) -> Iterator[Tuple[int, int, object]]:
    """``(field, wire type, value)`` of one message; a length-delimited
    value is a ``memoryview`` of its bytes, not yet read."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(buf, i)
            yield field, wire, v
        elif wire == 2:
            ln, i = _varint(buf, i)
            yield field, wire, buf[i:i + ln]
            i += ln
        elif wire in (1, 5):
            width = 8 if wire == 1 else 4
            yield field, wire, bytes(buf[i:i + width])
            i += width
        else:
            raise ValueError(f"wire type {wire} in an XSpace")


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _stat(buf) -> Tuple[int, object]:
    """One ``XStat``: ``(metadata id, value)``; a ``ref_value`` comes
    back as ``("ref", id)`` for the plane's stat names to resolve."""
    mid, val = 0, None
    for f, w, v in _fields(buf):
        if f == 1:
            mid = v
        elif f == 2:
            val = struct.unpack("<d", v)[0]
        elif f == 3:
            val = v
        elif f == 4:
            val = _signed(v)
        elif f in (5, 6):
            val = bytes(v).decode("utf-8", "replace")
        elif f == 7:
            val = ("ref", v)
    return mid, val


def _named(buf) -> Tuple[int, str, list]:
    """An ``XEventMetadata``/``XStatMetadata`` map entry: ``(id, name,
    stats)``."""
    mid, name, stats = 0, "", []
    for f, w, v in _fields(buf):
        if f == 1 and w == 0:
            mid = v
        elif f == 2 and w == 2:
            for g, gw, gv in _fields(v):
                if g == 1 and gw == 0:
                    mid = gv
                elif g == 2 and gw == 2:
                    name = bytes(gv).decode("utf-8", "replace")
                elif g == 5 and gw == 2:
                    stats.append(_stat(gv))
    return mid, name, stats


class _Plane:
    """One ``XPlane``, its lines still unread."""

    def __init__(self, buf):
        self.name = ""
        self._lines, self._events, self._stats = [], [], []
        for f, w, v in _fields(buf):
            if f == 2:
                self.name = bytes(v).decode("utf-8", "replace")
            elif f == 3:
                self._lines.append(v)
            elif f == 4:
                self._events.append(v)
            elif f == 5:
                self._stats.append(v)
        self._meta = None

    def _read_meta(self):
        if self._meta is None:
            self.stat_names = {i: n for i, n, _ in map(_named, self._stats)}
            self._meta = {i: (n, st) for i, n, st in
                          map(_named, self._events)}

    def stats(self, pairs) -> dict:
        """``(metadata id, value)`` pairs under their names."""
        self._read_meta()
        out = {}
        for mid, val in pairs:
            if isinstance(val, tuple):
                val = self.stat_names.get(val[1], "")
            out[self.stat_names.get(mid, str(mid))] = val
        return out

    def lines(self, keep=None, only=None
              ) -> Iterator[Tuple[int, str, list]]:
        """``(index, line name, events)`` with each event ``(start,
        end, name, own stats, metadata's stats)``; ``keep(line name)``
        false leaves a line unread, ``only(event name)`` false an
        event."""
        self._read_meta()
        wanted = None if only is None else \
            {i for i, (n, _) in self._meta.items() if only(n)}
        for idx, lb in enumerate(self._lines):
            name, t0, raw = "", 0, []
            for f, w, v in _fields(lb):
                if f == 2:
                    name = bytes(v).decode("utf-8", "replace")
                elif f == 3:
                    t0 = _signed(v)
                elif f == 4:
                    raw.append(v)
            if keep is not None and not keep(name):
                continue
            events = []
            for eb in raw:
                mid = off = dur = 0
                own = []
                for f, w, v in _fields(eb):
                    if f == 1:
                        mid = v
                        if wanted is not None and mid not in wanted:
                            break
                    elif f == 2:
                        off = _signed(v)
                    elif f == 3:
                        dur = _signed(v)
                    elif f == 4:
                        own.append(_stat(v))
                if wanted is not None and mid not in wanted:
                    continue
                ename, mstats = self._meta.get(mid, ("", []))
                start = t0 + off / 1e3
                events.append((start, start + dur / 1e3, ename, own, mstats))
            yield idx, name, events


def planes(path: str) -> List[_Plane]:
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    return [_Plane(v) for f, w, v in _fields(buf) if f == 1 and w == 2]


def is_device(name: str) -> bool:
    """The planes ``trace_reduce.load`` takes for devices."""
    return name.startswith("/device:") and "TPU" in name.upper() \
        and not re.search(r"sparse|host", name, re.I)


# ------------------------------------------------------------ the trace
def scopes_of(text: str) -> Tuple[str, ...]:
    """The ``pmt.`` components of an ``op_name`` path, outermost
    first: ``jit(f)/while/body/pmt.A.matvec/pmt.B.matvec/dot`` →
    ``("pmt.A.matvec", "pmt.B.matvec")``."""
    return tuple(_SCOPE.findall(text))


class ProgramTrace:
    """The ``pmt.*`` host spans and, per device, the leaf ops with the
    scopes each was lowered under."""

    def __init__(self, host: List[HostSpan], ops: Dict[str, List[Op]]):
        self.host = sorted(host)
        self.ops = {d: sorted(v) for d, v in sorted(ops.items())}

    @property
    def scoped(self) -> bool:
        """Whether any device op carries a ``pmt.`` scope."""
        return any(sc for v in self.ops.values() for *_, sc in v)

    def spans(self, name: str, lo: float, hi: float) -> List[HostSpan]:
        """Host spans ``pmt.<name>`` lying inside ``[lo, hi]``."""
        return [h for h in self.host if h[2] == PREFIX + name
                and h[0] >= lo and h[1] <= hi]

    def dispatcher_line(self) -> Optional[int]:
        """The thread the dispatcher's ``serve.batch`` spans ran on."""
        lines = {h[3] for h in self.host if h[2] == PREFIX + "serve.batch"}
        return min(lines) if lines else None


@functools.lru_cache(maxsize=4)
def load(path: str) -> ProgramTrace:
    host: List[HostSpan] = []
    ops: Dict[str, List[Op]] = {}
    for plane in planes(path):
        if is_device(plane.name):
            ev = []
            for _, _, events in plane.lines(lambda n: n in T.OP_LINES):
                scope = {}
                for s, e, name, own, meta in events:
                    scope[(s, e, T.short(name))] = scopes_of(
                        str(plane.stats(meta).get(SCOPE_STAT, "")))
                ev.extend((s, e, n, scope[(s, e, n)])
                          for s, e, n in T.leaves(list(scope)))
            if ev:
                ops[plane.name] = ev
        elif plane.name.startswith("/host:CPU"):
            for idx, _, events in plane.lines(
                    only=lambda n: n.startswith(PREFIX)):
                host.extend((s, e, name, idx, plane.stats(own))
                            for s, e, name, own, _ in events)
    return ProgramTrace(host, ops)


def once(fn):
    """A reduction shared by several readers of one run: worked out,
    and said on the log, once per ``ctx``."""
    @functools.wraps(fn)
    def wrapped(ctx):
        memo = ctx.setdefault("program_trace", {})
        if fn.__name__ not in memo:
            memo[fn.__name__] = fn(ctx)
        return memo[fn.__name__]
    return wrapped


def for_ctx(ctx) -> Optional[ProgramTrace]:
    """The program's side of the traced run ``ctx`` describes; ``None``
    in a run that took no trace."""
    if ctx.get("trace") is None:
        return None
    return load(T.newest_xplane(
        os.path.join(OUT, "trace", ctx["cell"]["name"])))


# --------------------------------------------------------------- clocks
def clock_check(pt: ProgramTrace, t: T.Trace
                ) -> Optional[Tuple[int, float]]:
    """Over the ``pmt.serve.solve``/``pmt.solver.cgls`` spans of the
    slice: ``(violations, lead)`` — in how many the first device op
    began more than ``CLOCK_SLACK_NS`` before the span or not at all,
    and by how many nanoseconds at most a first op led its span;
    ``None`` when the slice holds no such span."""
    spans = [h for h in pt.host if h[2] in CLOCKED
             and h[0] >= t.lo and h[1] <= t.hi]
    if not spans or not t.devices:
        return None
    bad, lead = 0, 0.0
    for s, e, *_ in spans:
        for d in t.devices:
            # uncut events: one that began before the span shows it
            first = next((o for o in pt.ops.get(d, ())
                          if o[1] > s and o[0] < e), None)
            if first is None or s - first[0] > CLOCK_SLACK_NS:
                bad += 1
                break
            lead = max(lead, s - first[0])
    return bad, lead


def aligned(ctx, pt: ProgramTrace) -> bool:
    """Whether numbers may be attributed across the two clocks; says
    on the log what it found."""
    found = clock_check(pt, ctx["trace"])
    if found is None:
        ctx["log"]("program_trace: no pmt.serve.solve or pmt.solver.cgls "
                   "span in the slice")
        return False
    bad, lead = found
    ctx["log"](f"program_trace: clock violations {bad} (a solve span "
               f"whose first device op began over {CLOCK_SLACK_NS / 1e3:g} "
               f"us before it, or never); largest lead {lead / 1e3:.1f} us")
    return bad == 0


# ------------------------------------------------------ serving: stages
def intersect(a, b) -> List[T.Interval]:
    return T.subtract(a, T.subtract(a, b))


@once
def idle_split(ctx) -> Optional[dict]:
    """The idle time of the slice's idlest device, as shares of the
    slice in percent, by what the dispatcher's thread was inside:
    ``staging`` (pack, stage_in, pull, resolve), ``collect``,
    ``solve``, ``elsewhere``; ``total`` is their sum, which is
    ``device_idle_pct``."""
    t = ctx["trace"]
    pt = for_ctx(ctx)
    if pt is None or not t.devices or t.hi <= t.lo:
        return None
    line = pt.dispatcher_line()
    if line is None or not aligned(ctx, pt):
        return None
    dev = min(t.devices, key=lambda d: T.total(t.busy(d)))
    idle = T.complement(t.busy(dev), t.lo, t.hi)
    width = t.hi - t.lo

    def inside(stages):
        names = {PREFIX + "serve." + n for n in stages}
        cover = T.clip([(h[0], h[1]) for h in pt.host
                        if h[3] == line and h[2] in names], t.lo, t.hi)
        return 100.0 * T.total(intersect(idle, cover)) / width

    out = {"staging": inside(STAGING), "collect": inside(("collect",)),
           "solve": inside(("solve",)),
           "total": 100.0 * T.total(idle) / width}
    out["elsewhere"] = out["total"] - out["staging"] - out["collect"] \
        - out["solve"]
    ctx["log"]("program_trace: idle %(total).2f %% of the slice = staging "
               "%(staging).2f + collect %(collect).2f + solve %(solve).2f "
               "+ elsewhere %(elsewhere).2f" % out)
    return out


def stage_times(ctx) -> Optional[Dict[int, Dict[str, float]]]:
    """Per batch of the slice (its ``serve.batch`` span inside it):
    nanoseconds in each of the dispatcher's stage spans."""
    t = ctx["trace"]
    pt = for_ctx(ctx)
    if pt is None:
        return None
    out: Dict[int, Dict[str, float]] = {}
    whole = {h[4].get("batch") for h in pt.spans("serve.batch", t.lo, t.hi)}
    stages = {PREFIX + "serve." + n: n for n in STAGING + ("solve",)}
    for s, e, name, _, stats in pt.host:
        if name in stages and stats.get("batch") in whole:
            row = out.setdefault(stats["batch"], {})
            row[stages[name]] = row.get(stages[name], 0.0) + (e - s)
    return out or None


# ----------------------------------------------------- solvers: scopes
@once
def operator_split(ctx) -> Optional[Dict[str, float]]:
    """Device milliseconds per iteration of leaf ops under a ``pmt.``
    operator scope inside the benchmark's ``cb.solve`` spans, by the
    innermost scope's name, mean over the devices — the same spans and
    divisor as ``iter_device_ms``."""
    t = ctx["trace"]
    pt = for_ctx(ctx)
    if pt is None or not t.devices:
        return None
    if not pt.scoped:
        ctx["log"]("program_trace: no pmt scope in the trace (executable "
                   "served from a compile cache older than the scopes?)")
        return None
    if not aligned(ctx, pt):
        return None
    niter = ctx["records"]["iterations_per_solve"]
    acc: Dict[str, float] = {}
    ndev = 0
    for d in t.devices:
        held = [(s, e) for s, e in t.span_list("solve") if t.ops(d, s, e)]
        if not held:
            continue
        ndev += 1
        for s, e, _, scopes in pt.ops.get(d, ()):
            mine = [sc for sc in scopes if not sc.startswith(NOT_OPERATOR)]
            part = sum(T.total(T.clip([(s, e)], lo, hi))
                       for lo, hi in held) if mine else 0.0
            if part:
                acc[mine[-1]] = acc.get(mine[-1], 0.0) \
                    + part / (len(held) * niter)
    if not ndev:
        return None
    out = {k: v / ndev / 1e6 for k, v in sorted(acc.items())}
    ctx["log"]("program_trace: device ms per iteration by scope: "
               + ", ".join(f"{k} {v:.3f}" for k, v in out.items()))
    return out
