"""From a profiler trace (``.xplane.pb``) to intervals and numbers.

The reduction every PR uses, kept with the benchmark so that no PR
that claims a gain can change how its numbers are read. It reads the
file with ``jax.profiler.ProfileData`` and nothing else.

What it takes from the trace:

- **device planes** (``/device:TPU:<i>``): the events of the op line
  (``XLA Ops``), under the instruction's name (the trace prints the
  whole HLO instruction; the part before `` = `` is kept, so that an
  operand's name is never mistaken for the op's). A ``while`` is drawn
  as one long event with its body's ops nested inside it, so only LEAF
  events count as the device doing something — a container is not
  work, and counting it would hide every gap inside a solver loop.
  The line ``Async XLA Ops`` holds asynchronous operations from start
  to done; its collectives count as in flight, never as busy;
- **host plane** (``/host:CPU``): the benchmark's own
  ``jax.profiler.TraceAnnotation`` spans, whose names start with
  ``cb.`` (``cb.solve``, ``cb.submit``, ``cb.wait``, ``cb.pull``, and
  the slice marker ``cb.slice``).

All times are nanoseconds on the trace's clock; intervals are
``(start, end)`` pairs.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

SPAN_PREFIX = "cb."
SLICE = "cb.slice"
OP_LINES = ("XLA Ops",)
ASYNC_LINES = ("Async XLA Ops",)
# lines of a device plane that never hold single operations
NOT_OP_LINES = ("Steps", "XLA Modules", "XLA TraceMe", "Framework Ops",
                "Framework Name Scope", "Source code", "Launch Stats"
                ) + ASYNC_LINES
_COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all",
    re.I)
_ASYNC = re.compile(r"-(start|done)(\.|\s|$)", re.I)


# ------------------------------------------------------------- intervals
def union(iv: Iterable[Interval]) -> List[Interval]:
    """Merged, sorted, non-overlapping cover of ``iv``."""
    out: List[Interval] = []
    for s, e in sorted(iv):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(iv: Iterable[Interval]) -> float:
    """Summed length of a union."""
    return float(sum(e - s for s, e in union(iv)))


def clip(iv: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in iv
            if min(e, hi) > max(s, lo)]


def subtract(a: Iterable[Interval], b: Iterable[Interval]
             ) -> List[Interval]:
    """The part of ``union(a)`` that ``union(b)`` does not cover."""
    out: List[Interval] = []
    b = union(b)
    j = 0
    for s, e in union(a):
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def complement(iv: Iterable[Interval], lo: float, hi: float
               ) -> List[Interval]:
    """The gaps of ``iv`` inside ``[lo, hi]``."""
    return subtract([(lo, hi)], iv)


def leaves(events: Sequence[Tuple[float, float, str]]
           ) -> List[Tuple[float, float, str]]:
    """Events of one line that hold no other event of that line."""
    ev = sorted(events, key=lambda t: (t[0], -(t[1])))
    out = []
    for i, (s, e, name) in enumerate(ev):
        nxt = ev[i + 1] if i + 1 < len(ev) else None
        # sorted by start (ties: longest first), so an event is a
        # container exactly when the next one starts inside it
        if nxt is not None and nxt[0] < e and nxt[1] <= e \
                and (nxt[0], nxt[1]) != (s, e):
            continue
        out.append((s, e, name))
    return out


def is_collective(name: str) -> bool:
    return bool(_COLLECTIVE.search(name))


_OPCODE = re.compile(r"\s((?:" + _COLLECTIVE.pattern
                     + r")(?:-start|-done)?)\(", re.I)


def short(name: str) -> str:
    """The instruction's own name out of the HLO text the trace
    prints: ``%fusion.3 = f32[..] fusion(..)`` -> ``fusion.3``. A
    collective whose name hides its opcode (``jax.lax.psum`` gives
    ``%psum.17 = .. all-reduce(..)``) gets the opcode appended:
    ``psum.17 all-reduce``."""
    head, sep, rest = name.partition(" = ")
    head = head.strip().lstrip("%")
    if sep and not _COLLECTIVE.search(head):
        m = _OPCODE.search(" " + rest)
        if m:
            head += " " + m.group(1).lower()
    return head


# ----------------------------------------------------------------- trace
class Trace:
    """One reduced trace: per device the leaf op events, on the host
    the benchmark's spans, and the slice the numbers are taken over."""

    def __init__(self, devices: Dict[str, List[Tuple[float, float, str]]],
                 spans: Dict[str, List[Interval]],
                 asyncs: Optional[Dict[str, list]] = None):
        self.devices = {d: sorted(ev) for d, ev in sorted(devices.items())}
        self.asyncs = {d: sorted((asyncs or {}).get(d, []))
                       for d in self.devices}
        # the op line is serial, so leaf events sorted by start are
        # sorted by end too: a window is then two bisections
        self._starts = {d: [s for s, _, _ in ev]
                        for d, ev in self.devices.items()}
        self._ends = {d: [e for _, e, _ in ev]
                      for d, ev in self.devices.items()}
        self._serial = {d: all(a <= b for a, b in zip(e, e[1:]))
                        for d, e in self._ends.items()}
        self.spans = {k: sorted(v) for k, v in spans.items()}
        marks = self.spans.get(SLICE)
        if marks:
            self.lo, self.hi = marks[0][0], marks[-1][1]
        else:
            ev = [t for d in self.devices.values() for t in d]
            self.lo = min((s for s, _, _ in ev), default=0.0)
            self.hi = max((e for _, e, _ in ev), default=0.0)

    # ------------------------------------------------------------ basics
    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) / 1e9

    def ops(self, dev: str, lo: Optional[float] = None,
            hi: Optional[float] = None, collective: Optional[bool] = None
            ) -> List[Tuple[float, float, str]]:
        """Leaf op events of ``dev`` cut to ``[lo, hi]`` (default the
        slice); ``collective`` True/False keeps only such ops."""
        lo = self.lo if lo is None else lo
        hi = self.hi if hi is None else hi
        out = []
        first = bisect.bisect_right(self._ends[dev], lo) \
            if self._serial[dev] else 0
        last = bisect.bisect_left(self._starts[dev], hi)
        for s, e, name in self.devices[dev][first:last]:
            if e <= lo or s >= hi:
                continue
            if collective is not None and is_collective(name) != collective:
                continue
            out.append((max(s, lo), min(e, hi), name))
        return out

    def busy(self, dev: str, lo=None, hi=None) -> List[Interval]:
        return union((s, e) for s, e, _ in self.ops(dev, lo, hi))

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over devices."""
        if not self.devices:
            return 0.0
        return sum(total(self.busy(d)) for d in self.devices) \
            / len(self.devices) / 1e9

    def idle_share(self) -> Optional[float]:
        """1 - busy/slice on the WORST (idlest) device."""
        if not self.devices or self.hi <= self.lo:
            return None
        return max(1.0 - total(self.busy(d)) / (self.hi - self.lo)
                   for d in self.devices)

    def span_list(self, name: str) -> List[Interval]:
        """The benchmark's spans called ``name`` lying inside the slice."""
        return [(s, e) for s, e in self.spans.get(SPAN_PREFIX + name, [])
                if s >= self.lo and e <= self.hi]

    def per_span(self, name: str, dev: str
                 ) -> List[Tuple[float, float, float]]:
        """For each benchmark span ``name`` in the slice that holds at
        least one op of ``dev``: ``(first op's start, last op's end,
        busy ns)``."""
        out = []
        for s, e in self.span_list(name):
            ops = self.ops(dev, s, e)
            if ops:
                out.append((min(o[0] for o in ops), max(o[1] for o in ops),
                            total((a, b) for a, b, _ in ops)))
        return out

    # ------------------------------------------------------- collectives
    def collective_intervals(self, dev: str, lo=None, hi=None
                             ) -> List[Interval]:
        """When a collective was in flight on ``dev``: a synchronous
        collective's own event, and for an asynchronous pair the time
        from the ``-start`` event's begin to the matching ``-done``
        event's end (paired first-in first-out per kind), and every
        collective on the asynchronous line."""
        lo_ = self.lo if lo is None else lo
        hi_ = self.hi if hi is None else hi
        out: List[Interval] = clip(
            [(s, e) for s, e, n in self.asyncs[dev] if is_collective(n)],
            lo_, hi_)
        open_: Dict[str, List[float]] = {}
        for s, e, name in self.ops(dev, lo, hi, collective=True):
            m = _ASYNC.search(name)
            kind = _COLLECTIVE.search(name).group(0).lower()
            if m is None:
                out.append((s, e))
            elif m.group(1).lower() == "start":
                open_.setdefault(kind, []).append(s)
                out.append((s, e))
            else:
                q = open_.get(kind)
                out.append((q.pop(0), e) if q else (s, e))
        return union(out)

    def collective_exposed(self, dev: str, lo=None, hi=None
                           ) -> Tuple[float, float]:
        """``(in_flight_ns, exposed_ns)``: the exposed part is the time
        a collective was in flight while no other op ran on ``dev``."""
        coll = self.collective_intervals(dev, lo, hi)
        other = union((s, e) for s, e, _ in
                      self.ops(dev, lo, hi, collective=False))
        return total(coll), total(subtract(coll, other))

    # -------------------------------------------------------------- gaps
    def gaps(self, dev: str, top: int = 10) -> List[Tuple[str, float]]:
        """The longest idle gaps of ``dev`` inside the slice, each
        named by the benchmark span the host was in at the gap's
        middle (``outside spans`` when in none), as (name, seconds)."""
        named = [(s, e, k[len(SPAN_PREFIX):]) for k, v in self.spans.items()
                 if k != SLICE for s, e in v]
        longest = sorted(complement(self.busy(dev), self.lo, self.hi),
                         key=lambda g: g[0] - g[1])[:top]
        out = []
        for s, e in longest:
            mid = (s + e) / 2
            # innermost span holding the middle
            hold = [(ee - ss, n) for ss, ee, n in named if ss <= mid <= ee]
            out.append((min(hold)[1] if hold else "outside spans",
                        (e - s) / 1e9))
        return out

    def top_ops(self, top: int = 10) -> List[Tuple[str, float]]:
        """Device operations that took most time in the slice, summed
        by the name the trace prints, averaged over devices."""
        acc: Dict[str, float] = {}
        for d in self.devices:
            for s, e, name in self.ops(d):
                acc[name] = acc.get(name, 0.0) + (e - s)
        n = max(1, len(self.devices))
        rows = sorted(acc.items(), key=lambda t: -t[1])[:top]
        return [(k, v / n / 1e9) for k, v in rows]


# ---------------------------------------------------------------- reading
def newest_xplane(logdir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")),
        key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return files[-1]


def load(path: str) -> Trace:
    """Reduce the ``.xplane.pb`` at ``path``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices: Dict[str, list] = {}
    asyncs: Dict[str, list] = {}
    spans: Dict[str, list] = {}
    triple = lambda e: (float(e.start_ns),
                        float(e.start_ns + e.duration_ns), short(e.name))
    for plane in data.planes:
        pname = plane.name or ""
        if pname.startswith("/device:") and "TPU" in pname.upper() \
                and not re.search(r"sparse|host", pname, re.I):
            lines = list(plane.lines)
            pick = [l for l in lines if l.name in OP_LINES] or \
                [l for l in lines if l.name not in NOT_OP_LINES]
            ev = []
            for line in pick:
                ev.extend(leaves([triple(e) for e in line.events]))
            if ev:
                devices[pname] = ev
                asyncs[pname] = [triple(e) for l in lines
                                 if l.name in ASYNC_LINES for e in l.events]
        elif pname.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.setdefault(e.name, []).append(
                            (float(e.start_ns),
                             float(e.start_ns + e.duration_ns)))
    return Trace(devices, spans, asyncs)
