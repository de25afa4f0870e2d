"""The Solvers layer's own names in a traced run, read beside
``program_trace``'s with the same spans, clock check and divisor.

Inside the fused program the recurrence's own passes carry the scopes
``pmt.solver.setup`` / ``step`` / ``direction`` / ``cost``
(``pylops_mpi_tpu/solvers/basic.py``, ``block.py``), never around an
operator apply; around it the wrapper's two host phases are the spans
``pmt.solver.launch`` and ``pmt.solver.collect``. Two reductions:

- :func:`own_split`: what ``solver_self_device_ms`` takes by
  subtraction, by name — the device time an iteration of the leaf ops
  that carry no operator scope, keyed by their innermost
  ``pmt.solver.*`` scope or ``unscoped`` (no ``pmt.`` component at
  all: what the compiler put between the program's parts);
- :func:`host_phases`: the idle time of the idlest device inside each
  clocked span (``program_trace.CLOCKED``), split at ``launch``'s end
  and at the first and last op of the program.

A program compiled before the names existed (a parent, or a compile
cache it filled under JAX's default key, which ignores metadata) has
nothing to read: both say so on the log and return ``None``.
"""

from __future__ import annotations

from typing import Dict, Optional

from chipbench import program_trace as P
from chipbench import stats
from chipbench import trace_reduce as T

SOLVER = P.PREFIX + "solver."
UNSCOPED = "unscoped"
UPDATE = ("setup", "step", "direction")
COST = "cost"
PHASES = ("launch", "input", "loop", "tail")


# ------------------------------------------------- inside the program
@P.once
def own_split(ctx) -> Optional[Dict[str, float]]:
    """Device milliseconds an iteration of the leaf ops inside the
    ``cb.solve`` spans that carry no operator scope — the complement
    of ``program_trace.operator_split`` — by the innermost
    ``pmt.solver.<part>`` scope (key ``<part>``) or ``unscoped``; mean
    over the devices. ``None`` where ``operator_split`` reads nothing,
    or where no op carries a ``pmt.solver.`` scope."""
    if P.operator_split(ctx) is None:      # no trace, no scope, clocks
        return None
    t, pt = ctx["trace"], P.for_ctx(ctx)
    niter = ctx["records"]["iterations_per_solve"]
    acc: Dict[str, float] = {}
    names: Dict[str, float] = {}
    ndev = 0
    for d in t.devices:
        held = [(s, e) for s, e in t.span_list("solve") if t.ops(d, s, e)]
        if not held:
            continue
        ndev += 1
        for s, e, name, scopes in pt.ops.get(d, ()):
            if any(not sc.startswith(P.NOT_OPERATOR) for sc in scopes):
                continue                   # an operator's
            part = sum(T.total(T.clip([(s, e)], lo, hi))
                       for lo, hi in held) / (len(held) * niter)
            if not part:
                continue
            mine = [sc for sc in scopes if sc.startswith(SOLVER)]
            key = mine[-1][len(SOLVER):] if mine else UNSCOPED
            acc[key] = acc.get(key, 0.0) + part
            if key == UNSCOPED:
                names[name] = names.get(name, 0.0) + part
    if not ndev:
        return None
    if not set(acc) - {UNSCOPED}:
        ctx["log"]("solver_phases: no pmt.solver scope in the trace (a "
                   "program from before the solver's scopes, or an "
                   "executable served from a compile cache older than "
                   "them)")
        return None
    out = {k: acc.get(k, 0.0) / ndev / 1e6
           for k in sorted(set(acc) | set(UPDATE) | {COST, UNSCOPED})}
    top = sorted(names.items(), key=lambda kv: -kv[1])[:5]
    ctx["log"]("solver_phases: solver's own device ms per iteration: "
               + ", ".join(f"{k} {v:.3f}" for k, v in out.items())
               + "; largest unscoped: "
               + (", ".join(f"{n} {v / ndev / 1e6:.3f}" for n, v in top)
                  or "none"))
    return out


def own(ctx, parts) -> Optional[float]:
    """The sum of ``own_split``'s ``parts``."""
    split = own_split(ctx)
    return None if split is None else sum(split[p] for p in parts)


# ------------------------------------------------- around the program
def _inside(pt: P.ProgramTrace, name: str, outer: P.HostSpan
            ) -> Optional[P.HostSpan]:
    """The first ``pmt.solver.<name>`` span on ``outer``'s thread lying
    inside it."""
    return next((h for h in pt.spans("solver." + name, outer[0], outer[1])
                 if h[3] == outer[3]), None)


@P.once
def host_phases(ctx) -> Optional[dict]:
    """Per clocked span of the slice (a ``pmt.serve.solve`` on the
    dispatcher's thread; else a ``pmt.solver.cgls``): the durations of
    the ``pmt.solver.launch`` and ``collect`` inside it, and the idle
    time of the slice's idlest device inside the span — cut to the
    slice, as ``idle_split`` cuts it — in four parts: ``launch`` from
    the span's start to ``launch``'s end, ``input`` from there to the
    first op that carries a ``pmt.`` scope, ``loop`` between that op
    and the last such, ``tail`` after it.

    Returns ``{"solves", "launch_ms", "collect_ms",
    "before_launch_ms"`` (medians over the spans that lie whole in the
    slice)``, "idle_ms": {part: median ms a solve}, "idle_pct": {part:
    per cent of the slice}}``; ``None`` without a trace, aligned clocks
    or the two spans."""
    t = ctx.get("trace")
    pt = P.for_ctx(ctx)
    if pt is None or not t.devices or t.hi <= t.lo:
        return None
    if not {SOLVER + "launch", SOLVER + "collect"} \
            <= {h[2] for h in pt.host}:
        ctx["log"]("solver_phases: no pmt.solver.launch / collect span "
                   "in the trace (a program from before them)")
        return None
    if not P.aligned(ctx, pt):
        return None
    line = pt.dispatcher_line()
    name = P.CLOCKED[0] if line is not None else P.CLOCKED[1]
    dev = min(t.devices, key=lambda d: T.total(t.busy(d)))
    idle = T.complement(t.busy(dev), t.lo, t.hi)
    rows = []
    for outer in pt.host:
        s, e = outer[0], outer[1]
        if outer[2] != name or e <= t.lo or s >= t.hi \
                or line not in (None, outer[3]):
            continue
        la, co = _inside(pt, "launch", outer), _inside(pt, "collect", outer)
        mine = [o for o in pt.ops.get(dev, ())
                if o[3] and o[1] > s and o[0] < e]
        if la is None or co is None or not mine:
            continue
        first, last = mine[0][0], max(o[1] for o in mine)
        cuts = (s, la[1], max(first, la[1]), max(last, la[1]), e)
        rows.append({
            "whole": s >= t.lo and e <= t.hi, "launch_span": la[1] - la[0],
            "collect_span": co[1] - co[0], "before": la[0] - s,
            **{p: T.total(P.intersect(idle, [(lo, hi)]))
               for p, lo, hi in zip(PHASES, cuts, cuts[1:])}})
    whole = [r for r in rows if r["whole"]]  # a span the slice cuts is
    if not whole:                            # no sample of a solve
        return None
    med = lambda key: stats.median([r[key] for r in whole]) / 1e6
    out = {"solves": len(whole), "launch_ms": med("launch_span"),
           "collect_ms": med("collect_span"),
           "before_launch_ms": med("before"),
           "idle_ms": {p: med(p) for p in PHASES},
           "idle_pct": {p: 100.0 * sum(r[p] for r in rows) / (t.hi - t.lo)
                        for p in PHASES}}
    ctx["log"](
        f"solver_phases: {len(whole)} {name} spans; median ms a solve: "
        f"launch {out['launch_ms']:.3f} (opens "
        f"{out['before_launch_ms']:.3f} after the span's start), collect "
        f"{out['collect_ms']:.3f}; device idle inside them, median ms a "
        "solve / % of the slice: " + ", ".join(
            f"{p} {out['idle_ms'][p]:.3f} / {out['idle_pct'][p]:.3f}"
            for p in PHASES)
        + f"; sum {sum(out['idle_pct'].values()):.3f} %")
    return out


def idle_share(ctx, part: str) -> Optional[float]:
    """``host_phases``' idle ``part`` as a share of the slice, %."""
    found = host_phases(ctx)
    return None if found is None else found["idle_pct"][part]
