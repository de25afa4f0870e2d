"""Device time an iteration of the leaf ops lowered UNDER a program
scope — anywhere in their ``op_name`` path, not only innermost as
``program_trace.operator_split`` keys them — inside the benchmark's
``cb.solve`` spans: the same spans, clock check and divisor as
``operator_device_ms``, mean over the devices. For the layers that
split one operator's time by what it is composed of
(``pmt.MPIBlockDiag.*`` ⊃ ``pmt.local.Conv1D``)."""

from __future__ import annotations

from typing import Optional

from chipbench import program_trace
from chipbench import trace_reduce as T


def under(ctx, prefix: str) -> Optional[float]:
    """Milliseconds an iteration under any scope that starts with
    ``prefix``; ``None`` where the run took no trace, the clocks do not
    align, or no op carries such a scope (a program without it)."""
    t = ctx.get("trace")
    pt = program_trace.for_ctx(ctx)
    if pt is None or not t.devices or not pt.scoped:
        return None
    if program_trace.operator_split(ctx) is None:    # the clock check
        return None
    niter = ctx["records"]["iterations_per_solve"]
    total, ndev, seen = 0.0, 0, False
    for d in t.devices:
        held = [(s, e) for s, e in t.span_list("solve") if t.ops(d, s, e)]
        if not held:
            continue
        ndev += 1
        for s, e, _, scopes in pt.ops.get(d, ()):
            if any(sc.startswith(prefix) for sc in scopes):
                seen = True
                total += sum(T.total(T.clip([(s, e)], lo, hi))
                             for lo, hi in held) / (len(held) * niter)
    return total / ndev / 1e6 if ndev and seen else None
