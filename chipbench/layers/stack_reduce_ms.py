"""Collectives: device time a SWEEP PAIR (one forward and one adjoint
apply) under ``pmt.collective.stack_reduce`` — the one all-reduce of the
image that a sharded ``MPIVStack``'s adjoint makes (``ops/stack.py``):
the transfer in flight plus each chip's wait there for the slowest
shard — mean over the devices, a solve's time there over its ``niter +
1`` pairs as ``kirchhoff_device_ms`` divides. The log line gives each
device's time under ``pmt.local.TravelTimeSpray`` and under the scope, so
the imbalance between the shards reads beside it. A program without the
scope (a replicated stack, a program from before it) has nothing to
read."""
from chipbench import program_trace
from chipbench import trace_reduce as T

SCOPE = "pmt.collective.stack_reduce"
SPRAY = "pmt.local.TravelTimeSpray"


def by_device(ctx, prefix: str):
    """``({device: ms an iteration under prefix}, seen)`` over the
    devices that ran inside the ``cb.solve`` spans — the spans, clock
    check and divisor of ``scope_time.under``, one device at a time."""
    t = ctx.get("trace")
    pt = program_trace.for_ctx(ctx)
    if pt is None or not t.devices or not pt.scoped:
        return {}, False
    if program_trace.operator_split(ctx) is None:    # the clock check
        return {}, False
    niter = ctx["records"]["iterations_per_solve"]
    out, seen = {}, False
    for d in t.devices:
        held = [(s, e) for s, e in t.span_list("solve") if t.ops(d, s, e)]
        if not held:
            continue
        total = 0.0
        for s, e, _, scopes in pt.ops.get(d, ()):
            if any(sc.startswith(prefix) for sc in scopes):
                seen = True
                total += sum(T.total(T.clip([(s, e)], lo, hi))
                             for lo, hi in held) / (len(held) * niter)
        out[d] = total / 1e6
    return out, seen


def read(ctx):
    reduce_, seen = by_device(ctx, SCOPE)
    if not seen:
        return None
    niter = ctx["records"]["iterations_per_solve"]
    pair = niter / (niter + 1)
    spray, _ = by_device(ctx, SPRAY)
    ctx["log"]("stack_reduce_ms: ms a sweep pair by device, under "
               f"{SPRAY} / {SCOPE}: " + ", ".join(
                   f"{d} {spray.get(d, 0.0) * pair:.6g} / "
                   f"{reduce_[d] * pair:.6g}" for d in sorted(reduce_)))
    return sum(reduce_.values()) / len(reduce_) * pair
