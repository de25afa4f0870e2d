"""Ops and kernels: device time a SWEEP PAIR (one forward and one
adjoint apply) of the leaf ops lowered under
``pmt.local.TravelTimeSpray`` — the indexed spray and gather of the
Kirchhoff operator, with whatever the compiler put inside their scope
(padding, the reshapes around the kernels). ``scope_time.under``
divides a solve's time by its iterations; the recurrence makes one
sweep pair before its loop and one an iteration, ``niter + 1`` a solve,
and here the operator is the whole solve: divided by the iterations a
pair would read ``(niter + 1) / niter`` too long, and move with the
cell's depth. A program without the scope has nothing to read."""
from chipbench import scope_time


def read(ctx):
    ms = scope_time.under(ctx, "pmt.local.TravelTimeSpray")
    if not ms:
        return None
    niter = ctx["records"]["iterations_per_solve"]
    return ms * niter / (niter + 1)
