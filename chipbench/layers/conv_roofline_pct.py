"""Ops and kernels: the least time one chip could take for an
iteration's convolution and its adjoint (``dep.conv_cost``: two volume
streams each against ``2 * taps`` flops an element, the larger;
``peaks.json``) over the device time an iteration under
``pmt.local.Conv1D``, forward and adjoint together — the convolution
kernel's share of its roofline. A deployment without a convolution, or
a program without the scope, has nothing to read."""
from chipbench import costs, scope_time


def read(ctx):
    dep = ctx["deployment"]
    if ctx["peaks"] is None or not hasattr(dep, "conv_cost"):
        return None
    ms = scope_time.under(ctx, "pmt.local.Conv1D")
    if not ms:
        return None
    floor = costs.least_seconds(dep.conv_cost(), ctx["peaks"], dep.dtype)
    ctx["log"](f"conv_roofline_pct: floor {1e3 * floor['seconds']:.4f} ms "
               f"({floor['binds']} bind: flops {1e3 * floor['flops_s']:.4f} "
               f"ms, bytes {1e3 * floor['bytes_s']:.4f} ms) over measured "
               f"{ms:.4f} ms an iteration")
    return 100.0 * floor["seconds"] * 1e3 / ms
