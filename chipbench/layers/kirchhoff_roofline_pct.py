"""Ops and kernels: the least time one chip could take for an
iteration's two indexed applies (``dep.kirchhoff_cost``: the per-pair
tables read ONCE at their stored 8 bytes an entry plus the image and
trace streams, against 8 flops a pair-pixel at the float32 peak, the
larger; ``peaks.json``) over the device time a sweep pair under
``pmt.local.TravelTimeSpray`` (``kirchhoff_device_ms``: a solve's time
there over its ``niter + 1`` pairs) — the kernel's share of its roofline.
Two honest sweeps of the tables cannot pass 50 %; one fused sweep
cannot pass 100. A deployment without the operator, or a program
without the scope, has nothing to read."""
from chipbench import costs
from chipbench.layers import kirchhoff_device_ms


def read(ctx):
    dep = ctx["deployment"]
    if ctx["peaks"] is None or not hasattr(dep, "kirchhoff_cost"):
        return None
    ms = kirchhoff_device_ms.read(ctx)
    if not ms:
        return None
    floor = costs.least_seconds(dep.kirchhoff_cost(), ctx["peaks"],
                                dep.dtype)
    ctx["log"](f"kirchhoff_roofline_pct: floor "
               f"{1e3 * floor['seconds']:.4f} ms ({floor['binds']} bind: "
               f"flops {1e3 * floor['flops_s']:.4f} ms, bytes "
               f"{1e3 * floor['bytes_s']:.4f} ms) over measured {ms:.4f} "
               "ms a sweep pair")
    return 100.0 * floor["seconds"] * 1e3 / ms
