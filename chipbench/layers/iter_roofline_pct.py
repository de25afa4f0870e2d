"""Ops and kernels: the least time one chip could take for one
iteration's operator work (costs.py, peaks.json) over the device time
an iteration took (``iter_device_ms``)."""
from chipbench import costs
from chipbench.layers import iter_device_ms


def read(ctx):
    ms = iter_device_ms.read(ctx)
    if ms is None or ctx["peaks"] is None:
        return None
    dep = ctx["deployment"]
    floor = costs.least_seconds(dep.cost(ctx["records"].get("columns", 1)),
                                ctx["peaks"], dep.dtype)
    ctx["log"](f"iter_roofline_pct: floor {1e3 * floor['seconds']:.4f} ms "
               f"({floor['binds']} bind: flops {1e3 * floor['flops_s']:.4f} "
               f"ms, bytes {1e3 * floor['bytes_s']:.4f} ms) over measured "
               f"{ms:.4f} ms an iteration")
    return 100.0 * floor["seconds"] * 1e3 / ms
