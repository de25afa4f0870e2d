"""Ops and kernels: the least time one chip could take for an
iteration's two complex products (``dep.fredholm_cost``: the kernel
read ONCE at its stored 8 bytes an element plus the four spectra,
against both products' flops at ``highest``, the larger;
``peaks.json``) over the device time an iteration under
``pmt.MPIFredholm1.*`` — the product's share of its roofline. Two
honest sweeps of the kernel read near 45 %; a one-sweep product cannot
pass 100. A deployment without the product, or a program without the
scope, has nothing to read."""
from chipbench import costs, scope_time


def read(ctx):
    dep = ctx["deployment"]
    if ctx["peaks"] is None or not hasattr(dep, "fredholm_cost"):
        return None
    ms = scope_time.under(ctx, "pmt.MPIFredholm1.")
    if not ms:
        return None
    floor = costs.least_seconds(dep.fredholm_cost(), ctx["peaks"], dep.dtype)
    ctx["log"](f"fredholm_roofline_pct: floor {1e3 * floor['seconds']:.4f} "
               f"ms ({floor['binds']} bind: flops "
               f"{1e3 * floor['flops_s']:.4f} ms, bytes "
               f"{1e3 * floor['bytes_s']:.4f} ms) over measured {ms:.4f} ms "
               "an iteration")
    return 100.0 * floor["seconds"] * 1e3 / ms
