"""Operations and bytes one solver iteration needs, computed from the
configuration's shapes, and the least time a chip could take for them.
The arithmetic is a copy of what ``pylops_mpi_tpu/diagnostics/
costmodel.py`` (``OpCost``) does for these two operators; no peak is
taken from there — peaks come from ``peaks.json`` alone.

Every function here returns the work of ONE chip for ONE iteration of
CGLS (one forward and one adjoint product) at ``k`` right-hand-side
columns, as ``{"flops", "bytes"}``. The byte floor reads the operator
ONCE per iteration at its stored dtype (a one-sweep normal-equations
kernel can do both products from one read) plus the vectors both
products touch.
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def blockdiag(sizes: dict, k: int = 1) -> dict:
    """``MPIBlockDiag`` of ``blocks_per_chip`` dense ``n x n`` blocks."""
    n, nblk = int(sizes["n"]), int(sizes["blocks_per_chip"])
    item = int(sizes.get("itemsize", 4))
    flops = 2 * (2 * nblk * n * n * k)             # A c and A^H s
    vec = nblk * n * k * 4
    return {"flops": float(flops),
            "bytes": float(nblk * n * n * item + 4 * vec)}


def summa(sizes: dict, k: int = 1) -> dict:
    """SUMMA ``A (N x K)`` on a ``pr x pc`` grid against ``M`` columns:
    each chip owns one ``N/pr x K/pc`` tile."""
    N, K, M = int(sizes["N"]), int(sizes["K"]), int(sizes["M"])
    pr, pc = (int(g) for g in sizes["grid"])
    item = int(sizes.get("itemsize", 4))
    tile = (N // pr) * (K // pc)
    flops = 2 * (2 * tile * M * k)
    vec = (N // pr + K // pc) * M * k * 4
    return {"flops": float(flops), "bytes": float(tile * item + 2 * vec)}


def peaks(device_kind: str) -> dict:
    """The peak table's row for ``device_kind``; unknown is an error."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(
            f"device_kind {device_kind!r} is not in chipbench/peaks.json "
            f"(known: {[k for k in table if not k.startswith('_')]}); "
            "add a sourced row, there is no default")
    return table[device_kind]


def least_seconds(cost: dict, peak: dict, dtype: str = "float32") -> dict:
    """Roofline floor of ``cost`` on a chip with ``peak``: the larger
    of flops over peak FLOP/s and bytes over peak bytes/s, and which
    of the two binds."""
    fl = peak["bf16_flops_per_s"]
    if dtype == "float32":
        fl = fl / peak["f32_passes"]
    t_f = cost["flops"] / fl
    t_b = cost["bytes"] / peak["hbm_bytes_per_s"]
    return {"seconds": max(t_f, t_b), "flops_s": t_f, "bytes_s": t_b,
            "binds": "flops" if t_f >= t_b else "bytes"}
