"""Solvers: ``iter_device_ms`` less ``operator_device_ms`` — the
solver's own vector updates and reductions."""
from chipbench.layers import iter_device_ms, operator_device_ms


def read(ctx):
    whole, ops = iter_device_ms.read(ctx), operator_device_ms.read(ctx)
    return None if whole is None or ops is None else whole - ops
