"""Largest ``memory_stats()["peak_bytes_in_use"]`` over the cell's
devices after the window, in GB (1e9 bytes)."""


def read(ctx):
    peak = ctx["device"]["memory_peak_bytes"]
    return peak / 1e9 if peak else None
