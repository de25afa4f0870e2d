"""Pallas TPU kernels for the stencil hot loops.

The reference's hot stencil path is ghost-cell exchange + NumPy slicing
per rank (SURVEY §3.3). Here the default path is already a fused XLA
stencil; this module adds hand-written Pallas kernels for the
first/second-derivative inner loops so the shift+subtract+scale chain is
a single VMEM pass instead of several HLO slices — useful when the
operator is applied standalone (XLA fuses it into neighbours anyway when
composed).

Kernels run natively on TPU; on CPU they fall back to ``interpret=True``
(tests) or the plain jnp formulation.
"""

from __future__ import annotations

from functools import partial

import numpy as np
import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl

__all__ = ["first_derivative_centered", "second_derivative",
           "stencil_taps", "batched_normal_matvec",
           "normal_matvec_supported", "normal_matvec_pays",
           "pallas_available"]


def pallas_available() -> bool:
    return jax.default_backend() in ("tpu", "cpu")


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _centered3(x: jax.Array, axis: int, taps) -> jax.Array:
    """Shared wrapper for the centered-3 conveniences: one
    :func:`stencil_taps` VMEM pass on the moved/flattened array, edge
    rows zeroed inside the same pass (pylops ``edge=False``), original
    layout restored."""
    v = jnp.moveaxis(x, axis, 0)
    shp = v.shape
    if shp[0] < 3:  # too short for the 3-point core: all edge rows
        return jnp.zeros_like(x)
    y = stencil_taps(v.reshape(shp[0], -1), taps, 1, out_pad=(1, 1))
    return jnp.moveaxis(y.reshape(shp), 0, axis)


def first_derivative_centered(x: jax.Array, axis: int = 0,
                              sampling: float = 1.0) -> jax.Array:
    """Centered 3-point first derivative along ``axis`` (edge rows zero,
    pylops ``edge=False``), as one Pallas VMEM pass."""
    c = 1.0 / (2.0 * sampling)
    return _centered3(x, axis, ((-1, -c), (1, c)))


def second_derivative(x: jax.Array, axis: int = 0,
                      sampling: float = 1.0) -> jax.Array:
    """3-point second derivative along ``axis`` as one Pallas pass."""
    c = 1.0 / sampling ** 2
    return _centered3(x, axis, ((-1, c), (0, -2.0 * c), (1, c)))


def _taps_kernel(x_ref, o_ref, *, taps, w: int, rows: int, pad):
    """One VMEM pass of an arbitrary static tap stencil: the slab
    (``rows + 2w`` sublanes) is loaded once and every tap is a shifted
    slice of the loaded block — XLA-level slicing would reload for
    each shift. ``pad`` zero rows are written at each end INSIDE the
    pass (the edge=False convention) so callers need no separate
    full-output pad copy."""
    g = x_ref[:]
    y = None
    for d, c in taps:  # static python loop: unrolled at trace time
        part = g[w + d: w + d + rows] * c
        y = part if y is None else y + part
    if pad != (0, 0):
        y = jnp.pad(y, [pad] + [(0, 0)] * (y.ndim - 1))
    o_ref[:] = y


# INPUT-block share of the tiled stencil's VMEM budget. True per-step
# footprint is ~4x this: input block + similarly-sized output block,
# each double-buffered by the pipeline — so 2 MB here means ~8 MB of
# the ~16 MB/core VMEM, leaving headroom for compiler scratch.
_STENCIL_TILE_BYTES = 2 << 20


def _stencil_col_tile(nrows: int, cols: int, itemsize: int) -> int:
    """Widest 128-lane-aligned column tile whose input block fits the
    VMEM budget (the whole slab when it fits); 0 when even one
    lane-width strip does not fit (caller falls back to the XLA slice
    form). The tile need not divide ``cols`` — the grid uses ceiling
    division and Mosaic masks the ragged last block (columns carry no
    stencil dependency, so masked lanes are simply unused)."""
    max_cols = _STENCIL_TILE_BYTES // max(nrows * itemsize, 1)
    if cols <= max_cols:
        return cols
    return (max_cols // 128) * 128


def stencil_taps(slab: jax.Array, taps, w: int,
                 out_pad=(0, 0)) -> jax.Array:
    """Apply the pure tap stencil ``y[j] = Σ_d c_d · slab[w + j + d]``
    to a halo-extended 2-D slab ``(rows + 2w, cols)`` → ``(pad_lo +
    rows + pad_hi, cols)``, as a Pallas VMEM pass (the generalization
    of the centered-3 kernels above to every kind/order the explicit
    distributed stencil path supports — forward/backward, centered-5,
    second-derivative offsets). Wide slabs are tiled over the column
    (lane) axis — columns carry no stencil dependency, so the grid is
    embarrassingly parallel and arbitrarily wide shards stay on the
    fused path instead of falling back to XLA slices. ``taps`` is a
    static sequence of ``(offset, coefficient)`` pairs with
    ``|offset| <= w``; ``out_pad`` prepends/appends zero rows inside
    the same pass."""
    nrows = slab.shape[0]
    rows = nrows - 2 * w
    taps = tuple(taps)
    pad = (int(out_pad[0]), int(out_pad[1]))
    cols = int(np.prod(slab.shape[1:])) if slab.ndim > 1 else 1
    tile = _stencil_col_tile(nrows, cols, slab.dtype.itemsize)
    if not pallas_available() or tile == 0:
        y = None
        for d, c in taps:
            part = slab[w + d: w + d + rows] * c
            y = part if y is None else y + part
        if pad != (0, 0):
            y = jnp.pad(y, [pad] + [(0, 0)] * (y.ndim - 1))
        return y
    shp = slab.shape
    slab2 = slab.reshape(nrows, cols)
    out_rows = pad[0] + rows + pad[1]
    y2 = pl.pallas_call(
        partial(_taps_kernel, taps=taps, w=w, rows=rows, pad=pad),
        grid=((cols + tile - 1) // tile,),
        in_specs=[pl.BlockSpec((nrows, tile), lambda j: (0, j))],
        out_specs=pl.BlockSpec((out_rows, tile), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((out_rows, cols), slab.dtype),
        interpret=_interpret(),
        name="pmt_taps",
    )(slab2)
    return y2.reshape((out_rows,) + shp[1:])


# ------------------------------------------------------- fused normal matvec
# One HBM sweep of A per CGLS iteration instead of two: within each row
# tile, t = A_tile @ x feeds u += A_tileᵀ t while the tile is still in
# VMEM, so q = A x and u = AᵀA x cost a single read of A. This is the
# solver hot-spot of SURVEY §3.2 (the reference reads its matrix once in
# matvec and once in rmatvec per iteration, ref cls_basic.py:389-397).
#
# Two kernels share the schedule:
#
# - ``_normal_kernel`` (f32 blocks): tile loaded at its own dtype,
#   dots accumulate f32.
# - ``_normal_kernel_stream`` (bf16/f16 blocks — the HBM-regime fast
#   path, ISSUE 2): the A tile streams HBM→VMEM at the NARROW dtype
#   (half the bytes of f32 — the only term that matters at 64 MB/block
#   working sets) and is widened to f32 once in VMEM; both dots and
#   the u accumulator run f32, and the (f32) x vector is never
#   narrowed — bf16 touches storage and the wire, never the solver
#   recurrence (ops/_precision.py module doc).

_VMEM_TILE_BYTES = 4 << 20  # A-tile budget (double-buffered by pipeline)


def _min_sublane(dtype) -> int:
    """Mosaic's minimum sublane multiple per dtype: 8 for 4-byte
    elements, 16 for 2-byte (bf16/f16), 32 for 1-byte — a narrow
    block's second-to-minor blocked dim must honor the packed tile."""
    return max(8, 32 // max(np.dtype(dtype).itemsize, 1))


def _pick_tile(m: int, n: int, itemsize: int, min_sublane: int = 8):
    """Row-tile honouring the VMEM budget and Mosaic's sublane rule:
    every blocked dim must be a multiple of the dtype's sublane tile
    (8 for f32, 16 for bf16) or equal to the full array dim — the
    round-3 hardware selfcheck showed tiles of 1/2/4 rows that pass in
    interpret mode are rejected by the TPU lowering. ``None`` when no
    legal tile fits (caller falls back to the generic two-sweep
    path)."""
    for tm in (512, 256, 128, 64, 32, 16, 8):
        if tm < min_sublane:
            break
        if m % tm == 0 and tm * n * itemsize <= _VMEM_TILE_BYTES:
            return tm
    if m * n * itemsize <= _VMEM_TILE_BYTES:
        return m  # whole-dim block: always legal
    return None


def _tile_args(A: jax.Array):
    """(row-tile, streaming?) for ``A``'s blocks. Narrow (sub-4-byte)
    blocks take the streaming kernel: the VMEM budget is charged for
    the f32 widened copy (worst term), the sublane rule for the narrow
    loaded block."""
    m, n = A.shape[1], A.shape[2]
    stream = A.dtype.itemsize < 4
    tm = _pick_tile(m, n, max(A.dtype.itemsize, 4),
                    min_sublane=_min_sublane(A.dtype))
    return tm, stream


def normal_matvec_supported(A: jax.Array) -> bool:
    """Pallas path requires real floating blocks (complex dots fall back
    to the generic two-sweep path) for which a Mosaic-legal row tile
    fits the VMEM budget — otherwise the generic path must be used."""
    if not (pallas_available() and A.ndim == 3
            and not jnp.iscomplexobj(A)):
        return False
    return _tile_args(A)[0] is not None


def _tile_beats_two_sweeps(tm: int, n: int, itemsize: int) -> bool:
    """Whether the chip has shown the one-sweep kernel with a
    ``(tm, n)`` row tile of ``itemsize``-byte elements faster than the
    XLA matvec + rmatvec pair it replaces. A grid step costs about
    0.45 us beside its tile's copy, so the tile's bytes decide (v5e,
    4.3 GB of blocks, n from 256 to 16384, f32 and bf16; the table is
    in PERF.md section 6, PR 26): tiles of 1 MiB and more ran 1.23-2.06 x as
    fast as two sweeps (64 rows at n=16384 and 128 at n=8192 among
    them), 512 KiB tiles 1.12-1.39 x, 256 KiB tiles 0.73-0.995 x,
    8-row tiles 0.2-0.5 x."""
    return tm * n * itemsize >= 512 << 10


def normal_matvec_pays(A: jax.Array) -> bool:
    """Whether :func:`batched_normal_matvec` on ``A``'s blocks is worth
    taking unasked: a compiled Mosaic kernel (on the CPU Pallas runs in
    interpret mode -- a perf trap inside a ``while_loop``) whose row
    tile is one the chip has shown to beat two sweeps."""
    if _interpret() or not normal_matvec_supported(A):
        return False
    tm, _ = _tile_args(A)
    return _tile_beats_two_sweeps(tm, A.shape[2], A.dtype.itemsize)


def _normal_kernel(a_ref, x_ref, u_ref, q_ref):
    i = pl.program_id(1)
    acc = jnp.promote_types(a_ref.dtype, jnp.float32)  # f32 acc for bf16/f32
    a = a_ref[0].astype(acc)                        # (TM, n)
    x = x_ref[0].astype(acc)                        # (1, n)
    t = jax.lax.dot_general(a, x, (((1,), (1,)), ((), ())),
                            preferred_element_type=acc)  # (TM, 1)
    q_ref[...] = t[None].astype(q_ref.dtype)        # block (1, TM, 1)
    u = jax.lax.dot_general(t, a, (((0,), (0,)), ((), ())),
                            preferred_element_type=acc)  # (1, n)

    @pl.when(i == 0)
    def _():
        u_ref[...] = jnp.zeros_like(u_ref)

    u_ref[...] += u[None].astype(u_ref.dtype)


def _normal_kernel_stream(a_ref, x_ref, u_ref, q_ref):
    """bf16-tile-streaming variant: ``a_ref`` is the NARROW block (its
    HBM→VMEM copy moved the narrow bytes — the streaming win); the one
    widen to f32 happens here in VMEM, and everything downstream
    (both dots, the running u accumulator, the q/u outputs) is f32.
    The x vector arrives f32 and stays f32 — no per-iteration rounding
    of solver state."""
    i = pl.program_id(1)
    a = a_ref[0].astype(jnp.float32)                # one VMEM widen/tile
    x = x_ref[0].astype(jnp.float32)                # (1, n), f32 already
    t = jax.lax.dot_general(a, x, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    q_ref[...] = t[None].astype(q_ref.dtype)
    u = jax.lax.dot_general(t, a, (((0,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)

    @pl.when(i == 0)
    def _():
        u_ref[...] = jnp.zeros_like(u_ref)

    u_ref[...] += u[None].astype(u_ref.dtype)


def batched_normal_matvec(A: jax.Array, X: jax.Array):
    """``(u, q) = (AᵀA x, A x)`` per block, reading each ``A`` block once.

    A: ``(nblk, m, n)`` real (f32, or bf16/f16 storage — the narrow
    case streams through ``_normal_kernel_stream``); X: ``(nblk, n)``,
    kept at ITS dtype (f32 for the mixed-precision solver stack).
    Returns ``u (nblk, n)``, ``q (nblk, m)`` at X's dtype. Call per
    shard (inside shard_map); on CPU runs in interpret mode. The x/u/q
    operands are staged as trivially-blocked 3-D views — a 2-D
    ``(1, n)`` block over an ``(nblk, n)`` array has a sublane dim of 1
    that is neither 8-divisible nor equal to ``nblk``, which Mosaic
    rejects.
    """
    nblk, m, n = A.shape
    tm, stream = _tile_args(A)
    if tm is None:
        raise ValueError(f"no Mosaic-legal row tile for blocks of {m}x{n}; "
                         "gate on normal_matvec_supported()")
    out_dtype = X.dtype
    u, q = pl.pallas_call(
        _normal_kernel_stream if stream else _normal_kernel,
        grid=(nblk, m // tm),
        in_specs=[pl.BlockSpec((1, tm, n), lambda b, i: (b, i, 0)),
                  pl.BlockSpec((1, 1, n), lambda b, i: (b, 0, 0))],
        out_specs=[pl.BlockSpec((1, 1, n), lambda b, i: (b, 0, 0)),
                   pl.BlockSpec((1, tm, 1), lambda b, i: (b, i, 0))],
        out_shape=[jax.ShapeDtypeStruct((nblk, 1, n), out_dtype),
                   jax.ShapeDtypeStruct((nblk, m, 1), out_dtype)],
        interpret=_interpret(),
        name="pmt_normal_stream" if stream else "pmt_normal",
    )(A, X[:, None, :])
    return u[:, 0, :], q[:, :, 0]
