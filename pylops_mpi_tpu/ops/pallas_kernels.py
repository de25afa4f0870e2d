"""The program's Pallas kernels, by the name each carries in a device
trace. All are compiled by Mosaic on a TPU and interpreted
(``interpret=True``) on any other backend, which is how the tests run
them.

- ``pmt_taps`` (:func:`stencil_taps`; :func:`first_derivative_centered`
  and :func:`second_derivative` are conveniences over it) — an
  arbitrary static tap stencil along axis 0 of a haloed slab as one
  VMEM pass: the slab is loaded once and every tap is a shifted slice
  of the loaded block (a slab no lane-wide strip of which fits VMEM
  takes the same taps as ``jnp`` slices). The axis-0 core of the
  explicit ghost-cell path of ``ops/derivatives.py::_StencilOperator``
  on a TPU.
- ``pmt_normal`` / ``pmt_normal_stream``
  (:func:`batched_normal_matvec`) — ``(AᴴA X, A X)`` for K columns a
  block from ONE read of A: the one-sweep CGLS schedule of
  ``ops/blockdiag.py``.
- ``pmt_conv1d`` (:func:`conv1d_toeplitz`) — a stationary 1-D
  convolution along the minor axis as banded Toeplitz tiles on the MXU:
  ``ops/local.py::Conv1D``'s one form. Live in an apply: the input and
  the output, 2 volumes.
- ``pmt_laplacian`` (:func:`laplacian_stencil`) — the centered,
  ``edge=False`` Laplacian of a 2-D or 3-D array, forward or adjoint,
  as ONE pass over the cube where it lies: the form
  ``ops/derivatives.py::MPILaplacian`` takes where its rule allows.
  Live in an apply: the input and the output, 2 volumes, and two ghost
  planes.
"""

from __future__ import annotations

from functools import partial, reduce

import numpy as np
import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["first_derivative_centered", "second_derivative",
           "stencil_taps", "batched_normal_matvec",
           "normal_matvec_supported", "normal_matvec_pays",
           "conv1d_toeplitz", "conv1d_tile",
           "laplacian_stencil", "laplacian_legal",
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
# tile, T = X @ A_tileᵀ feeds U += T @ A_tile while the tile is still in
# VMEM, so Q = A X and U = AᵀA X cost a single read of A — for all K
# columns of X at once. This is the solver hot-spot of SURVEY §3.2 (the
# reference reads its matrix once in matvec and once in rmatvec per
# iteration, ref cls_basic.py:389-397).
#
# One kernel body, ``_normal_kernel``, under two names: ``pmt_normal``
# (f32/f64 blocks) and ``pmt_normal_stream`` (bf16/f16 blocks — the
# HBM-regime fast path, ISSUE 2: the A tile streams HBM→VMEM at the
# NARROW dtype, half the bytes of f32). Both dots and the U accumulator
# run f32 (f64 for f64 blocks, interpreted only), and the X columns are
# never narrowed below what ``highest`` itself does — bf16 touches
# storage and the wire, never the solver recurrence
# (ops/_precision.py module doc).
#
# Every operand has its long axis minor: X and U are ``(nblk, K, n)``,
# Q is ``(nblk, K, m)``, with K the full second-minor dim of each block
# (Mosaic-legal for any K >= 1; a vector is K = 1). A ``(nblk, m, 1)``
# Q — the vector form this kernel had through PR 29 — is tiled
# T(8,128) in HBM, so 2 MB travelled as 268 MB out of the kernel and
# into each of its readers.

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
    blocks stream: the VMEM budget is charged for the f32 widened copy
    (worst term), the sublane rule for the narrow loaded block."""
    m, n = A.shape[1], A.shape[2]
    stream = A.dtype.itemsize < 4
    tm = _pick_tile(m, n, max(A.dtype.itemsize, 4),
                    min_sublane=_min_sublane(A.dtype))
    return tm, stream


def normal_matvec_supported(A: jax.Array, K: int = 1) -> bool:
    """Pallas path requires real floating blocks (complex dots fall back
    to the generic two-sweep path) for which a Mosaic-legal row tile
    fits the VMEM budget, and a block of ``K`` columns no larger than
    that budget (``K * n`` elements of the accumulation dtype: the
    kernel keeps X, U and their bf16 parts resident beside the tile;
    compiled for a described v5e, 256 columns of n=4096 fit its 48 MiB
    and 384 do not, 64 / 128 at n=16384, 1024 / 2048 at n=1024) —
    otherwise the generic path must be used."""
    if not (pallas_available() and A.ndim == 3
            and not jnp.iscomplexobj(A)):
        return False
    cols_bytes = K * A.shape[2] * max(A.dtype.itemsize, 4)
    return _tile_args(A)[0] is not None and cols_bytes <= _VMEM_TILE_BYTES


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


def _cols_beat_two_sweeps(K: int) -> bool:
    """Whether the chip has shown the one-sweep kernel carrying ``K``
    columns a block faster than the XLA pair. Up to 16 columns the
    tile's copy from HBM binds both and one sweep is half of two; from
    32 the MXU's passes show, and at 128 the pair is MXU-bound too.
    v5e, the flagship's 128 blocks of 4096^2 f32 (8.59 GB, 4 MiB
    tiles), ms a product (best of 3 x 10), one sweep / pair through
    ``MPIBlockDiag.normal_matvec`` / ``rmatvec(matvec)`` with the
    solvers' ``(rows, K)`` vectors (my chip run, PR 31; PERF.md
    section 6):

    ======= ======= ======= =====
    K       one     pair    ratio
    ======= ======= ======= =====
    1       11.45   23.03   2.01
    2       11.47   23.52   2.05
    3       11.47   23.55   2.05
    4       11.46   23.57   2.06
    8       11.53   23.49   2.04
    16      11.71   24.49   2.09
    32      12.60   24.19   1.92
    48      15.60   24.23   1.55
    64      19.14   25.88   1.35
    128     37.56   37.99   1.01
    ======= ======= ======= =====

    65-127 columns are not measured and stay with the pair, as the tie
    at 128 does: the one-sweep recurrence carries rounding of its own
    and has to pay for it."""
    return K <= 64


def normal_matvec_pays(A: jax.Array, K: int = 1) -> bool:
    """Whether :func:`batched_normal_matvec` on ``A``'s blocks with
    ``K`` columns a block is worth taking unasked: a compiled Mosaic
    kernel (on the CPU Pallas runs in interpret mode -- a perf trap
    inside a ``while_loop``) whose row tile
    (:func:`_tile_beats_two_sweeps`) and column count
    (:func:`_cols_beat_two_sweeps`) are ones at which the chip has
    shown one sweep to beat two."""
    if _interpret() or not normal_matvec_supported(A, K):
        return False
    tm, _ = _tile_args(A)
    return (_tile_beats_two_sweeps(tm, A.shape[2], A.dtype.itemsize)
            and _cols_beat_two_sweeps(int(K)))


def _bf16_parts(v, parts: int):
    """``v`` (f32) as ``parts`` bf16 terms, largest first, each the
    rounding of what the ones before left over (the remainders are
    exact in f32): three terms carry f32's 24 bits."""
    out = []
    for _ in range(parts):
        p = v.astype(jnp.bfloat16)
        out.append(p)
        v = v - p.astype(jnp.float32)
    return out


def _dot_highest(lhs, a_parts, dims):
    """``lhs (K, ·)`` f32 against A given as its bf16 parts: the
    products XLA's ``highest`` makes of two three-term expansions (part
    ``i`` of one with part ``j`` of the other for ``i + j <= 2``; the
    three smallest are below f32's last bit), each one MXU pass with
    f32 accumulation, added smallest first. The K columns' own parts
    sit stacked on sublanes, so a part of A passes through the MXU
    once for all of them — where Mosaic's own f32 matmul passes every
    part of the tile once a product, and no longer hides under the
    tile's copy from HBM (PERF.md section 6, PR 31). A stored in bf16
    is its own single part."""
    K = lhs.shape[0]
    l3 = jnp.concatenate(_bf16_parts(lhs, 3), axis=0)       # (3K, ·)
    terms = []
    for j, a in enumerate(a_parts):
        p = jax.lax.dot_general(l3[:(3 - j) * K], a, dims,
                                preferred_element_type=jnp.float32,
                                precision=jax.lax.Precision.DEFAULT)
        terms += [(i + j, p[i * K:(i + 1) * K]) for i in range(3 - j)]
    terms.sort(key=lambda t: -t[0])
    return reduce(jnp.add, (t for _, t in terms))


def _normal_kernel(a_ref, x_ref, u_ref, q_ref):
    """One row tile of one block: ``a_ref (1, tm, n)`` at A's storage
    dtype (a narrow tile's HBM→VMEM copy moved the narrow bytes),
    ``x_ref``/``u_ref (1, K, n)``, ``q_ref`` K rows of ``tm`` lanes —
    columns on sublanes, the long axes on lanes. ``u_ref`` stays
    resident over the row-tile axis."""
    nt, nn = (((1,), (1,)), ((), ())), (((1,), (0,)), ((), ()))
    acc = jnp.promote_types(a_ref.dtype, jnp.float32)
    x = x_ref[0].astype(acc)                                # (K, n)
    if acc == jnp.float32:
        a = a_ref[0]                                        # (tm, n)
        parts = ([a] if a.dtype == jnp.bfloat16 else
                 _bf16_parts(a.astype(jnp.float32), 3))
        t = _dot_highest(x, parts, nt)                      # (K, tm)
        u = _dot_highest(t, parts, nn)                      # (K, n)
    else:           # wider than f32: interpreted only, plain dots
        a = a_ref[0].astype(acc)
        t = jax.lax.dot_general(x, a, nt, preferred_element_type=acc)
        u = jax.lax.dot_general(t, a, nn, preferred_element_type=acc)
    q_ref[...] = t.reshape(q_ref.shape).astype(q_ref.dtype)

    @pl.when(pl.program_id(1) == 0)
    def _():
        u_ref[...] = jnp.zeros_like(u_ref)

    u_ref[0] += u.astype(u_ref.dtype)


# Scoped VMEM the kernel may use: the double-buffered 4 MiB tile, its
# bf16 parts and the products' temporaries pass Mosaic's default of
# 16 MiB on the flagship's tile (the chip's VMEM is 128 MiB on
# v5e/v6e).
_VMEM_LIMIT_BYTES = 48 << 20


def batched_normal_matvec(A: jax.Array, X: jax.Array):
    """``(U, Q) = (AᵀA X, A X)`` per block, reading each ``A`` block once
    for all of ``X``'s columns.

    A: ``(nblk, m, n)`` real (f32/f64, or bf16/f16 storage — the narrow
    case is named ``pmt_normal_stream``); X: ``(nblk, K, n)``, K >= 1
    columns a block laid ROW-wise (long axis minor), kept at ITS dtype
    (f32 for the mixed-precision solver stack). Returns
    ``U (nblk, K, n)``, ``Q (nblk, K, m)`` at X's dtype. Call per shard
    (inside shard_map); on CPU runs in interpret mode. K is the full
    second-minor dim of the x/u/q blocks, which Mosaic accepts at any
    size. Q's minor block dim is the row tile: where that is neither a
    multiple of 128 lanes nor the whole of ``m`` (tiles of 8-64 rows,
    which never pay unasked), Q leaves the kernel as
    ``(nblk, m // tm, K, tm)`` — whole trailing dims, legal at any
    width — and is put in order outside.
    """
    nblk, m, n = A.shape
    K = X.shape[1]
    tm, stream = _tile_args(A)
    if not normal_matvec_supported(A, K):
        raise ValueError(f"no Mosaic-legal row tile for blocks of {m}x{n} "
                         f"with {K} columns; gate on "
                         "normal_matvec_supported()")
    out_dtype = X.dtype
    lane_dense = tm % 128 == 0 or tm == m
    if lane_dense:
        q_spec = pl.BlockSpec((1, K, tm), lambda b, i: (b, 0, i))
        q_shape = (nblk, K, m)
    else:
        q_spec = pl.BlockSpec((1, 1, K, tm), lambda b, i: (b, i, 0, 0))
        q_shape = (nblk, m // tm, K, tm)
    u, q = pl.pallas_call(
        _normal_kernel,
        grid=(nblk, m // tm),
        in_specs=[pl.BlockSpec((1, tm, n), lambda b, i: (b, i, 0)),
                  pl.BlockSpec((1, K, n), lambda b, i: (b, 0, 0))],
        out_specs=[pl.BlockSpec((1, K, n), lambda b, i: (b, 0, 0)),
                   q_spec],
        out_shape=[jax.ShapeDtypeStruct((nblk, K, n), out_dtype),
                   jax.ShapeDtypeStruct(q_shape, out_dtype)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=_interpret(),
        name="pmt_normal_stream" if stream else "pmt_normal",
    )(A, X)
    if not lane_dense:
        q = q.transpose(0, 2, 1, 3).reshape(nblk, K, m)
    return u, q


# ------------------------------------------------------- stationary conv1d
# ``ops/local.py::Conv1D`` as one pass over the array: a block of whole
# rows (the convolved axis on lanes) comes into VMEM once, every output
# tile of L lanes is the product of the (at most three) input tiles
# around it with the filter's Toeplitz blocks on the MXU, and the block
# of outputs leaves once. ``highest`` by hand, as ``_dot_highest``: the
# six products of two three-term bf16 expansions, f32 accumulation,
# smallest first.

_CONV_BLOCK_BYTES = 2 << 20   # one input block; in and out double-buffered


def conv1d_tile(nh: int) -> int:
    """The tile ``L`` of an ``nh``-tap filter: whole 128-lane groups
    that hold ``nh - 1`` samples, so an output tile reads no further
    than the tile on either side. The blocks and their bf16 parts take
    ``30 L^2`` bytes of VMEM: compiled for a v5e the kernel holds
    ``L = 768`` (769 taps); a longer filter is Mosaic's to refuse."""
    return 128 * max(1, -(-(nh - 1) // 128))


def _conv_rows(n: int, itemsize: int) -> int:
    """Rows a block: as many as ``_CONV_BLOCK_BYTES`` hold, whole
    sublane groups, at least one."""
    return max(8, (_CONV_BLOCK_BYTES // (itemsize * n)) // 8 * 8)


def _conv1d_kernel(t_ref, x_ref, o_ref, *, L: int):
    """``x_ref``/``o_ref (R, n)``, ``t_ref (3L, L)``: the blocks
    ``[T_-1; T_0; T_+1]`` stacked on rows, so the input tiles
    ``k-1 .. k+1`` side by side meet the rows ``0 .. 3L`` (an edge tile
    meets the rows of the tiles that exist). The blocks' bf16 parts are
    made HERE, a grid step (0.2 MB beside the block's 2): made outside,
    by XLA, the round trip f32 -> bf16 -> f32 of a fused expansion is
    computed in excess precision on the chip and the second and third
    parts come out zero — a one-pass bf16 convolution, 1.3e-3 from the
    plain one where this is 1e-6 (PERF.md section 6, PR 32). Wider
    than f32 (interpreted only): plain dots."""
    nt = x_ref.shape[1] // L
    f32 = x_ref.dtype == jnp.float32
    xp = _bf16_parts(x_ref[...], 3) if f32 else [x_ref[...]]
    tp = _bf16_parts(t_ref[...], 3) if f32 else [t_ref[...]]
    for k in range(nt):                  # static: unrolled at trace time
        lo, hi = max(k - 1, 0), min(k + 2, nt)
        rows = slice((lo - k + 1) * L, (hi - k + 1) * L)
        if not f32:
            o_ref[:, k * L:(k + 1) * L] = jnp.dot(
                xp[0][:, lo * L:hi * L], tp[0][rows, :],
                preferred_element_type=x_ref.dtype,
                precision=jax.lax.Precision.HIGHEST)
            continue
        terms = []
        for j in range(3):
            t = tp[j][rows, :]
            terms += [(i + j, jnp.dot(xp[i][:, lo * L:hi * L], t,
                                      preferred_element_type=jnp.float32,
                                      precision=jax.lax.Precision.DEFAULT))
                      for i in range(3 - j)]
        terms.sort(key=lambda p: -p[0])
        o_ref[:, k * L:(k + 1) * L] = reduce(jnp.add,
                                             (p for _, p in terms))


def conv1d_toeplitz(v: jax.Array, T: jax.Array) -> jax.Array:
    """``y = v @ Toeplitz`` along the minor axis of ``v (rows, n)``,
    real, ``n`` whole tiles: the banded Toeplitz matrix given as its
    blocks ``T (3L, L)`` = ``[T_-1; T_0; T_+1]`` (``Conv1D._blocks``):
    output tile ``k`` is input tiles ``k-1, k, k+1`` through them, zero
    beyond the ends. The rows need not divide into blocks: rows carry
    no dependency, Mosaic masks the ragged last block."""
    rows, n = v.shape
    L = T.shape[1]
    if n % L or jnp.iscomplexobj(v):
        raise ValueError(f"conv1d_toeplitz: real rows of whole {L}-sample "
                         f"tiles, not {v.dtype}[{rows}, {n}]")
    R = _conv_rows(n, v.dtype.itemsize)
    return pl.pallas_call(
        partial(_conv1d_kernel, L=L),
        grid=((rows + R - 1) // R,),
        in_specs=[pl.BlockSpec((3 * L, L), lambda i: (0, 0)),
                  pl.BlockSpec((R, n), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((R, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, n), v.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=_interpret(),
        name="pmt_conv1d",
    )(T.astype(v.dtype), v)


# ------------------------------------------------------ centered Laplacian
# ``ops/derivatives.py::MPILaplacian`` (``kind="centered"``,
# ``edge=False``) as one pass over a shard's cube ``(rows, n1, n2)``
# where it lies: axis 0 major, axis 1 on sublanes, axis 2 on lanes.
# The grid walks the planes once, in order. A plane arrives from HBM
# ONCE and is kept in a two-plane ring in VMEM for the two steps that
# need it again (as the centre, then as the plane above), so step ``i``
# holds planes ``i-2`` (ring), ``i-1`` (ring) and ``i`` (the block just
# fetched) and writes output plane ``i-1``: one volume read, one
# written. The two ghost planes either side of the shard are operands
# (zeros at the ends of the global array). In-plane neighbours are
# shifts inside VMEM: along sublanes the ring keeps ``_LAP_HALO`` zero
# rows above and below each plane, so every strip's window is an
# aligned load that already holds "zero beyond the ends"; along lanes
# a roll, whose wrap-around only ever lands on an index the mask
# zeroes.
#
# Measured alone on a TPU v5e at 192 x 1,024 x 1,024 float32 (1.61 GB
# moved; 1.97 ms at the HBM peak), forward / adjoint, ms an apply
# (``benchmarks/laplacian_probe.py``; PERF.md section 6, PR 33): this
# ring form 2.59 / 2.61 with strips of 32 rows (8-row strips 3.01 /
# 3.29: the loop's 128 short bodies a plane bind; 16 to 128 rows 2.58 -
# 2.61: the plane's DMA binds); the same arithmetic with the cube
# passed three times under index maps i-1, i, i+1 (three reads) 4.71 /
# 4.71 at every strip height; the pad-and-slice form XLA makes of
# ``ops/local.py::Laplacian`` 18.63 / 18.69.

_LAP_HALO = 8                 # zero rows above and below a ring plane
_LAP_STRIPS = (32, 16, 8)     # rows a strip: the tallest that divides n1
_LAP_PLANE_BYTES = 4 << 20    # largest plane the compiled kernel takes


def laplacian_legal(shape, dtype) -> bool:
    """Whether :func:`laplacian_stencil` takes a shard ``(rows, n1,
    n2)`` of ``dtype``: always where it is interpreted; compiled,
    Mosaic wants f32 planes of whole ``(8, 128)`` tiles (``n1 % 8 ==
    0``, ``n2 % 128 == 0``) and the kernel holds ten planes in VMEM (the
    ring's two, the block in and out and the two ghosts double-
    buffered), so a plane of at most 4 MiB."""
    if _interpret():
        return True
    _, n1, n2 = shape
    return (np.dtype(dtype) == np.float32 and n1 % 8 == 0 and n2 % 128 == 0
            and 4 * n1 * n2 <= _LAP_PLANE_BYTES)


def _laplacian_plane(o_ref, window, above, below, g, *, n0: int, n1: int,
                     coef, adjoint: bool, R: int, keep=None):
    """One output plane, global index ``g``, strip by strip of ``R``
    rows. ``window(r)``: rows ``[r - H, r + R + H)`` of the centre
    plane, zeros beyond its ends (``H = _LAP_HALO``); ``above(r)`` /
    ``below(r)``: rows ``[r, r + R)`` of the planes either side;
    ``keep(r, rows)``, if given, is handed each strip of the plane
    below as it is read. Forward ``sum_a c_a Z_a S_a x`` masks each
    axis' OUTPUT on that axis' two boundary planes, the adjoint
    ``sum_a c_a S_a Z_a x`` its INPUT: one body, ``adjoint`` says which
    side. The axis-0 mask is by GLOBAL index, a scalar a plane."""
    c0, c1, c2 = coef
    H, n2, dt = _LAP_HALO, o_ref.shape[2], o_ref.dtype

    def interior(k):
        return ((k > 0) & (k < n0 - 1)).astype(dt)
    if adjoint:
        au, ac, ad = (c0 * interior(g - 1), c0 * interior(g),
                      c0 * interior(g + 1))
    else:
        au = ac = ad = c0 * interior(g)
    lane = jax.lax.broadcasted_iota(jnp.int32, (R, n2), 1)
    lanes = (lane > 0) & (lane < n2 - 1)

    def rows_inside(r, nrows):
        j = r + jax.lax.broadcasted_iota(jnp.int32, (nrows, n2), 0)
        return (j > 0) & (j < n1 - 1)

    def strip(s, carry):
        r = pl.multiple_of(s * R, R)
        w = window(r)
        cc = w[H:H + R]
        y = jnp.zeros((R, n2), dt)
        if c1:
            v = jnp.where(rows_inside(r - H, R + 2 * H), w, 0) if adjoint \
                else w
            t = v[H - 1:H - 1 + R] + v[H + 1:H + 1 + R] - 2 * v[H:H + R]
            if not adjoint:
                t = jnp.where(rows_inside(r, R), t, 0)
            y = y + c1 * t
        if c2:
            v = jnp.where(lanes, cc, 0) if adjoint else cc
            t = pltpu.roll(v, 1, 1) + pltpu.roll(v, n2 - 1, 1) - 2 * v
            if not adjoint:
                t = jnp.where(lanes, t, 0)
            y = y + c2 * t
        dn = below(r)
        if c0:
            y = y + (au * above(r) + ad * dn - 2 * ac * cc)
        if keep is not None:
            keep(r, dn)
        o_ref[0, pl.ds(r, R), :] = y
        return carry
    jax.lax.fori_loop(0, n1 // R, strip, None)


def _laplacian_kernel(base_ref, x_ref, gf_ref, gb_ref, o_ref, ring, *,
                      n0: int, coef, adjoint: bool, R: int):
    """Step ``i`` of ``rows + 1``: ``x_ref`` is plane ``min(i, rows -
    1)``, ``o_ref`` plane ``max(i - 1, 0)`` (step 0 only fills the
    ring; its block is written back after step 1, when the index
    moves). Plane ``p`` lives in ``ring[p % 2]``, the front ghost as
    plane ``-1``. ``base_ref[0]`` is the global index of the shard's
    first plane, ``n0`` the global plane count."""
    i = pl.program_id(0)
    rows = pl.num_programs(0) - 1
    n1, n2 = x_ref.shape[1], x_ref.shape[2]
    H = _LAP_HALO

    @pl.when(i == 0)
    def _():
        zeros = jnp.zeros((H, n2), x_ref.dtype)
        for slot in (0, 1):
            ring[slot, 0:H, :] = zeros
            ring[slot, H + n1:H + n1 + H, :] = zeros

        def fill(s, carry):
            r = pl.multiple_of(s * R, R)
            ring[1, pl.ds(r + H, R), :] = gf_ref[0, pl.ds(r, R), :]
            ring[0, pl.ds(r + H, R), :] = x_ref[0, pl.ds(r, R), :]
            return carry
        jax.lax.fori_loop(0, n1 // R, fill, None)

    def emit(dn_ref):
        centre, above = (i - 1) % 2, i % 2

        def keep(r, dn):                     # plane i, for step i + 1
            ring[above, pl.ds(r + H, R), :] = dn
        _laplacian_plane(
            o_ref, lambda r: ring[centre, pl.ds(r, R + 2 * H), :],
            lambda r: ring[above, pl.ds(r + H, R), :],
            lambda r: dn_ref[0, pl.ds(r, R), :], base_ref[0] + i - 1,
            n0=n0, n1=n1, coef=coef, adjoint=adjoint, R=R, keep=keep)

    @pl.when((i > 0) & (i < rows))
    def _():
        emit(x_ref)

    @pl.when(i == rows)
    def _():
        emit(gb_ref)


def laplacian_stencil(x: jax.Array, ghost_front: jax.Array,
                      ghost_back: jax.Array, base, n0: int, coef,
                      adjoint: bool) -> jax.Array:
    """Centered, ``edge=False`` Laplacian of one shard ``x (rows, n1,
    n2)`` of a cube of ``n0`` planes split along axis 0, forward or
    adjoint, as ONE pass: kernel ``pmt_laplacian`` (compiled on a TPU,
    interpreted elsewhere). ``ghost_front`` / ``ghost_back (1, n1,
    n2)``: the neighbours' planes either side, zeros at the ends of the
    global array; ``base``: the global index of ``x``'s first plane (an
    int32 scalar, traced or not); ``coef``: three static floats
    ``weights[a] / sampling[a]**2``, 0.0 for an axis left out. Gate on
    :func:`laplacian_legal`. Live in an apply: the input and the
    output, **2 volumes**, and the two ghost planes."""
    rows, n1, n2 = x.shape
    coef = tuple(float(c) for c in coef)
    R = next((r for r in _LAP_STRIPS if n1 % r == 0), n1)
    plane = (1, n1, n2)

    def ghost():
        return pl.BlockSpec(plane, lambda i, b: (0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(rows + 1,),
        in_specs=[pl.BlockSpec(
            plane, lambda i, b: (jnp.minimum(i, rows - 1), 0, 0)),
            ghost(), ghost()],
        out_specs=pl.BlockSpec(
            plane, lambda i, b: (jnp.maximum(i - 1, 0), 0, 0)),
        scratch_shapes=[pltpu.VMEM((2, n1 + 2 * _LAP_HALO, n2), x.dtype)])
    return pl.pallas_call(
        partial(_laplacian_kernel, n0=int(n0), coef=coef,
                adjoint=bool(adjoint), R=R),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT_BYTES,
            dimension_semantics=("arbitrary",)),
        interpret=_interpret(),
        name="pmt_laplacian",
    )(jnp.asarray(base, jnp.int32).reshape(1), x, ghost_front, ghost_back)


# -------------------------------------------------- indexed spray / gather
# ``pmt_kirchhoff`` / ``pmt_kirchhoff_adj`` (:func:`kirchhoff_spray`,
# :func:`kirchhoff_gather`), added below everything else so that no
# line of the kernels above moves (their compiled text carries their
# line numbers). Live in an apply: the tables where they lie, a
# trace-sized and an image-sized vector.
#
# ``models/lsm.py::TravelTimeSpray``: for a trace (a source-receiver
# pair) ``p`` and a pixel ``x`` a stored sample index ``i[p, x]`` and a
# stored weight ``w[p, x]``; one tap ``y[p, i] += w m[x]`` or two,
# ``y[p, i] += (1 - w) m[x]; y[p, i + 1] += w m[x]`` (linear
# interpolation between samples). Neither a GEMM nor a stencil, and the
# chip has no scatter: the kernels turn the index into COMPARES on the
# VPU. The pixels are cut into tiles of ``KIRCHHOFF_TILE`` = 1,024, one
# ``(8, 128)`` register of indices; a tile's indices span a band of
# samples ``[lo, hi]`` (stored a pair-tile beside the tables), and for
# each sample ``t`` of the band ONE compare of the register against
# ``t`` selects the pixels that land there:
#
# - spray: ``acc[t] += where(i == t, (1 - w) m, 0) + where(i == t - 1,
#   w m, 0)`` into a VMEM accumulator of one register a sample, which
#   is summed over its 1,024 places (sublanes on the VPU, lanes as a
#   product with ones on the MXU, three bf16 parts) once a trace;
# - gather: ``g0 = where(i == t, z[p, t], g0); g1 = where(i == t,
#   z[p, t + 1], g1)`` with the trace's samples read as SCALARS from
#   SMEM, then ``m[x] += (1 - w) g0 + w g1``.
#
# So an apply costs ``3-4`` vector operations a pair-TILE a sample of
# its band, whatever the trace's length: pixels that are neighbours in
# the image have travel times that are neighbours in time, and the
# order of the pixels in the tables (``models/lsm.py`` cuts the image
# into 32 x 32 blocks) keeps the bands short. Tables in any order give
# the same answer with longer bands. An entry that is dropped (outside
# the trace) holds an index no sample equals.
#
# The spray takes G consecutive traces a grid step
# (:func:`kirchhoff_group`): pairs are shot-major and neighbouring
# receivers' travel times differ by a sample or two, so the G traces'
# bands of one tile nearly coincide. A tile walks the UNION of their
# bands once, and a loop step loads the G accumulators' rows of its
# samples, updates them and stores them: G x unroll independent chains
# where one trace's tiles, which share one accumulator, give one.

__all__ += ["kirchhoff_spray", "kirchhoff_gather", "kirchhoff_pack",
            "kirchhoff_legal", "kirchhoff_group", "KIRCHHOFF_TILE"]

KIRCHHOFF_TILE = 1024          # pixels a tile: one (8, 128) register
_KIR_UNROLL = 4                # samples a loop step
_KIR_DROPPED = -(1 << 30)      # the index of a dropped entry
_KIR_BLOCK_TILES = 64          # tiles a grid step (2 x 256 KiB of tables)
_KIR_ACC_BYTES = 24 << 20      # the spray's accumulator: nt registers
_KIR_GROUPS = (8, 4, 2, 1)     # traces a grid step of the spray, in order
# the spray's accumulators, table blocks and the reduction's
# temporaries: the limit less the image's and the output's blocks
_KIR_SPRAY_VMEM = _VMEM_LIMIT_BYTES - (2 << 20)


def kirchhoff_legal(nt: int, dtype) -> bool:
    """Whether the kernels take traces of ``nt`` samples of ``dtype``:
    real, and the spray's accumulator (one ``(8, 128)`` register a
    sample) within its share of VMEM — 6,144 samples of float32."""
    dtype = np.dtype(dtype)
    return (dtype.kind == "f"
            and (nt + 128 + _KIR_UNROLL) * KIRCHHOFF_TILE * dtype.itemsize
            <= _KIR_ACC_BYTES)


def kirchhoff_group(pairs: int, nt: int, dtype) -> int:
    """Traces a grid step of ``pmt_kirchhoff`` for ``pairs`` traces of
    ``nt`` samples of ``dtype``: the largest of ``_KIR_GROUPS`` that
    divides ``pairs`` and whose accumulators (one ``(8, 128)`` register
    a sample a trace) and double-buffered table blocks fit the spray's
    share of VMEM beside the three accumulator-sized temporaries that
    Mosaic gives the final reduction (compiled for a v5e: 18, 23, 33
    and 53 MiB at 1, 2, 4 and 8 traces of 1,024 float32 samples); 1
    for a trace that fits alone (:func:`kirchhoff_legal`). 4 for the
    ``lsm_kirchhoff`` cell's 2,048 traces of 1,024 float32 samples."""
    itemsize = np.dtype(dtype).itemsize
    acc = (-(-nt // 128) * 128 + _KIR_UNROLL) * KIRCHHOFF_TILE * itemsize
    tables = 2 * _KIR_BLOCK_TILES * KIRCHHOFF_TILE * (4 + itemsize)
    return next(g for g in _KIR_GROUPS if pairs % g == 0 and (
        g == 1 or (g + 3) * acc + g * tables <= _KIR_SPRAY_VMEM))


def kirchhoff_pack(i, w, valid):
    """Tables ``(pairs, npix)`` (``npix`` whole tiles) in the kernels'
    layout: ``it (pairs, ntiles, 8, 128)`` int32 with dropped entries
    marked, ``wt`` likewise with zeros there, and the bands ``lohi
    (pairs, nblk, 2, TB)`` int32 (an empty tile: ``lo > hi``), the
    tiles padded to whole grid steps of ``TB``. Traceable: the caller's
    program makes the tables where they are to lie."""
    pairs, npix = i.shape
    ntiles = npix // KIRCHHOFF_TILE
    tb = min(_KIR_BLOCK_TILES, ntiles)
    pad = -ntiles % tb
    it = jnp.where(valid, i, _KIR_DROPPED).astype(jnp.int32)
    wt = jnp.where(valid, w, 0)
    tiles = it.reshape(pairs, ntiles, KIRCHHOFF_TILE)
    lo = jnp.min(jnp.where(tiles == _KIR_DROPPED, 1 << 30, tiles), axis=-1)
    hi = jnp.max(jnp.where(tiles == _KIR_DROPPED, -1, tiles), axis=-1)
    if pad:
        lo = jnp.pad(lo, ((0, 0), (0, pad)), constant_values=1 << 30)
        hi = jnp.pad(hi, ((0, 0), (0, pad)), constant_values=-1)
    lohi = jnp.stack([lo.reshape(pairs, -1, tb), hi.reshape(pairs, -1, tb)],
                     axis=2)
    shape = (pairs, ntiles, 8, 128)
    it, wt = it.reshape(shape), wt.reshape(shape)
    if pad:
        widths = ((0, 0), (0, pad), (0, 0), (0, 0))
        it = jnp.pad(it, widths, constant_values=_KIR_DROPPED)
        wt = jnp.pad(wt, widths)
    return it, wt, lohi


def _kir_steps(lo, hi):
    """Loop steps of ``_KIR_UNROLL`` samples that cover ``[lo, hi]``
    (none for an empty band); the samples past ``hi`` a last step
    visits match no index of the tile."""
    return jnp.maximum(hi - lo + _KIR_UNROLL, 0) // _KIR_UNROLL


def _kirchhoff_spray_kernel(lh_ref, i_ref, w_ref, m_ref, y_ref, *accs,
                            taps: int, ntp: int):
    """Grid ``(pairs // G, nblk)``: ``G`` consecutive traces, one block
    of ``TB`` tiles. ``lh_ref (G, 2, TB)`` in SMEM, ``i_ref`` / ``w_ref
    (G, TB, 8, 128)``, ``m_ref (TB, 8, 128)``, ``y_ref (G, 1, ntp)``
    written at the group's last block from ``accs``: ``G`` buffers
    ``(ntp + unroll, 8, 128)``, one a trace. A tile walks the union of
    its ``G`` bands; a sample outside a trace's own band adds ``+0.0``
    to its accumulator, which is never ``-0.0`` (it starts at ``+0.0``),
    so each trace's sums are the one-trace kernel's, bit for bit."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        for acc in accs:
            acc[...] = jnp.zeros_like(acc)

    def tile(k, carry):
        m, group = m_ref[k], range(len(accs))
        rows = []
        for g in group:
            i, w = i_ref[g, k], w_ref[g, k]
            a = w * m              # two taps: (1 - w) m at i, w m at i + 1
            b = (1 - w) * m        # (the gather's weights, bit for bit)
            rows.append((i, a, b))
        lo = reduce(jnp.minimum, [lh_ref[g, 0, k] for g in group])
        hi = reduce(jnp.maximum, [lh_ref[g, 1, k] for g in group])

        def step(s, c):
            ts = [lo + s * _KIR_UNROLL + q for q in range(_KIR_UNROLL)]
            new = [[acc[t] for t in ts] for acc in accs]   # loads first
            for (i, a, b), row in zip(rows, new):
                for q, t in enumerate(ts):
                    if taps == 2:
                        v = jnp.where(i == t, b, 0) + jnp.where(i == t - 1, a,
                                                                0)
                    else:
                        v = jnp.where(i == t, a, 0)
                    row[q] = row[q] + v
            for acc, row in zip(accs, new):                # then stores
                for t, r in zip(ts, row):
                    acc[t] = r
            return c
        return jax.lax.fori_loop(0, _kir_steps(lo, hi + (taps - 1)), step,
                                 carry)
    jax.lax.fori_loop(0, i_ref.shape[1], tile, 0)

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        lanes = (((1,), (1,)), ((), ()))
        for g, acc in enumerate(accs):
            ys = jnp.sum(acc[0:ntp], axis=1)                  # (ntp, 128)
            if ys.dtype == jnp.float32:
                ones = jnp.ones((8, 128), jnp.bfloat16)
                y = reduce(jnp.add, (jax.lax.dot_general(
                    ones, p, lanes, preferred_element_type=jnp.float32,
                    precision=jax.lax.Precision.DEFAULT)
                    for p in reversed(_bf16_parts(ys, 3))))
            else:       # wider than f32: interpreted only, a plain dot
                y = jax.lax.dot_general(jnp.ones((8, 128), ys.dtype), ys,
                                        lanes)
            y_ref[g] = y[0:1]


# The gather by LANE GATHER (PR 39). The kernel also takes the trace as
# overlapping WINDOWS of ``_KIR_WINDOW`` = 128 samples at a stride of
# ``_KIR_STRIDE`` = 64 (window ``b``: samples ``[64 b, 64 b + 128)``,
# zeros past the trace's end), one row of lanes each, in VMEM. A tile
# whose band, with the ``taps - 1`` samples past it, lies in the window
# that starts at or below its ``lo`` (:func:`kirchhoff_windowed`: every
# band of up to 65 - taps samples does) broadcasts that row to a
# register and takes each tap with ONE lane gather (``jnp.take_along_
# axis`` along lanes, Mosaic's ``tpu.dynamic_gather``): ``g0 = row[i -
# 64 b]``, ``g1 = row[i - 64 b + 1]``, whatever the band's length; an
# empty tile, whose entries are all dropped, likewise. Any other tile
# walks its band as the block above says. The loop takes
# ``_KIR_GROUP`` tiles a step, without a branch between them where
# every tile of the grid step is gathered (a flag a step, made from the
# bands beside the kernel), so that their gathers overlap. The gathered
# values are the ones the compares pick, the sum over the pairs keeps
# its order, and both ways end in one expression after a ``lax.cond``:
# the same result, bit for bit.

_KIR_WINDOW = 128              # samples a window of the trace: a lane row
_KIR_SHIFT = 6                 # a window starts every 2 ** 6 samples
_KIR_STRIDE = 1 << _KIR_SHIFT
_KIR_GROUP = 16                # tiles a step of the gather's loop

__all__ += ["kirchhoff_windowed"]


def kirchhoff_windowed(lo, hi, taps: int):
    """Whether the band ``[lo, hi]`` of a tile (scalars, or arrays of
    bands) is not empty and lies, with the ``taps - 1`` samples past
    it, in the window of the trace that starts at ``64 * (lo // 64)``:
    the tiles ``pmt_kirchhoff_adj`` reads by lane gather."""
    start = (lo >> _KIR_SHIFT) << _KIR_SHIFT
    return (lo <= hi) & (hi + (taps - 1) - start < _KIR_WINDOW)


def _kir_gathered(lo, hi, taps: int):
    """The tiles the gather reads by lane gather: windowed or empty."""
    return kirchhoff_windowed(lo, hi, taps) | (lo > hi)


def _kirchhoff_gather_kernel(fit_ref, lh_ref, z_ref, zw_ref, i_ref, w_ref,
                             m_ref, *, taps: int, group: int):
    """Grid ``(nblk, pairs)``: one block of ``TB`` tiles, one trace;
    ``m_ref (TB, 8, 128)`` stays where it is over the traces and is
    added to. ``fit_ref (1, nblk)`` in SMEM: whether each block's every
    tile is gathered; ``z_ref (1, ntz)``: the trace's samples in SMEM,
    zeros past its end, for the band loop; ``zw_ref (nwin, 1, 128)``:
    its windows, for the lane gather."""
    @pl.when(pl.program_id(1) == 0)
    def _():
        m_ref[...] = jnp.zeros_like(m_ref)

    zero = jnp.zeros(i_ref.shape[2:], m_ref.dtype)

    def lanes(k):
        i = i_ref[0, k]
        b = jnp.minimum(lh_ref[0, k] >> _KIR_SHIFT, zw_ref.shape[0] - 1)
        row = jnp.broadcast_to(zw_ref[b], i.shape)
        d = i - b * _KIR_STRIDE
        live = d >= 0          # a dropped entry; any other is in the window
        d = jnp.where(live, d, 0)
        g = [jnp.where(live, jnp.take_along_axis(
            row, d + s, axis=1, mode="promise_in_bounds"), 0)
            for s in range(taps)]
        return g[0], g[1] if taps == 2 else zero

    def band(k):
        i, lo = i_ref[0, k], lh_ref[0, k]

        def step(s, g):
            g0, g1 = g
            for q in range(_KIR_UNROLL):
                t = lo + s * _KIR_UNROLL + q
                here = i == t
                g0 = jnp.where(here, z_ref[0, t], g0)
                if taps == 2:
                    g1 = jnp.where(here, z_ref[0, t + 1], g1)
            return g0, g1
        return jax.lax.fori_loop(0, _kir_steps(lo, lh_ref[1, k]), step,
                                 (zero, zero))

    def either(k):
        return jax.lax.cond(_kir_gathered(lh_ref[0, k], lh_ref[1, k], taps),
                            lanes, band, k)

    fit = fit_ref[0, pl.program_id(0)] != 0

    def tiles(s, carry):
        ks = [s * group + u for u in range(group)]
        gs = jax.lax.cond(fit, lambda: [lanes(k) for k in ks],
                          lambda: [either(k) for k in ks])
        for k, (g0, g1) in zip(ks, gs):
            w = w_ref[0, k]
            m_ref[k] = m_ref[k] + ((1 - w) * g0 + w * g1 if taps == 2
                                 else w * g0)
        return carry
    jax.lax.fori_loop(0, i_ref.shape[1] // group, tiles, 0)


def _kir_specs(lohi, swap: bool):
    """Block specs of the bands and the two tables for a grid
    ``(pairs, nblk)`` (``swap``: ``(nblk, pairs)``)."""
    tb = lohi.shape[3]
    at = (lambda j, p: (p, j, 0, 0)) if swap else (lambda p, j: (p, j, 0, 0))
    table = pl.BlockSpec((1, tb, 8, 128), at)
    return pl.BlockSpec((None, None, 2, tb), at,
                        memory_space=pltpu.SMEM), table, table


def _spray_call(lohi, it, wt, m, nt: int, taps: int, group: int):
    """``pmt_kirchhoff`` at ``group`` traces a grid step ``(pairs //
    group, nblk)``: the bands' block ``(group, 2, TB)``, the tables'
    ``(group, TB, 8, 128)``."""
    pairs, nblk, _, tb = lohi.shape
    ntp = -(-nt // 128) * 128

    def at(p, j):
        return p, j, 0, 0
    table = pl.BlockSpec((group, tb, 8, 128), at)
    y = pl.pallas_call(
        partial(_kirchhoff_spray_kernel, taps=taps, ntp=ntp),
        grid=(pairs // group, nblk),
        in_specs=[pl.BlockSpec((group, None, 2, tb), at,
                               memory_space=pltpu.SMEM), table, table,
                  pl.BlockSpec((tb, 8, 128), lambda p, j: (j, 0, 0))],
        out_specs=pl.BlockSpec((group, 1, ntp), lambda p, j: (p, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((pairs, 1, ntp), m.dtype),
        scratch_shapes=[pltpu.VMEM((ntp + _KIR_UNROLL, 8, 128), m.dtype)
                        for _ in range(group)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=_interpret(),
        name="pmt_kirchhoff",
    )(lohi, it, wt, m.reshape(nblk * tb, 8, 128))
    return y[:, 0, :nt]


@partial(jax.jit, static_argnames=("nt", "taps"))
def kirchhoff_spray(lohi, it, wt, m, nt: int, taps: int) -> jax.Array:
    """``y (pairs, nt)``: the pixels ``m (ntiles * 1024,)`` sprayed
    through the packed tables (:func:`kirchhoff_pack`), ``taps`` 1 or
    2. Kernel ``pmt_kirchhoff`` (compiled on a TPU, interpreted
    elsewhere), :func:`kirchhoff_group` traces a grid step. Gate on
    :func:`kirchhoff_legal`. Under ``jax.jit`` of its own, as
    :func:`kirchhoff_gather`: an eager apply does not trace the
    interpreted kernel anew, and the blocks of a stack that have one
    shape share one lowering."""
    return _spray_call(lohi, it, wt, m, nt, taps,
                       kirchhoff_group(lohi.shape[0], nt, m.dtype))


@partial(jax.jit, static_argnames=("taps",))
def kirchhoff_gather(lohi, it, wt, z, taps: int) -> jax.Array:
    """``m (ntiles * 1024,)``: the adjoint of :func:`kirchhoff_spray`
    on traces ``z (pairs, nt)``, summed over the traces. Kernel
    ``pmt_kirchhoff_adj``: a tile whose band fits a window of the trace
    (:func:`kirchhoff_windowed`) by lane gather, any other by a walk
    over its band."""
    pairs, nblk, _, tb = lohi.shape
    nt = z.shape[1]
    ntz = -(-(nt + _KIR_UNROLL + 1) // 128) * 128
    nwin = -(-nt // _KIR_STRIDE)     # the last starts at or below nt - 1
    zs = jnp.pad(z, ((0, 0), (0, (nwin + 1) * _KIR_STRIDE - nt))).reshape(
        pairs, nwin + 1, _KIR_STRIDE)
    windows = jnp.concatenate([zs[:, :-1], zs[:, 1:]], axis=2)[:, :, None]
    fit = jnp.all(_kir_gathered(lohi[:, :, 0], lohi[:, :, 1], taps),
                  axis=-1).astype(jnp.int32)[:, None, :]
    bands, ti, tw = _kir_specs(lohi, swap=True)
    m = pl.pallas_call(
        partial(_kirchhoff_gather_kernel, taps=taps,
                group=int(np.gcd(_KIR_GROUP, tb))),
        grid=(nblk, pairs),
        in_specs=[pl.BlockSpec((None, 1, nblk), lambda j, p: (p, 0, 0),
                               memory_space=pltpu.SMEM),
                  bands,
                  pl.BlockSpec((None, 1, ntz), lambda j, p: (p, 0, 0),
                               memory_space=pltpu.SMEM),
                  pl.BlockSpec((None, nwin, 1, _KIR_WINDOW),
                               lambda j, p: (p, 0, 0, 0)),
                  ti, tw],
        out_specs=pl.BlockSpec((tb, 8, 128), lambda j, p: (j, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nblk * tb, 8, 128), z.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=_interpret(),
        name="pmt_kirchhoff_adj",
    )(fit, lohi, jnp.pad(z, ((0, 0), (0, ntz - nt)))[:, None, :], windows,
      it, wt)
    return m.ravel()


# ------------------------------------------------- plane-pair normal product
# ``pmt_normal_planes`` (:func:`plane_pair_normal`), added below
# everything else so that no line of the kernels above moves. For a
# complex kernel stored as its real (re, im) planes, ``G = Gr + i Gi``,
# a batch of ``nsl`` frequency blocks ``(m, n)``, and two spectra with
# ``nz`` columns a block — ``C (n, nz)`` on the model side and ``S (m,
# nz)`` on the data side — the kernel makes, from ONE read of each
# plane,
#
#     Q = G C,    Z = Gᴴ [M Q | S]     (``Gᴴ = Grᵀ − i Giᵀ``),
#
# ``M`` zeroing the imaginary part of global block 0 and nothing else:
# everything an iteration of CGLS on ``MPIMDC``'s chain ``F1ᴴ (a G) F``
# needs from the kernel (``ops/mdc.py``: on the kept bins ``F1 F1ᴴ`` is
# ``M``). Within a block's row tile ``i`` the plane pair ``(Gr, Gi)[i]``
# comes into VMEM once; the forward makes the tile's rows of ``Q`` as
# the four real products on ``[Cr; Ci]`` against each plane (the
# columns on sublanes, as ``pmt_normal``'s), and the adjoint adds the
# tile's share of ``Z`` — ``[M Q_i | S_i]`` against each plane — to an
# accumulator that stays resident over the row tiles. Every product is
# ``highest`` by hand (``_dot_highest``): a plane's three bf16 parts
# are made once a tile and serve both products. A part of the
# spectrum's columns is padded to whole sublane groups of 8, so every
# slice in the kernel is a whole group.

__all__ += ["plane_pair_normal", "plane_pair_tile", "plane_pair_cols_pay",
            "PLANE_PAIR_ROWS"]

PLANE_PAIR_ROWS = 8            # a part's columns are padded to whole groups


def plane_pair_cols_pay(K: int) -> bool:
    """Whether the chip has shown ``pmt_normal_planes`` with ``K``
    forward columns a block (``2 nz``: the spectrum's two parts; the
    adjoint carries ``2 K``) faster than the two plane ``einsum``s it
    replaces. v5e, ``mdd_obc``'s 64 blocks of 4,096^2 complex64 as
    f32 planes (8.59 GB), ms a product (median of 10), on BROADCAST
    spectra: ``one`` is ``MPIFredholm1.normal_planes``, the spectra's
    relayouts to and from the kernel's rows included, 256-row tiles;
    ``pair`` the operator's ``matvec`` and ``rmatvec``, ``nz`` columns
    each, as a classic CGLS iteration runs them
    (``chip_probe/fredholm_normal_probe.py`` on a TPU v5e; PERF.md
    section 6):

    ======= ======= ======= ======= =====
    nz      K       one     pair    ratio
    ======= ======= ======= ======= =====
    1       2       12.59   24.09   1.91
    16      32      20.78   28.76   1.38
    32      64      32.58   31.83   0.98
    ======= ======= ======= ======= =====

    The kernel alone at 16 columns a part: 16.93 ms at 128-row tiles,
    16.32 at 256 (:func:`plane_pair_tile`). The MXU's six passes over
    ``K`` forward and ``2 K`` adjoint columns show from 32 columns on,
    as in the real kernel's table (``_cols_beat_two_sweeps``), and at
    64 the one sweep no longer pays; more than 32 forward columns stay
    with the pair."""
    return K <= 32


def plane_pair_tile(P: jax.Array):
    """The row tile of ``pmt_normal_planes`` for the planes ``P (2, nsl,
    m, n)``: ``pmt_normal``'s, each plane's tile within the 4 MiB
    budget (256 rows at ``n`` = 4,096 f32: compiled for a v5e, 256 rows
    and 32 columns a part fit the 48 MiB limit, 512 rows do not; alone
    on the chip 16.32 ms a sweep of ``mdd_obc``'s planes at 16 columns
    a part against 16.90 at 128 rows, measured on a v5e), which,
    compiled, has to be whole 128-lane groups or all of ``m`` (the tile
    is the lane extent of ``Q``'s and ``S``'s blocks); ``None`` where no
    tile is legal."""
    _, _, m, n = P.shape
    tm = _pick_tile(m, n, max(P.dtype.itemsize, 4),
                    min_sublane=_min_sublane(P.dtype))
    if tm is None or (not _interpret() and tm % 128 and tm != m):
        return None
    return tm


def _plane_pair_kernel(first_ref, g_ref, c_ref, s_ref, q_ref, z_ref, *,
                       nz: int):
    """Block ``b``, row tile ``i``: ``g_ref (2, 1, tm, n)`` the planes'
    rows, ``c_ref (1, 2 nz, n)`` = ``[Cr; Ci]``, ``s_ref (1, 2 nz, tm)``
    = ``[Sr; Si]`` of the tile's rows, ``q_ref (1, 2 nz, tm)`` =
    ``[Qr; Qi]``, ``z_ref (1, 4 nz, n)`` = ``[Re Gᴴ MQ; Re Gᴴ S; Im Gᴴ
    MQ; Im Gᴴ S]``, resident over ``i``. ``first_ref[0]``: the global
    index of the shard's first block. Wider than f32 (interpreted only):
    plain dots."""
    nt, nn = (((1,), (1,)), ((), ())), (((1,), (0,)), ((), ()))
    acc = jnp.promote_types(g_ref.dtype, jnp.float32)
    planes = (g_ref[0, 0], g_ref[1, 0])                      # (tm, n)
    if acc == jnp.float32:
        parts = [[g] if g.dtype == jnp.bfloat16 else
                 _bf16_parts(g.astype(jnp.float32), 3) for g in planes]

        def dot(lhs, plane, dims):
            return _dot_highest(lhs, parts[plane], dims)
    else:
        wide = [g.astype(acc) for g in planes]

        def dot(lhs, plane, dims):
            return jax.lax.dot_general(lhs, wide[plane], dims,
                                       preferred_element_type=acc)
    c = c_ref[0].astype(acc)                                 # (2 nz, n)
    tr, ti = dot(c, 0, nt), dot(c, 1, nt)                    # (2 nz, tm)
    qr = tr[:nz] - ti[nz:]                      # Gr Cr − Gi Ci
    qi = tr[nz:] + ti[:nz]                      # Gr Ci + Gi Cr
    q_ref[0] = jnp.concatenate([qr, qi]).astype(q_ref.dtype)
    qi = jnp.where(first_ref[0] + pl.program_id(0) == 0, 0.0, qi)
    s = s_ref[0].astype(acc)
    v = jnp.concatenate([qr, s[:nz], qi, s[nz:]])            # (4 nz, tm)
    a, b = dot(v, 0, nn), dot(v, 1, nn)                      # (4 nz, n)
    h = 2 * nz                  # Re: Vrᵀ Gr + Viᵀ Gi; Im: Viᵀ Gr − Vrᵀ Gi
    z = jnp.concatenate([a[:h] + b[h:], a[h:] - b[:h]])

    @pl.when(pl.program_id(1) == 0)
    def _():
        z_ref[...] = jnp.zeros_like(z_ref)

    z_ref[0] += z.astype(z_ref.dtype)


def plane_pair_normal(P: jax.Array, C: jax.Array, S: jax.Array, first=0,
                      tm=None):
    """``(Q, Z)`` of the block above for the planes ``P (2, nsl, m,
    n)`` and the spectra's parts as rows: ``C (nsl, 2 nz, n)`` =
    ``[Cr; Ci]``, ``S (nsl, 2 nz, m)`` = ``[Sr; Si]``, ``nz`` a whole
    number of ``PLANE_PAIR_ROWS``. Returns ``Q (nsl, 2 nz, m)`` =
    ``[Qr; Qi]`` and ``Z (nsl, 4 nz, n)`` = ``[Re Z1; Re Z2; Im Z1; Im
    Z2]`` (``Z1 = Gᴴ M Q``, ``Z2 = Gᴴ S``) at ``C``'s dtype. ``first``:
    the global index of ``P``'s first block (an int32 scalar, traced or
    not: a shard's). Kernel ``pmt_normal_planes``, compiled on a TPU,
    interpreted elsewhere; ``tm`` (the row tile) defaults to
    :func:`plane_pair_tile`'s. Call per shard."""
    _, nsl, m, n = P.shape
    nz = C.shape[1] // 2
    tm = plane_pair_tile(P) if tm is None else int(tm)
    if tm is None or nz % PLANE_PAIR_ROWS or m % tm:
        raise ValueError(f"pmt_normal_planes: no legal row tile for blocks "
                         f"of {m}x{n} with {nz} columns a part; gate on "
                         "plane_pair_tile()")
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1, grid=(nsl, m // tm),
        in_specs=[pl.BlockSpec((2, 1, tm, n), lambda b, i, f: (0, b, i, 0)),
                  pl.BlockSpec((1, 2 * nz, n), lambda b, i, f: (b, 0, 0)),
                  pl.BlockSpec((1, 2 * nz, tm), lambda b, i, f: (b, 0, i))],
        out_specs=[pl.BlockSpec((1, 2 * nz, tm), lambda b, i, f: (b, 0, i)),
                   pl.BlockSpec((1, 4 * nz, n), lambda b, i, f: (b, 0, 0))])
    return pl.pallas_call(
        partial(_plane_pair_kernel, nz=nz),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((nsl, 2 * nz, m), C.dtype),
                   jax.ShapeDtypeStruct((nsl, 4 * nz, n), C.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=_interpret(),
        name="pmt_normal_planes",
    )(jnp.asarray(first, jnp.int32).reshape(1), P, C, S)
