"""Pallas TPU kernel: fused selection-objective transform-reduce.

This is the compute hot-spot of the paper — the GPU code's
``thrust::transform_reduce`` (Fig. 1), executed ``maxit`` times per
selection.  On TPU we tile the array HBM -> VMEM in ``(block_rows, 128)``
blocks and emit *per-block partials* for the pivot(s) ``y``.  Partials are
combined by a tiny tree-reduce outside the kernel (parallel across
MegaCore, no cross-grid accumulation races); they are additive, which is
exactly what makes the paper's method shard-friendly: the same vectors are
psum'd across chips in ``core.distributed``.

ONE kernel family serves both measures (see ``core.objective``): every
body shares the tile prologue (HBM tile fetch + f32 cast + tail mask) and
the per-tile accumulators in :func:`_fg_tile` / :func:`_bin_tile`; the
weights leg is a static specialization that rides a second tile stream and
two extra mass accumulators.  The counting leg keeps its SMALLER partial
vectors — two f32 sums + two i32 counts per pivot, and no weights array
read from HBM at all (the specialization is resolved at trace time, so the
unweighted kernels are byte-identical in memory traffic to the
pre-unification ones).

Counts are carried as int32 (f32 mantissa overflows beyond 2^24 elements —
the paper's n reaches 1.34e8).

Layout notes (TPU-native, not a CUDA port; every rule below is one the
Mosaic compiler enforces):
  * last dim is the 128-lane VPU axis; ``block_rows`` a multiple of 8
    (f32 sublane tiling) — default (512, 128) = 256 KiB f32 per input tile,
    comfortably inside the default scoped VMEM with double buffering;
    short rows shrink the tile to the row (:func:`_fit_block_rows`);
  * pivots and slot bounds live in SMEM (uniform across the tile, read as
    scalars and splat against the tile) — the body cannot load from
    ``pl.ANY`` memory;
  * every FG tile reduction keeps its dims ((1, 1) vectors), and each
    grid step writes ONE lane-padded row per pivot/bracket: partial ``i``
    in lane ``i`` (FG) or slot ``s`` in lane ``s`` (histogram).  Scalars
    cannot be stored to VMEM, and a row-per-step output keeps every block
    at its array's full trailing dims;
  * the histogram visits slots in a loop, reducing each slot over the
    tile's rows only into a ``(width, 128)`` VMEM scratch row, and
    reduces lanes once per tile (transpose + row sum); its counts come out
    cumulative (one compare per slot) and are differenced outside;
  * masking by global element index handles the tail block (masked
    elements become NaN, which no comparison admits), so any ``n`` is
    supported without host-side padding corrections;
  * scalar (one-pivot) entry points are the K=1 view of the multi-pivot
    kernels — same tile reductions, same block tree-reduce, one less body
    to tune.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
DEF_BLOCK_ROWS = 512

_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)  # whole array, scalar reads


def _round_up(a: int, m: int) -> int:
    return -(-a // m) * m


def _fit_block_rows(n: int, block_rows: int) -> int:
    """Shrink the tile to the (8-row-rounded) row length when a row is
    shorter than one tile, so a short row is not padded to a full tile."""
    return max(8, min(block_rows, _round_up(-(-n // LANES), 8)))


def _pad_to_tiles(x: jax.Array, block_rows: int):
    """Shared prologue of every kernel wrapper: pad the trailing dim of
    ``x`` to a whole number of ``(block_rows, LANES)`` tiles and expose the
    tile grid as the two trailing axes.

    Returns ``(x_tiled, nblocks)`` where ``x_tiled`` has shape
    ``(*leading, nblocks * block_rows, LANES)``.  The padded tail is masked
    inside the kernels via the global element index, so any ``n`` is
    supported without host-side padding corrections.
    """
    n = x.shape[-1]
    block = block_rows * LANES
    nblocks = max(1, -(-n // block))
    padded = nblocks * block
    if padded != n:
        pad = [(0, 0)] * (x.ndim - 1) + [(0, padded - n)]
        x = jnp.pad(x, pad)
    return x.reshape(x.shape[:-1] + (nblocks * block_rows, LANES)), nblocks


def _load_tile(x_ref, w_ref, b, n, block_rows):
    """Tile prologue: f32 cast, and the tail (global element index >= n)
    set to NaN — no ``<``/``<=``/``>`` admits NaN, so masked elements fall
    out of every count, mass and sum without a per-use mask."""
    x = x_ref[...].reshape(block_rows, LANES).astype(jnp.float32)
    rows = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    valid = (b * block_rows + rows) * LANES + cols < n
    x = jnp.where(valid, x, jnp.float32(jnp.nan))
    if w_ref is None:
        return x, None
    return x, w_ref[...].reshape(block_rows, LANES).astype(jnp.float32)


def _tile_sum(v, dtype=jnp.float32):
    """Whole-tile reduction that keeps its dims: ``(1, 1)``."""
    return jnp.sum(v, axis=(0, 1), keepdims=True, dtype=dtype)


def _lane_row(vals, width, dtype):
    """Place ``(1, 1)`` partials in lanes ``0..len(vals)-1`` of one
    ``(1, width)`` row."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
    row = jnp.zeros((1, width), dtype)
    for i, v in enumerate(vals):
        row = jnp.where(lane == i, v, row)
    return row


# ---------------------------------------------------------------------------
# Shared per-tile accumulators (the single implementation of both measures)
# ---------------------------------------------------------------------------


def _fg_tile(x, y, w=None):
    """Per-tile additive FG partials for one pivot, each ``(1, 1)``.

    Counting leg (``w=None``): ``((sum_pos, sum_neg), (n_lt, n_le))``.
    Weights leg: ``((wsum_pos, wsum_neg, w_lt, w_le), (n_lt, n_le))`` — the
    weighted objective terms and the weight masses below / at-or-below the
    pivot; the integer counts ride along on both legs (they drive the
    engine's cap-based stopping rule).
    """
    d = x - y
    zero = jnp.zeros_like(x)
    if w is None:
        fsums = (_tile_sum(jnp.where(d > 0, d, zero)),
                 _tile_sum(jnp.where(d < 0, -d, zero)))
    else:
        fsums = (_tile_sum(jnp.where(d > 0, w * d, zero)),
                 _tile_sum(jnp.where(d < 0, -w * d, zero)),
                 _tile_sum(jnp.where(d < 0, w, zero)),
                 _tile_sum(jnp.where(d <= 0, w, zero)))
    # dtype pinned: under global x64 an unpinned int sum accumulates int64,
    # which the int32 output refs reject (and the engine carries int32)
    cnts = (_tile_sum(d < 0, jnp.int32), _tile_sum(d <= 0, jnp.int32))
    return fsums, cnts


def _bin_tile(x, bound, nslots, acc_refs, w=None, want_sums=True):
    """Per-tile slot partials for one bracket, each a ``(1, width)`` row
    with slot ``s`` in lane ``s`` (``width >= nslots``, lane-padded).

    ``bound(s) -> (lower_s, upper_s)`` reads the slot's realized bounds as
    scalars; ``lower_0`` is NaN, which no ``x <= lower_0`` admits.  The
    first output is CUMULATIVE: lane ``s`` holds ``count(x <= upper_s)``,
    one compare per slot; the slot counts are its differences, taken
    outside the kernel (exact: the bounds are monotone).  The per-slot
    sums need the slot mask ``x <= upper_s and not x <= lower_s``:
    counting leg ``bsum``; weights leg ``(wcnt, wsum)`` — weight mass and
    ``sum(w*x)``.

    Each slot reduces the ``(block_rows, LANES)`` tile over rows only, into
    row ``s`` of a ``(width, LANES)`` VMEM scratch per output
    (``acc_refs``); the lane reduction runs once per tile, on the
    transposed scratch.

    ``want_sums=False`` (static) drops the trailing per-slot sum — only
    the in-bin polish reads ``bsum``/``wsum``; plain binned sweeps skip
    that accumulator and its HBM writeback entirely (the weighted mass
    vector ``wcnt`` always rides: it IS the weighted narrowing signal).
    """
    zero = jnp.zeros_like(x)

    def rows_sum(v, dtype=jnp.float32):
        return jnp.sum(v, axis=0, keepdims=True, dtype=dtype)

    def slot(s, carry):
        lo, up = bound(s)
        le = x <= up
        vals = [rows_sum(le, jnp.int32)]
        if w is not None or want_sums:
            m = le & jnp.logical_not(x <= lo)
            if w is not None:
                vals.append(rows_sum(jnp.where(m, w, zero)))
            if want_sums:
                vals.append(rows_sum(jnp.where(m, x if w is None else w * x,
                                               zero)))
        for ref, v in zip(acc_refs, vals):
            ref[pl.ds(s, 1), :] = v
        return carry

    jax.lax.fori_loop(0, nslots, slot, 0)
    # (width, LANES) -> (LANES, width): slot s lands in lane s
    return tuple(jnp.sum(ref[...].T, axis=0, keepdims=True)
                 for ref in acc_refs)


# ---------------------------------------------------------------------------
# Kernel bodies: one multi-pivot + one row-batched body per pass kind, each
# statically specialized on the weights leg (the extra tile stream and
# wider partial vector exist only when weighted=True)
# ---------------------------------------------------------------------------


def _fg_kernel_multi(y_ref, *refs, n, npiv, block_rows, weighted):
    """One x (or x/w) tile, ALL K pivots: the tile is read HBM -> VMEM once
    and the K per-pivot partial rows are computed from registers/VMEM —
    K× less HBM traffic than K independent passes (the win behind shared-x
    batched selection: a quantile set costs one sweep per iteration, not
    K).  K is static (the pivot vector's shape), so the pivot loop is
    unrolled at trace time; all stores use static indices.  Scalar
    ``cp_partials`` / ``wcp_partials`` are the K=1 view."""
    x_ref, w_ref = refs[0], (refs[1] if weighted else None)
    fsum_ref, cnt_ref = refs[-2:]
    x, w = _load_tile(x_ref, w_ref, pl.program_id(0), n, block_rows)
    for j in range(npiv):  # static unroll: npiv is a trace-time constant
        fsums, cnts = _fg_tile(x, y_ref[j], w)
        fsum_ref[0, j:j + 1, :] = _lane_row(fsums, LANES, jnp.float32)
        cnt_ref[0, j:j + 1, :] = _lane_row(cnts, LANES, jnp.int32)


def _fg_kernel_batched(y_ref, *refs, n, block_rows, weighted):
    """Row-wise body: grid (B, nblocks), one pivot per problem row;
    ``y_ref`` is this row's SMEM ``(1, 1, 1)`` pivot block."""
    x_ref, w_ref = refs[0], (refs[1] if weighted else None)
    fsum_ref, cnt_ref = refs[-2:]
    x, w = _load_tile(x_ref, w_ref, pl.program_id(1), n, block_rows)
    fsums, cnts = _fg_tile(x, y_ref[0, 0, 0], w)
    fsum_ref[...] = _lane_row(fsums, LANES, jnp.float32).reshape(
        fsum_ref.shape)
    cnt_ref[...] = _lane_row(cnts, LANES, jnp.int32).reshape(cnt_ref.shape)


def _split_outs(refs):
    """Histogram bodies get their outputs, then one scratch per output."""
    half = len(refs) // 2
    return refs[:half], refs[half:]


def _hist_kernel_multi(b_ref, *refs, n, npiv, nslots, block_rows, weighted,
                       want_sums):
    """One x (or x/w) tile, ALL K brackets: like :func:`_fg_kernel_multi`,
    the tile is resident once and every live bracket's histogram is
    computed from it (K static, bracket loop unrolls at trace time).
    ``b_ref`` is the SMEM ``(2, K, nslots)`` lower/upper slot bounds;
    ``refs`` are the data tiles, the outputs and one scratch per output."""
    x_ref, w_ref = refs[0], (refs[1] if weighted else None)
    out_refs, acc_refs = _split_outs(refs[2 if weighted else 1:])
    x, w = _load_tile(x_ref, w_ref, pl.program_id(0), n, block_rows)
    for j in range(npiv):  # static unroll
        outs = _bin_tile(x, lambda s: (b_ref[0, j, s], b_ref[1, j, s]),
                         nslots, acc_refs, w, want_sums=want_sums)
        for ref, v in zip(out_refs, outs):
            ref[0, j:j + 1, :] = v


def _hist_kernel_batched(b_ref, *refs, n, nslots, block_rows, weighted,
                         want_sums):
    """Row-wise histogram body: grid (B, nblocks), per-row slot bounds.
    ``b_ref`` is this row's SMEM ``(1, 2, nslots)`` block."""
    x_ref, w_ref = refs[0], (refs[1] if weighted else None)
    out_refs, acc_refs = _split_outs(refs[2 if weighted else 1:])
    x, w = _load_tile(x_ref, w_ref, pl.program_id(1), n, block_rows)
    outs = _bin_tile(x, lambda s: (b_ref[0, 0, s], b_ref[0, 1, s]),
                     nslots, acc_refs, w, want_sums=want_sums)
    for ref, v in zip(out_refs, outs):
        ref[...] = v.reshape(ref.shape)


# ---------------------------------------------------------------------------
# pallas_call builders (shared pad/spec/tree-reduce plumbing)
# ---------------------------------------------------------------------------


def _tiles(x, w, block_rows):
    """Pad ``x`` (and ``w``) to tiles; ``(data, nblocks, block_rows)``."""
    block_rows = _fit_block_rows(x.shape[-1], block_rows)
    x2, nblocks = _pad_to_tiles(x, block_rows)
    data = [x2]
    if w is not None:
        data.append(_pad_to_tiles(w, block_rows)[0])
    return data, nblocks, block_rows


def _fg_call_multi(x, w, y, *, block_rows, interpret):
    """Shared-x multi-pivot launch; returns per-pivot (K,) partial vectors
    (the counting leg's four or the weights leg's six)."""
    weighted = w is not None
    n = x.size
    npiv = y.shape[0]
    data, nblocks, block_rows = _tiles(
        x.reshape(-1), None if w is None else w.reshape(-1), block_rows)
    y = jnp.asarray(y, jnp.float32).reshape(npiv)
    nf = 4 if weighted else 2

    out = pl.BlockSpec((1, npiv, LANES), lambda i: (i, 0, 0))
    fsum, cnt = pl.pallas_call(
        functools.partial(_fg_kernel_multi, n=n, npiv=npiv,
                          block_rows=block_rows, weighted=weighted),
        grid=(nblocks,),
        in_specs=[_SMEM]
        + [pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))] * len(data),
        out_specs=[out, out],
        out_shape=[
            jax.ShapeDtypeStruct((nblocks, npiv, LANES), jnp.float32),
            jax.ShapeDtypeStruct((nblocks, npiv, LANES), jnp.int32),
        ],
        interpret=interpret,
        name="fg_multi",
    )(y, *data)
    s = jnp.sum(fsum[..., :nf], axis=0)
    # int32 under global x64 too
    c = jnp.sum(cnt[..., :2], axis=0, dtype=jnp.int32)
    return tuple(s[:, i] for i in range(nf)) + (c[:, 0], c[:, 1])


def _fg_call_batched(x, w, y, *, block_rows, interpret):
    """Row-wise launch over (B, n) problems; returns (B,) partial vectors."""
    weighted = w is not None
    bsz, n = x.shape
    data, nblocks, block_rows = _tiles(x, w, block_rows)
    y = jnp.asarray(y, jnp.float32).reshape(bsz, 1, 1)
    nf = 4 if weighted else 2

    # one (1, LANES) row per grid step: trailing block dims == array dims
    out = pl.BlockSpec((1, 1, 1, LANES), lambda r, b: (r, b, 0, 0))
    fsum, cnt = pl.pallas_call(
        functools.partial(_fg_kernel_batched, n=n, block_rows=block_rows,
                          weighted=weighted),
        grid=(bsz, nblocks),
        # this row's pivot only: SMEM holds one scalar, not (B,)
        in_specs=[pl.BlockSpec((1, 1, 1), lambda r, b: (r, 0, 0),
                               memory_space=pltpu.SMEM)]
        + [pl.BlockSpec((1, block_rows, LANES),
                        lambda r, b: (r, b, 0))] * len(data),
        out_specs=[out, out],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, nblocks, 1, LANES), jnp.float32),
            jax.ShapeDtypeStruct((bsz, nblocks, 1, LANES), jnp.int32),
        ],
        interpret=interpret,
        name="fg_batched",
    )(y, *data)
    s = jnp.sum(fsum[:, :, 0, :nf], axis=1)
    # int32 under global x64 too
    c = jnp.sum(cnt[:, :, 0, :2], axis=1, dtype=jnp.int32)
    return tuple(s[..., i] for i in range(nf)) + (c[..., 0], c[..., 1])


def _slot_bounds(edges):
    """``(..., nbins+1)`` edges -> ``(..., nbins+2)`` (lower, upper) slot
    bounds.  Pure concatenation — NO fp arithmetic (see the exactness
    contract below).  Slot 0's lower bound is NaN: ``x <= NaN`` admits
    nothing, so slot 0 keeps every ``x <= e_0``, ``-inf`` included."""
    nan = jnp.full_like(edges[..., :1], jnp.nan)
    pinf = jnp.full_like(edges[..., :1], jnp.inf)
    return (jnp.concatenate([nan, edges], axis=-1),
            jnp.concatenate([edges, pinf], axis=-1))


def _hist_io(nout, shape, width):
    """Histogram ``(out_shape, scratch_shapes)``: cnt is int32, the
    mass/sum slots f32; each output gets a ``(width, LANES)`` scratch."""
    dts = [jnp.int32] + [jnp.float32] * (nout - 1)
    return ([jax.ShapeDtypeStruct(shape, dt) for dt in dts],
            [pltpu.VMEM((width, LANES), dt) for dt in dts])


def _slot_counts(outs, want_sums):
    """Cumulative counts -> slot counts (exact integer differences); the
    caller gets ``None`` for a dropped sum."""
    cum = outs[0]
    cnt = cum - jnp.pad(cum[..., :-1], [(0, 0)] * (cum.ndim - 1) + [(1, 0)])
    outs = (cnt,) + tuple(outs[1:])
    return outs if want_sums else outs + (None,)


def _hist_call_multi(x, w, edges, *, block_rows, interpret,
                     want_sums=True):
    """Shared-x multi-bracket histogram launch; per-bracket slot vectors.
    ``want_sums=False`` drops the trailing per-slot sum output (and its
    accumulator/HBM writeback) — the caller gets ``None`` in its place."""
    weighted = w is not None
    n = x.size
    npiv, nslots = edges.shape[0], edges.shape[-1] + 1
    width = _round_up(nslots, LANES)
    data, nblocks, block_rows = _tiles(
        x.reshape(-1), None if w is None else w.reshape(-1), block_rows)
    lower, upper = _slot_bounds(jnp.asarray(edges, jnp.float32))
    bounds = jnp.stack([lower, upper])  # (2, K, nslots)
    nout = (3 if weighted else 2) - (not want_sums)

    out_shape, scratch = _hist_io(nout, (nblocks, npiv, width), width)
    outs = pl.pallas_call(
        functools.partial(_hist_kernel_multi, n=n, npiv=npiv, nslots=nslots,
                          block_rows=block_rows, weighted=weighted,
                          want_sums=want_sums),
        grid=(nblocks,),
        in_specs=[_SMEM]
        + [pl.BlockSpec((block_rows, LANES), lambda i: (i, 0))] * len(data),
        out_specs=[pl.BlockSpec((1, npiv, width),
                                lambda i: (i, 0, 0))] * nout,
        out_shape=out_shape,
        scratch_shapes=scratch,
        interpret=interpret,
        name="histogram_multi",
    )(bounds, *data)
    return _slot_counts(tuple(jnp.sum(o[..., :nslots], axis=0, dtype=o.dtype)
                              for o in outs), want_sums)


def _hist_call_batched(x, w, edges, *, block_rows, interpret,
                       want_sums=True):
    """Row-wise histogram launch: per-row slot vectors ``(B, nbins + 2)``."""
    weighted = w is not None
    bsz, n = x.shape
    nslots = edges.shape[-1] + 1
    width = _round_up(nslots, LANES)
    data, nblocks, block_rows = _tiles(x, w, block_rows)
    lower, upper = _slot_bounds(
        jnp.asarray(edges, jnp.float32).reshape(bsz, nslots - 1))
    bounds = jnp.stack([lower, upper], axis=1)  # (B, 2, nslots)
    nout = (3 if weighted else 2) - (not want_sums)

    out_shape, scratch = _hist_io(nout, (bsz, nblocks, 1, width), width)
    outs = pl.pallas_call(
        functools.partial(_hist_kernel_batched, n=n, nslots=nslots,
                          block_rows=block_rows, weighted=weighted,
                          want_sums=want_sums),
        grid=(bsz, nblocks),
        # this row's bounds only: SMEM holds (2, nslots), not (B, 2, nslots)
        in_specs=[pl.BlockSpec((1, 2, nslots), lambda r, b: (r, 0, 0),
                               memory_space=pltpu.SMEM)]
        + [pl.BlockSpec((1, block_rows, LANES),
                        lambda r, b: (r, b, 0))] * len(data),
        # one (1, width) row per grid step: trailing block dims == array dims
        out_specs=[pl.BlockSpec((1, 1, 1, width),
                                lambda r, b: (r, b, 0, 0))] * nout,
        out_shape=out_shape,
        scratch_shapes=scratch,
        interpret=interpret,
        name="histogram_batched",
    )(bounds, *data)
    return _slot_counts(tuple(jnp.sum(o[:, :, 0, :nslots], axis=1,
                                      dtype=o.dtype) for o in outs),
                        want_sums)


# ---------------------------------------------------------------------------
# Public entry points (stable names; thin views of the builders above)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def cp_partials(
    x: jax.Array,
    y: jax.Array,
    *,
    block_rows: int = DEF_BLOCK_ROWS,
    interpret: bool = False,
):
    """Per-pivot fused partials of the selection objective (K=1 view of the
    multi-pivot kernel).

    Returns ``(sum_pos, sum_neg, n_lt, n_le)`` scalars, bit-identical in
    count terms to the pure-jnp oracle ``kernels.ref.cp_partials_ref``.
    """
    parts = _fg_call_multi(x, None, jnp.asarray(y, jnp.float32).reshape(1),
                           block_rows=block_rows, interpret=interpret)
    return tuple(p[0] for p in parts)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def cp_partials_multi(
    x: jax.Array,
    y: jax.Array,
    *,
    block_rows: int = DEF_BLOCK_ROWS,
    interpret: bool = False,
):
    """Shared-x multi-pivot partials: ``x`` is (n,), ``y`` is (K,) pivots.

    Returns four (K,) vectors ``(sum_pos, sum_neg, n_lt, n_le)``; count
    terms bit-identical to ``kernels.ref.cp_partials_multi_ref``.  This is
    the data pass of shared-x batched selection (``multi_order_statistic`` /
    ``quantiles``): all K brackets iterate against one sweep of ``x``.
    """
    return _fg_call_multi(x, None, y, block_rows=block_rows,
                          interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def cp_partials_batched(
    x: jax.Array,
    y: jax.Array,
    *,
    block_rows: int = DEF_BLOCK_ROWS,
    interpret: bool = False,
):
    """Row-wise partials: ``x`` is (B, n), ``y`` is (B,) pivots.

    Used by the vectorized selection solver (coordinate-wise medians for
    robust gradient aggregation solve millions of small problems at once).
    Returns four (B,) vectors.
    """
    return _fg_call_batched(x, None, y, block_rows=block_rows,
                            interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def wcp_partials(
    x: jax.Array,
    w: jax.Array,
    y: jax.Array,
    *,
    block_rows: int = DEF_BLOCK_ROWS,
    interpret: bool = False,
):
    """Weighted fused partials: ``x``/``w`` (n,), scalar pivot ``y`` (K=1
    view of the weighted multi-pivot kernel).

    Returns ``(wsum_pos, wsum_neg, w_lt, w_le, n_lt, n_le)`` scalars; count
    terms bit-identical to ``kernels.ref.wcp_partials_ref``.
    """
    parts = _fg_call_multi(x, w, jnp.asarray(y, jnp.float32).reshape(1),
                           block_rows=block_rows, interpret=interpret)
    return tuple(p[0] for p in parts)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def wcp_partials_multi(
    x: jax.Array,
    w: jax.Array,
    y: jax.Array,
    *,
    block_rows: int = DEF_BLOCK_ROWS,
    interpret: bool = False,
):
    """Shared-x weighted multi-pivot partials: ``x``/``w`` (n,), ``y`` (K,).

    Returns six (K,) vectors.
    """
    return _fg_call_multi(x, w, y, block_rows=block_rows,
                          interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def wcp_partials_batched(
    x: jax.Array,
    w: jax.Array,
    y: jax.Array,
    *,
    block_rows: int = DEF_BLOCK_ROWS,
    interpret: bool = False,
):
    """Row-wise weighted partials: ``x``/``w`` (B, n), ``y`` (B,) pivots.

    Returns six (B,) vectors ``(wsum_pos, wsum_neg, w_lt, w_le, n_lt,
    n_le)``.
    """
    return _fg_call_batched(x, w, y, block_rows=block_rows,
                            interpret=interpret)


# ---------------------------------------------------------------------------
# Binned bracket descent: multi-bin histogram kernels
# ---------------------------------------------------------------------------
#
# One sweep bins x against the current bracket's NBINS sub-intervals and
# emits additive per-slot partials — the measure vector localizes x_(k) to
# ONE bin (log2(NBINS) bisection steps of information per data pass) and
# the per-bin sums are the CP support-line ingredients (the in-bin polish:
# the support lines at every edge come free from prefix sums), all for the
# HBM cost of a single fused pass.  All outputs are additive over
# blocks/shards, so they psum across a mesh exactly like the FG partials.
#
# Slot layout (nbins + 2 slots for edges e_0 <= ... <= e_nbins):
#   slot 0          x <= e_0
#   slot j          e_{j-1} < x <= e_j          (j = 1..nbins)
#   slot nbins+1    x > e_nbins
# so prefix sums over slots 0..j give exact count(x <= e_j) / sum(x <= e_j)
# at every edge, and sum(cnt) == n is the per-row count invariant.
#
# EXACTNESS CONTRACT: the kernels take the REALIZED edge values — computed
# ONCE by the engine via ``kernels.ref.bin_edges`` (or
# ``core.selection.polish_edges``) — and only COMPARE against them.
# Recomputing edges here from (lo, hi) would be unsound: XLA may contract
# ``lo + w*j`` into an FMA in one fusion context and not another, yielding
# different fp edges (observed at full-f32-range brackets); comparisons
# against one shared array cannot diverge, so the histogram counts are
# exactly consistent with the engine's later ``x <= e_j`` narrowing and
# finalize comparisons.


@functools.partial(jax.jit,
                   static_argnames=("block_rows", "interpret", "want_sums"))
def cp_histogram(
    x: jax.Array,
    edges: jax.Array,
    *,
    block_rows: int = DEF_BLOCK_ROWS,
    interpret: bool = False,
    want_sums: bool = True,
):
    """Binned data pass: ``x`` (n,), realized bracket edges (nbins+1,)
    (monotone non-decreasing; build them with ``kernels.ref.bin_edges``).
    The K=1 view of :func:`cp_histogram_multi`.

    Returns ``(cnt, bsum)`` of shape ``(nbins + 2,)`` — counts int32
    (bit-identical to ``kernels.ref.cp_histogram_ref``), sums f32.
    ``want_sums=False`` (static) skips the sum accumulator and its HBM
    writeback — only the in-bin polish reads ``bsum`` — returning
    ``(cnt, None)``.
    """
    nbins = edges.shape[-1] - 1
    outs = _hist_call_multi(
        x, None, jnp.asarray(edges, jnp.float32).reshape(1, nbins + 1),
        block_rows=block_rows, interpret=interpret, want_sums=want_sums)
    return tuple(o[0] if o is not None else None for o in outs)


@functools.partial(jax.jit,
                   static_argnames=("block_rows", "interpret", "want_sums"))
def cp_histogram_batched(
    x: jax.Array,
    edges: jax.Array,
    *,
    block_rows: int = DEF_BLOCK_ROWS,
    interpret: bool = False,
    want_sums: bool = True,
):
    """Row-wise binned pass: ``x`` (B, n), per-row realized edges
    ``(B, nbins+1)``.  Returns ``(cnt, bsum)`` of shape ``(B, nbins + 2)``
    (``bsum=None`` under ``want_sums=False``)."""
    return _hist_call_batched(x, None, edges, block_rows=block_rows,
                              interpret=interpret, want_sums=want_sums)


@functools.partial(jax.jit,
                   static_argnames=("block_rows", "interpret", "want_sums"))
def cp_histogram_multi(
    x: jax.Array,
    edges: jax.Array,
    *,
    block_rows: int = DEF_BLOCK_ROWS,
    interpret: bool = False,
    want_sums: bool = True,
):
    """Shared-x multi-bracket binned pass: ``x`` (n,), per-pivot realized
    edges ``(K, nbins+1)``.  Returns ``(cnt, bsum)`` of shape
    ``(K, nbins + 2)`` (``bsum=None`` under ``want_sums=False``)."""
    return _hist_call_multi(x, None, edges, block_rows=block_rows,
                            interpret=interpret, want_sums=want_sums)


@functools.partial(jax.jit,
                   static_argnames=("block_rows", "interpret", "want_sums"))
def wcp_histogram(
    x: jax.Array,
    w: jax.Array,
    edges: jax.Array,
    *,
    block_rows: int = DEF_BLOCK_ROWS,
    interpret: bool = False,
    want_sums: bool = True,
):
    """Weighted binned pass: ``x``/``w`` (n,), realized edges (nbins+1,).
    The K=1 view of :func:`wcp_histogram_multi`.

    Returns ``(cnt, wcnt, wsum)`` of shape ``(nbins + 2,)`` — counts int32
    (bit-identical to ``kernels.ref.wcp_histogram_ref``), masses/sums f32.
    ``want_sums=False`` skips ``wsum`` (returns ``None``); the mass vector
    ``wcnt`` always rides (it IS the weighted narrowing signal).
    """
    nbins = edges.shape[-1] - 1
    outs = _hist_call_multi(
        x, w, jnp.asarray(edges, jnp.float32).reshape(1, nbins + 1),
        block_rows=block_rows, interpret=interpret, want_sums=want_sums)
    return tuple(o[0] if o is not None else None for o in outs)


@functools.partial(jax.jit,
                   static_argnames=("block_rows", "interpret", "want_sums"))
def wcp_histogram_batched(
    x: jax.Array,
    w: jax.Array,
    edges: jax.Array,
    *,
    block_rows: int = DEF_BLOCK_ROWS,
    interpret: bool = False,
    want_sums: bool = True,
):
    """Row-wise weighted binned pass: ``x``/``w`` (B, n), per-row edges
    ``(B, nbins+1)``.  Returns ``(cnt, wcnt, wsum)``, each
    ``(B, nbins + 2)`` (``wsum=None`` under ``want_sums=False``)."""
    return _hist_call_batched(x, w, edges, block_rows=block_rows,
                              interpret=interpret, want_sums=want_sums)


@functools.partial(jax.jit,
                   static_argnames=("block_rows", "interpret", "want_sums"))
def wcp_histogram_multi(
    x: jax.Array,
    w: jax.Array,
    edges: jax.Array,
    *,
    block_rows: int = DEF_BLOCK_ROWS,
    interpret: bool = False,
    want_sums: bool = True,
):
    """Shared-x weighted multi-bracket binned pass: ``x``/``w`` (n,),
    per-pivot realized edges ``(K, nbins+1)``.  Returns ``(cnt, wcnt,
    wsum)``, each ``(K, nbins + 2)`` (``wsum=None`` under
    ``want_sums=False``)."""
    return _hist_call_multi(x, w, edges, block_rows=block_rows,
                            interpret=interpret, want_sums=want_sums)
