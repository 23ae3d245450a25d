"""Pure-jnp oracles for every Pallas kernel (the correctness references)."""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp


def _accum_dtype(x):
    # The TPU kernel accumulates in f32, so the oracle promotes low-precision
    # inputs (bf16) to f32 for bit-comparable partials — but NEVER downcasts:
    # f64 selection (x64 mode on CPU) must keep full precision or the count
    # certificates lie about exactness.
    return jnp.promote_types(x.dtype, jnp.float32)


def cp_partials_ref(x: jax.Array, y: jax.Array):
    """Oracle for kernels.cp_objective.cp_partials."""
    dt = _accum_dtype(x)
    x = x.reshape(-1).astype(dt)
    y = jnp.asarray(y, dt)
    d = x - y
    sum_pos = jnp.sum(jnp.maximum(d, 0))
    sum_neg = jnp.sum(jnp.maximum(-d, 0))
    n_lt = jnp.sum(d < 0, dtype=jnp.int32)
    n_le = jnp.sum(d <= 0, dtype=jnp.int32)
    return sum_pos, sum_neg, n_lt, n_le


def cp_partials_batched_ref(x: jax.Array, y: jax.Array):
    """Oracle for kernels.cp_objective.cp_partials_batched."""
    dt = _accum_dtype(x)
    return jax.vmap(cp_partials_ref)(x.astype(dt), jnp.asarray(y, dt))


def cp_partials_multi_ref(x: jax.Array, y: jax.Array):
    """Oracle for kernels.cp_objective.cp_partials_multi: one shared ``x``
    (n,), ``y`` is (K,) pivots; returns four (K,) vectors."""
    dt = _accum_dtype(x)
    return jax.vmap(cp_partials_ref, in_axes=(None, 0))(
        x.reshape(-1).astype(dt), jnp.asarray(y, dt)
    )


# ---------------------------------------------------------------------------
# Binned bracket descent: slot assignment + histogram oracles
# ---------------------------------------------------------------------------

BIN_IMPLS = ("searchsorted", "arithmetic")

# Chunk length for the factored one-hot accumulation below: one chunk's
# factor matrices stay L2-resident while the GEMM reduces them, which is
# what makes the arithmetic pass map-reduce-fast on CPU.
HIST_CHUNK = 1 << 14

# The one-hot contractions carry data values (per-slot sums), so they run
# at full precision: a TPU's default rounds f32 operands to bf16.
_EXACT = jax.lax.Precision.HIGHEST


def bin_edges(lo, hi, nbins: int):
    """Realized fp bin-edge values ``e_j = clip(lo + w*j, lo, hi)`` with
    ``w = hi/nbins - lo/nbins`` and ``e_nbins`` forced to ``hi`` exactly,
    appended as a trailing axis of size ``nbins + 1``.

    SINGLE SOURCE OF TRUTH for edge construction: the engine computes the
    edges ONCE per sweep with this function and passes the realized array
    to the histogram kernels/oracles, which only COMPARE against it — no
    consumer ever recomputes edge arithmetic (XLA FMA contraction makes
    recomputed ``lo + w*j`` fusion-context-dependent), so histogram counts
    stay bit-consistent with the engine's later ``x <= e_j`` narrowing and
    finalize comparisons.  The sequence is monotone non-decreasing in fp
    (``w >= 0``, ``w*j`` and ``lo + t`` are monotone, clip preserves
    order), which the bin-index search relies on.

    Overflow safety: ``(hi - lo)`` overflows f32 for full-range brackets
    (e.g. data spanning ±3e38 — width inf, NaN edges, garbage descent), so
    ``w`` divides BEFORE differencing (each term <= f32max/nbins; their
    difference <= f32max for nbins >= 2), the width is clamped into the
    finite range (nbins == 1 — reachable through ``polish_edges`` with a
    tiny bin budget — would otherwise make ``w = inf`` and ``w * 0 = NaN``)
    and ``lo + w*j`` — which can still overflow for large j — is clipped
    into ``[lo, hi]`` (collapsed top bins are just empty).
    """
    lo = jnp.asarray(lo)
    hi = jnp.asarray(hi, lo.dtype)
    w = jnp.clip(hi / nbins - lo / nbins, 0,
                 jnp.asarray(jnp.finfo(lo.dtype).max, lo.dtype))
    j = jnp.arange(nbins + 1)
    e = jnp.clip(lo[..., None] + w[..., None] * j.astype(lo.dtype),
                 lo[..., None], hi[..., None])
    return jnp.where(j == nbins, hi[..., None], e)


def searchsorted_slots(x: jax.Array, edges: jax.Array) -> jax.Array:
    """THE slot oracle: ``slot = count(edges < x)`` by binary search.

    Slot layout (``nbins + 2`` slots): 0 = ``x <= e_0``; j in 1..nbins =
    ``e_{j-1} < x <= e_j``; nbins+1 = ``x > e_nbins`` —
    ``searchsorted('left')`` on the monotone realized edges.  ``x``
    ``(..., n)`` and ``edges`` ``(..., nbins+1)`` broadcast over leading
    dims; returns int32 slots shaped like the broadcast ``x``.
    """
    if edges.ndim == 1:
        return jnp.searchsorted(edges, x, side="left").astype(jnp.int32)
    lead = jnp.broadcast_shapes(x.shape[:-1], edges.shape[:-1])
    xb = jnp.broadcast_to(x, lead + x.shape[-1:])
    eb = jnp.broadcast_to(edges, lead + edges.shape[-1:])
    out = jax.vmap(lambda e, xi: jnp.searchsorted(e, xi, side="left"))(
        eb.reshape((-1,) + eb.shape[-1:]),
        xb.reshape((-1,) + xb.shape[-1:]))
    return out.reshape(lead + x.shape[-1:]).astype(jnp.int32)


def _take_last(a, idx):
    """Per-element gather along the trailing axis with broadcast leading
    dims (``a`` (..., m), ``idx`` (..., n) int32)."""
    lead = jnp.broadcast_shapes(a.shape[:-1], idx.shape[:-1])
    a = jnp.broadcast_to(a, lead + a.shape[-1:])
    idx = jnp.broadcast_to(idx, lead + idx.shape[-1:])
    return jnp.take_along_axis(a, idx, axis=-1)


def _arith_candidates(x: jax.Array, edges: jax.Array) -> jax.Array:
    """Raw arithmetic slot candidates ``clip(floor((x - lo) * inv_w) + 1)``.

    MONOTONE NON-DECREASING in ``x`` by construction (every stage —
    multiply by a positive constant, subtract a constant, floor, the
    inf-saturating sanitize, clip — is fp-monotone), which is what lets
    :func:`bin_slots` verify soundness at the ``nbins + 1`` edges alone.
    Overflow-safe: ``x*inv_w - lo*inv_w`` keeps each product ~``nbins``
    for in-bracket data, so full-f32-range brackets never overflow the
    difference (out-of-bracket infinities saturate to the end slots).
    NaN data maps to the top slot, matching binary search (every NaN
    comparison is false, so searchsorted walks right).
    """
    nbins = edges.shape[-1] - 1
    dt = edges.dtype
    x = jnp.asarray(x, dt)
    lo, hi = edges[..., :1], edges[..., -1:]
    # candidate-only width/reciprocal (same divide-before-diff trick as
    # bin_edges); rounding here is harmless — soundness is verified
    # against the realized edges, never against this arithmetic
    w = hi / nbins - lo / nbins
    iw = jnp.where(w > 0, 1.0 / jnp.where(w > 0, w, 1), 0).astype(dt)
    ok_w = (w > 0) & jnp.isfinite(iw) & (iw > 0)
    pos = x * iw - lo * iw
    cand = jnp.where(ok_w, jnp.floor(pos) + 1,
                     # degenerate bracket (w == 0 / FTZ-flushed): interior
                     # values land in the top real bin (all realized
                     # interior edges collapse onto lo there)
                     jnp.where(x <= lo, 0.0,
                               jnp.where(x > hi, float(nbins + 1),
                                         float(nbins))))
    cand = jnp.where(jnp.isnan(x), float(nbins + 1),
                     jnp.nan_to_num(cand, nan=0.0, posinf=float(nbins + 1),
                                    neginf=0.0))
    return jnp.clip(cand, 0.0, nbins + 1).astype(jnp.int32)


def arithmetic_slots(x: jax.Array, edges: jax.Array, *,
                     widen: bool = True) -> jax.Array:
    """Arithmetic slot candidates + the ±1 widening step (Tibshirani's
    successive-binning slotting, made sound against the REALIZED edges).

    The widening compares each element against the realized ``edges`` at
    its candidate's two neighboring boundaries: a candidate one too high
    (``x <= e_{c-1}``, e.g. ``x`` exactly on an edge, where fp rounding of
    the reciprocal multiply puts ``pos`` at the integer) steps down, one
    too low (``x > e_c``) steps up.  For any candidate within ±1 of the
    true slot the corrected result is bit-identical to
    :func:`searchsorted_slots` — recomputed edge arithmetic appears ONLY
    in the candidate, never in a comparison that decides the final slot.

    ``widen=False`` disables the correction (the raw clipped candidate):
    it exists for the differential suite's adversarial leg, which proves
    an unverified implementation is caught.  This function never falls
    back to binary search; callers that need the full bit-exactness
    guarantee in degenerate regimes (clip-collapsed edges of full-range
    brackets, duplicate edges of ulp-wide brackets, denormal-underflowed
    bin widths) go through :func:`bin_slots`, which certifies the
    candidate map at the edges and rescues failures through the
    searchsorted oracle.
    """
    c = _arith_candidates(x, edges)
    if not widen:
        return c
    nbins = edges.shape[-1] - 1
    x = jnp.asarray(x, edges.dtype)
    # ±1 widening against the REALIZED edges (never recomputed)
    e_dn = _take_last(edges, jnp.maximum(c - 1, 0))
    e_up = _take_last(edges, jnp.minimum(c, nbins))
    down = (c > 0) & (x <= e_dn)
    up = (c <= nbins) & (x > e_up) & ~down
    return c - down.astype(jnp.int32) + up.astype(jnp.int32)


def _candidates_certified_rows(edges: jax.Array) -> jax.Array:
    """Per-ladder soundness certificate: the all-edges test of
    :func:`_candidates_certified`, reduced over the trailing (edge) axis
    only.  For ``(K, nbins+1)`` edge ladders this returns a ``(K,)`` bool
    vector — one degenerate ladder (a collapsed bracket, a polish ladder
    whose uniform candidate misfires) rescues ONLY its own row, the other
    K-1 ladders keep the arithmetic fast path."""
    nbins = edges.shape[-1] - 1
    ce = _arith_candidates(edges, edges)
    i = jnp.arange(nbins + 1, dtype=jnp.int32)
    return jnp.all((ce >= i) & (ce <= i + 1), axis=-1)


def _candidates_certified(edges: jax.Array) -> jax.Array:
    """O(nbins) soundness certificate for the arithmetic candidates.

    The candidate map is monotone in ``x`` (see :func:`_arith_candidates`),
    so for any ``x`` with true slot ``j`` — i.e. ``e_{j-1} < x <= e_j`` —
    the candidate is bracketed by the candidates AT those two edges.  If
    ``i <= cand(e_i) <= i + 1`` holds for every edge ``i`` (trivially true
    in exact arithmetic, where ``cand(e_i) = i + 1``), every element's
    candidate is within ±1 of its true slot and the widening makes the
    final slots exactly searchsorted's.  Degenerate regimes (duplicate or
    clip-collapsed edges, FTZ-flushed widths, polish's non-uniform
    ladders) break the bound AT AN EDGE, so checking the ``nbins + 1``
    edges — instead of all ``n`` elements — loses nothing.
    """
    return jnp.all(_candidates_certified_rows(edges))


def bin_slots(x: jax.Array, edges: jax.Array,
              impl: str = "searchsorted") -> jax.Array:
    """Slot assignment, bit-identical to :func:`searchsorted_slots` under
    BOTH impls.

    ``impl='arithmetic'`` replaces the per-element binary search with the
    fused multiply/floor/clip candidate + ±1 widening of
    :func:`arithmetic_slots`, VERIFIED by the edge-level certificate of
    :func:`_candidates_certified`; if the certificate fails (possible only
    in degenerate regimes — clip-collapsed or duplicate edges, underflowed
    widths, non-uniform polish ladders — where a candidate can be further
    than one bin out), that call falls back to the searchsorted oracle
    wholesale, so exactness never depends on the candidate quality.  The
    certificate makes the fast path self-certifying: arithmetic slots ship
    only when provably equal.
    """
    if impl == "searchsorted":
        return searchsorted_slots(x, edges)
    if impl != "arithmetic":
        raise ValueError(f"unknown binning impl {impl!r}; one of "
                         f"{BIN_IMPLS}")
    return jax.lax.cond(
        _candidates_certified(edges),
        lambda: arithmetic_slots(x, edges),
        lambda: searchsorted_slots(x, edges),
    )


def _factored_hist(slot, rows, nslots: int, dt):
    """Per-slot sums by chunked FACTORED one-hot contraction (map-reduce).

    ``slot`` (..., n) int32 in [0, nslots); ``rows`` is a tuple of
    (..., n) value arrays (each gets a per-slot sum; the count row is
    implicit).  The slot one-hot factors through ``slot = hi*B + lo`` into
    two skinny factor matrices (m, A) and (m, B) per chunk, so the per-slot
    reduction is a tiny batched GEMM with A+B one-hot columns instead of
    ``nslots`` — the XLA:CPU-fast formulation of the histogram reduce
    (scatter-add lowers to a serialized loop there, ~10x a fused pass).

    Counts stay exact for any n: each chunk's products are 0/1 floats whose
    per-chunk sums are <= HIST_CHUNK < 2^24 (exact in f32), accumulated
    across chunks in int32.  Value rows accumulate in ``dt`` (chunk-major
    order; exactly summable inputs — integer/dyadic weights — stay exact,
    same contract as the kernels' tile accumulation).

    Returns ``[cnt int32, *sums dt]``, each shaped ``lead + (nslots,)``.
    """
    lead = slot.shape[:-1]
    n = slot.shape[-1]
    r = max(1, int(np.prod(lead)) if lead else 1)
    m = min(HIST_CHUNK, max(n, 1))
    npad = -(-n // m) * m
    nc = npad // m
    bf = int(np.ceil(np.sqrt(nslots)))
    af = -(-nslots // bf)
    # pad slots into the all-zero one-hot row (hi == af matches no factor)
    pad = [(0, 0)] * len(lead) + [(0, npad - n)]
    sl = jnp.pad(slot, pad, constant_values=af * bf).reshape(r, nc, m)
    sl = jnp.moveaxis(sl, 1, 0)                          # (nc, r, m)
    vals = [jnp.pad(jnp.broadcast_to(jnp.asarray(v, dt), slot.shape),
                    pad).reshape(r, nc, m) for v in rows]
    vals = [jnp.moveaxis(v, 1, 0) for v in vals]
    ia = jnp.arange(af, dtype=jnp.int32)
    ib = jnp.arange(bf, dtype=jnp.int32)

    def body(acc, args):
        si = args[0]
        hi_oh = (si[..., None] // bf == ia).astype(dt)   # (r, m, A)
        lo_oh = (si[..., None] % bf == ib).astype(dt)    # (r, m, B)
        contract = lambda lhs: jnp.einsum(
            "rma,rmb->rab", lhs, lo_oh,
            precision=_EXACT).reshape(r, -1)[:, :nslots]
        cnt = contract(hi_oh)
        out = [acc[0] + cnt.astype(jnp.int32)]
        for k, v in enumerate(args[1:]):
            out.append(acc[k + 1] + contract(hi_oh * v[..., None]))
        return tuple(out), None

    acc0 = (jnp.zeros((r, nslots), jnp.int32),) + tuple(
        jnp.zeros((r, nslots), dt) for _ in rows)
    acc, _ = jax.lax.scan(body, acc0, (sl, *vals))
    return [a.reshape(lead + (nslots,)) for a in acc]


def _hist_multi_shared(x, edges, rows, nslots: int, dt):
    """ONE-SWEEP shared-x multi-ladder histogram (the jnp analogue of the
    multi-bracket Pallas kernel): ``x`` (n,) is read once per chunk and
    every ladder's ``(nslots,)`` slot vector is accumulated from the
    resident chunk — the K ladders share every data pass instead of
    paying K broadcast passes, and no ``(K, n)`` intermediate ever exists
    (everything per-chunk is capped at ``(K, HIST_CHUNK)``).

    Exactness: the per-chunk slots are the verified arithmetic candidates
    + ±1 widening of :func:`arithmetic_slots`, certified PER LADDER by
    :func:`_candidates_certified_rows`; when every ladder certifies, the
    scan runs arithmetic-only, otherwise a mixed scan also binary-searches
    the chunk and each uncertified ladder takes the searchsorted slots —
    per-k rescue, bit-identical counts to the searchsorted oracle either
    way.  Count/sum accumulation follows :func:`_factored_hist` (per-chunk
    0/1 sums exact in f32, int32 across chunks; value rows in ``dt``).

    Returns ``[cnt int32, *sums dt]``, each shaped ``(K, nslots)``.
    """
    kk = edges.shape[0]
    n = x.shape[-1]
    m = min(HIST_CHUNK, max(n, 1))
    npad = -(-n // m) * m
    nc = npad // m
    bf = int(np.ceil(np.sqrt(nslots)))
    af = -(-nslots // bf)
    sent = af * bf  # pad sentinel: hi factor == af matches no column
    xp = jnp.pad(x, (0, npad - n)).reshape(nc, m)
    validc = (jnp.arange(npad, dtype=jnp.int32) < n).reshape(nc, m)
    vals = [jnp.pad(jnp.asarray(v, dt), (0, npad - n)).reshape(nc, m)
            for v in rows]
    certs = _candidates_certified_rows(edges)  # (K,)
    ia = jnp.arange(af, dtype=jnp.int32)
    ib = jnp.arange(bf, dtype=jnp.int32)

    def _slots_arith(xc):
        return arithmetic_slots(xc, edges)  # (K, m)

    def _slots_mixed(xc):
        # per-k rescue: only uncertified ladders take the binary search
        return jnp.where(certs[:, None], arithmetic_slots(xc, edges),
                         searchsorted_slots(xc, edges))

    def _body(chunk_slots):
        def body(acc, args):
            xc, vc = args[0], args[1]
            si = jnp.where(vc, chunk_slots(xc), sent)  # (K, m)
            hi_oh = (si[..., None] // bf == ia).astype(dt)  # (K, m, A)
            lo_oh = (si[..., None] % bf == ib).astype(dt)   # (K, m, B)
            contract = lambda lhs: jnp.einsum(
                "kma,kmb->kab", lhs, lo_oh,
                precision=_EXACT).reshape(kk, -1)[:, :nslots]
            out = [acc[0] + contract(hi_oh).astype(jnp.int32)]
            for i, v in enumerate(args[2:]):
                out.append(acc[i + 1] + contract(hi_oh * v[None, :, None]))
            return tuple(out), None
        return body

    acc0 = (jnp.zeros((kk, nslots), jnp.int32),) + tuple(
        jnp.zeros((kk, nslots), dt) for _ in rows)
    run = lambda cs: jax.lax.scan(_body(cs), acc0, (xp, validc, *vals))[0]
    acc = jax.lax.cond(jnp.all(certs),
                       lambda: run(_slots_arith),
                       lambda: run(_slots_mixed))
    return list(acc)


def _hist_ref(x, edges, rows, *, impl, want_sums):
    """Shared histogram-oracle core: slot assignment (per ``impl``) + the
    per-slot reductions.  ``rows(x)`` builds the value rows to sum (beyond
    the implicit count row); sums are skipped when ``want_sums`` is False
    AND the impl has separate sum cost.  Leading dims of ``x``/``edges``
    broadcast (rows mode: (B, n) x with (B, nbins+1) edges; multi mode:
    (n,) x with (K, nbins+1) edges)."""
    nbins = edges.shape[-1] - 1
    nslots = nbins + 2
    dt = edges.dtype
    if impl == "searchsorted":
        # legacy scatter accumulation: bit-compatible with the historical
        # oracle (sums in data order), the differential reference
        slot = searchsorted_slots(x, edges)
        lead = slot.shape[:-1]
        xb = jnp.broadcast_to(x, slot.shape)
        vals = [jnp.broadcast_to(jnp.asarray(v, dt), slot.shape)
                for v in rows]

        def one(si, *vi):
            cnt = jnp.zeros((nslots,), jnp.int32).at[si].add(1)
            return (cnt,) + tuple(
                jnp.zeros((nslots,), dt).at[si].add(v) for v in vi)

        if lead:
            flat = jax.vmap(one)(
                slot.reshape((-1,) + slot.shape[-1:]),
                *(v.reshape((-1,) + slot.shape[-1:]) for v in vals))
            return [a.reshape(lead + (nslots,)) for a in flat]
        return list(one(slot, *vals))
    if x.ndim == 1 and edges.ndim == 2:
        # shared-x multi mode: one sweep serves every ladder (no (K, n))
        return _hist_multi_shared(x, edges, rows if want_sums else (),
                                  nslots, dt)
    slot = bin_slots(x, edges, impl)
    return _factored_hist(slot, rows if want_sums else (), nslots, dt)


def cp_histogram_ref(x: jax.Array, edges: jax.Array, *,
                     impl: str = "searchsorted", want_sums: bool = True):
    """Oracle for kernels.cp_objective.cp_histogram: ``x`` (n,), realized
    edges ``(nbins+1,)`` (monotone, from :func:`bin_edges`).

    Slot layout in :func:`searchsorted_slots`.  Counts int32, sums in the
    promoted accumulate dtype (f64 stays f64 — the x64-exact path).
    ``impl`` selects the slotting: ``'searchsorted'`` (binary search +
    scatter, the historical reference) or ``'arithmetic'`` (verified
    multiply/floor/clip slots + factored one-hot reduction — bit-identical
    counts, CPU-fast; see :func:`bin_slots`).  ``want_sums=False`` skips
    the per-slot sums on the arithmetic path (plain binned sweeps never
    read them) and returns ``bsum=None``.
    """
    dt = _accum_dtype(x)
    x = x.reshape(-1).astype(dt)
    nbins = edges.shape[-1] - 1
    # no value-changing cast: the engine builds edges at (at least) the
    # promoted dtype, so this astype is an identity
    edges = jnp.asarray(edges, dt).reshape(nbins + 1)
    out = _hist_ref(x, edges, (x,), impl=impl, want_sums=want_sums)
    return out[0], (out[1] if len(out) > 1 else None)


def cp_histogram_batched_ref(x: jax.Array, edges: jax.Array, *,
                             impl: str = "searchsorted",
                             want_sums: bool = True):
    """Oracle for kernels.cp_objective.cp_histogram_batched: ``x`` (B, n),
    per-row edges ``(B, nbins+1)``; returns ``(cnt, bsum)`` of shape
    ``(B, nbins + 2)``."""
    dt = _accum_dtype(x)
    x = x.astype(dt)
    edges = jnp.asarray(edges, dt)
    out = _hist_ref(x, edges, (x,), impl=impl, want_sums=want_sums)
    return out[0], (out[1] if len(out) > 1 else None)


def cp_histogram_multi_ref(x: jax.Array, edges: jax.Array, *,
                           impl: str = "searchsorted",
                           want_sums: bool = True):
    """Oracle for kernels.cp_objective.cp_histogram_multi: one shared ``x``
    (n,), per-pivot edges ``(K, nbins+1)``; returns ``(cnt, bsum)`` of
    shape ``(K, nbins + 2)``."""
    dt = _accum_dtype(x)
    x = x.reshape(-1).astype(dt)
    edges = jnp.asarray(edges, dt)
    out = _hist_ref(x, edges, (x,), impl=impl, want_sums=want_sums)
    return out[0], (out[1] if len(out) > 1 else None)


# ---------------------------------------------------------------------------
# Weighted selection: fused weighted-partials and weighted-histogram oracles
# ---------------------------------------------------------------------------


def _waccum_dtype(x, w):
    # Weighted accumulation promotes BOTH operands (f64 weights on f32 data
    # must accumulate mass in f64 — the x64-exact path mirrors counts).
    return jnp.promote_types(jnp.promote_types(x.dtype, w.dtype),
                             jnp.float32)


def wcp_partials_ref(x: jax.Array, w: jax.Array, y: jax.Array):
    """Oracle for kernels.cp_objective.wcp_partials: six additive partials
    ``(wsum_pos, wsum_neg, w_lt, w_le, n_lt, n_le)`` — weighted objective
    terms, weight masses below/at-or-below the pivot, and the element
    counts (which still drive the cap-based stopping rule)."""
    dt = _waccum_dtype(x, w)
    x = x.reshape(-1).astype(dt)
    w = w.reshape(-1).astype(dt)
    y = jnp.asarray(y, dt)
    d = x - y
    zero = jnp.zeros_like(x)
    wsum_pos = jnp.sum(jnp.where(d > 0, w * d, zero))
    wsum_neg = jnp.sum(jnp.where(d < 0, -w * d, zero))
    w_lt = jnp.sum(jnp.where(d < 0, w, zero))
    w_le = jnp.sum(jnp.where(d <= 0, w, zero))
    n_lt = jnp.sum(d < 0, dtype=jnp.int32)
    n_le = jnp.sum(d <= 0, dtype=jnp.int32)
    return wsum_pos, wsum_neg, w_lt, w_le, n_lt, n_le


def wcp_partials_batched_ref(x: jax.Array, w: jax.Array, y: jax.Array):
    """Oracle for kernels.cp_objective.wcp_partials_batched: ``x``/``w``
    (B, n), ``y`` (B,); returns six (B,) vectors."""
    dt = _waccum_dtype(x, w)
    return jax.vmap(wcp_partials_ref)(x.astype(dt), w.astype(dt),
                                      jnp.asarray(y, dt))


def wcp_partials_multi_ref(x: jax.Array, w: jax.Array, y: jax.Array):
    """Oracle for kernels.cp_objective.wcp_partials_multi: shared ``x``/``w``
    (n,), ``y`` (K,) pivots; returns six (K,) vectors."""
    dt = _waccum_dtype(x, w)
    return jax.vmap(wcp_partials_ref, in_axes=(None, None, 0))(
        x.reshape(-1).astype(dt), w.reshape(-1).astype(dt),
        jnp.asarray(y, dt)
    )


def _whist_ref(x, w, edges, *, impl, want_sums):
    """Weighted histogram core: the mass row ``w`` always rides (it is the
    narrowing signal), ``w*x`` only when ``want_sums`` (the polish
    ingredient).  On the arithmetic path ``want_sums=False`` therefore
    still returns ``(cnt, wcnt, None)``."""
    if impl == "searchsorted":
        out = _hist_ref(x, edges, (w, w * x), impl=impl,
                        want_sums=want_sums)
        return out[0], out[1], out[2]
    nslots = edges.shape[-1] + 1
    rows = (w, w * x) if want_sums else (w,)
    if x.ndim == 1 and edges.ndim == 2:
        # shared-x multi mode: one sweep serves every ladder (no (K, n))
        out = _hist_multi_shared(x, edges, rows, nslots, edges.dtype)
    else:
        slot = bin_slots(x, edges, impl)
        out = _factored_hist(slot, rows, nslots, edges.dtype)
    return out[0], out[1], (out[2] if len(out) > 2 else None)


def wcp_histogram_ref(x: jax.Array, w: jax.Array, edges: jax.Array, *,
                      impl: str = "searchsorted", want_sums: bool = True):
    """Oracle for kernels.cp_objective.wcp_histogram: same slot layout as
    :func:`cp_histogram_ref`, returning ``(cnt, wcnt, wsum)`` — counts,
    per-slot weight mass sum(w_i) and per-slot sum(w_i * x_i).  ``impl``
    as in :func:`cp_histogram_ref`; ``want_sums=False`` skips ``wsum``
    (returned as ``None``) on the arithmetic path — the mass vector
    ``wcnt`` always rides (it IS the weighted narrowing signal)."""
    dt = _waccum_dtype(x, w)
    x = x.reshape(-1).astype(dt)
    w = w.reshape(-1).astype(dt)
    nbins = edges.shape[-1] - 1
    # no value-changing cast: the engine builds edges at (at least) the
    # promoted dtype, so this astype is an identity
    edges = jnp.asarray(edges, dt).reshape(nbins + 1)
    return _whist_ref(x, w, edges, impl=impl, want_sums=want_sums)


def wcp_histogram_batched_ref(x: jax.Array, w: jax.Array,
                              edges: jax.Array, *,
                              impl: str = "searchsorted",
                              want_sums: bool = True):
    """Oracle for kernels.cp_objective.wcp_histogram_batched: ``x``/``w``
    (B, n), per-row edges ``(B, nbins+1)``; outputs ``(B, nbins + 2)``."""
    dt = _waccum_dtype(x, w)
    return _whist_ref(x.astype(dt), jnp.asarray(w, dt),
                      jnp.asarray(edges, dt), impl=impl,
                      want_sums=want_sums)


def wcp_histogram_multi_ref(x: jax.Array, w: jax.Array, edges: jax.Array, *,
                            impl: str = "searchsorted",
                            want_sums: bool = True):
    """Oracle for kernels.cp_objective.wcp_histogram_multi: shared
    ``x``/``w`` (n,), per-pivot edges ``(K, nbins+1)``; outputs
    ``(K, nbins + 2)``."""
    dt = _waccum_dtype(x, w)
    return _whist_ref(x.reshape(-1).astype(dt),
                      jnp.asarray(w, dt).reshape(-1),
                      jnp.asarray(edges, dt), impl=impl,
                      want_sums=want_sums)


# ---------------------------------------------------------------------------
# Segmented selection: per-segment slot assignment + histogram (each element
# binned against its OWN segment's edge ladder — the per-leaf quantile pass)
# ---------------------------------------------------------------------------


def segmented_slots(x: jax.Array, seg: jax.Array,
                    edges: jax.Array) -> jax.Array:
    """Per-element slot within its own segment's ladder:
    ``searchsorted_slots(x_i, edges[seg_i])`` without materializing
    per-element edge rows.

    Branchless binary search over the flattened ``(K, nbins+1)`` edge
    array — ``ceil(log2(nbins+2))`` rounds of (n,)-shaped gathers, so K
    ladders cost no extra memory traffic and no ``(n, nbins)`` or
    ``(K, n)`` intermediate exists.  Comparisons run under the platform's
    fp semantics against the REALIZED edges (the exactness contract), and
    the result is bit-identical to the searchsorted oracle applied
    segment-wise: ``pos = count(edges[seg] < x)`` with NaN forced to the
    top slot (every NaN comparison is false — binary search walks right).
    """
    ne = edges.shape[-1]
    ef = edges.reshape(-1)
    seg = jnp.asarray(seg, jnp.int32)
    base = seg * ne
    pos = jnp.zeros(x.shape, jnp.int32)
    step = 1
    while step * 2 <= ne:
        step *= 2
    # invariant: all edges[seg][:pos] < x; steps p, p/2, .., 1 reach any
    # count in [0, ne] (2p - 1 >= ne)
    while step:
        cand = pos + step
        e = ef[jnp.clip(base + cand - 1, 0, ef.shape[0] - 1)]
        pos = jnp.where((cand <= ne) & (e < x), cand, pos)
        step //= 2
    return jnp.where(jnp.isnan(x), ne, pos).astype(jnp.int32)


def segmented_histogram_ref(x: jax.Array, seg: jax.Array, edges: jax.Array,
                            rows=()):
    """Per-segment histogram in ONE data pass: element ``i`` lands in slot
    ``segmented_slots(x, seg, edges)[i]`` of segment ``seg[i]``'s
    ``(nbins+2,)`` vector.  The flattened slot id ``seg*(nbins+2) + slot``
    feeds the factored one-hot reduction, so all K segment histograms come
    from one chunked sweep.  Returns ``[cnt int32, *sums]``, each
    ``(K, nbins+2)`` (``rows`` as in :func:`_factored_hist`)."""
    kk = edges.shape[0]
    nslots = edges.shape[-1] + 1
    dt = edges.dtype
    x = jnp.asarray(x, dt)
    slot = segmented_slots(x, seg, edges)
    flat = jnp.asarray(seg, jnp.int32) * nslots + slot
    out = _factored_hist(flat, tuple(jnp.asarray(v, dt) for v in rows),
                         kk * nslots, dt)
    return [a.reshape(kk, nslots) for a in out]
