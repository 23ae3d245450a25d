"""jit'd dispatch wrappers around the Pallas kernels.

On TPU the Pallas path is used; elsewhere the pure-jnp oracle is
numerically identical and XLA fuses it into one pass, so it is the
default.  ``backend='pallas_interpret'`` forces the kernel body through the
Pallas interpreter (Python emulation) — used by the tests to validate the
TPU kernel logic on CPU.

f64 policy: the Pallas kernels accumulate in f32 (``x_ref[...].astype(
jnp.float32)`` — TPUs have no f64 VPU), so under x64 their counts would be
computed at f32 resolution: two f64 values straddling a pivot can collapse
onto it after the downcast, and the exactness certificates would lie.  Every
dispatcher therefore reroutes f64 inputs to the dtype-preserving jnp oracle,
even when ``backend='pallas'`` was requested.  ``pallas_interpret`` is NOT
rerouted — it exists precisely to emulate the TPU kernel (including its f32
accumulation) on CPU.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import cp_objective, ref


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _resolve_backend(backend: str | None, x: jax.Array) -> str:
    if backend is None:
        backend = "pallas" if _on_tpu() else "jnp"
    if backend == "pallas" and x.dtype == jnp.float64:
        # dtype-preserving variant: the f32-accumulating kernel would lose
        # sub-f32 resolution (see module docstring)
        backend = "jnp"
    return backend


def _resolve_impl(impl: str | None) -> str:
    """jnp-path slotting implementation for the histogram dispatchers.

    ``None`` resolves to ``'arithmetic'`` — the verified multiply/floor/clip
    slotting (bit-identical to the searchsorted oracle by construction, see
    ``ref.bin_slots``) whose factored one-hot reduction is what makes the
    CPU histogram pass competitive with a fused FG pass.  ``'searchsorted'``
    stays selectable for differential testing.  The Pallas kernels compare
    each tile against the resident edges (neither slotting applies), so
    ``impl`` only routes the jnp-oracle path — including the f64 reroute.
    """
    if impl is None:
        return "arithmetic"
    from repro.kernels.ref import BIN_IMPLS

    if impl not in BIN_IMPLS:
        raise ValueError(f"unknown binning impl {impl!r}; one of "
                         f"{BIN_IMPLS}")
    return impl


def fused_partials(x, y, *, backend: str | None = None):
    """(sum_pos, sum_neg, n_lt, n_le) for pivot y — kernel-accelerated."""
    backend = _resolve_backend(backend, x)
    if backend == "pallas":
        return cp_objective.cp_partials(x, y)
    if backend == "pallas_interpret":
        return cp_objective.cp_partials(x, y, interpret=True)
    if backend == "jnp":
        return ref.cp_partials_ref(x, y)
    raise ValueError(f"unknown backend {backend!r}")


def fused_partials_batched(x, y, *, backend: str | None = None):
    """Row-wise variant over (B, n) problems."""
    backend = _resolve_backend(backend, x)
    if backend == "pallas":
        return cp_objective.cp_partials_batched(x, y)
    if backend == "pallas_interpret":
        return cp_objective.cp_partials_batched(x, y, interpret=True)
    if backend == "jnp":
        return ref.cp_partials_batched_ref(x, y)
    raise ValueError(f"unknown backend {backend!r}")


def fused_partials_multi(x, y, *, backend: str | None = None):
    """Shared-x multi-pivot variant: ``x`` (n,), ``y`` (K,) pivots.

    On TPU the multi-pivot kernel reads each x tile into VMEM once and
    emits partials for every live pivot (K× less HBM traffic than K
    independent sweeps).
    """
    backend = _resolve_backend(backend, x)
    if backend == "pallas":
        return cp_objective.cp_partials_multi(x, y)
    if backend == "pallas_interpret":
        return cp_objective.cp_partials_multi(x, y, interpret=True)
    if backend == "jnp":
        return ref.cp_partials_multi_ref(x, y)
    raise ValueError(f"unknown backend {backend!r}")


def _resolve_backend_weighted(backend: str | None, x: jax.Array,
                              w: jax.Array) -> str:
    """Weighted variant of :func:`_resolve_backend`: the f64 reroute fires
    when EITHER operand is f64 (f64 weights on f32 data must accumulate
    mass at full precision or the weighted certificates lie)."""
    if backend is None:
        backend = "pallas" if _on_tpu() else "jnp"
    if backend == "pallas" and (x.dtype == jnp.float64
                                or w.dtype == jnp.float64):
        backend = "jnp"
    return backend


def fused_weighted_partials(x, w, y, *, backend: str | None = None):
    """Six weighted partials ``(wsum_pos, wsum_neg, w_lt, w_le, n_lt,
    n_le)`` for pivot ``y`` — kernel-accelerated."""
    backend = _resolve_backend_weighted(backend, x, w)
    if backend == "pallas":
        return cp_objective.wcp_partials(x, w, y)
    if backend == "pallas_interpret":
        return cp_objective.wcp_partials(x, w, y, interpret=True)
    if backend == "jnp":
        return ref.wcp_partials_ref(x, w, y)
    raise ValueError(f"unknown backend {backend!r}")


def fused_weighted_partials_batched(x, w, y, *, backend: str | None = None):
    """Row-wise weighted variant over (B, n) problems."""
    backend = _resolve_backend_weighted(backend, x, w)
    if backend == "pallas":
        return cp_objective.wcp_partials_batched(x, w, y)
    if backend == "pallas_interpret":
        return cp_objective.wcp_partials_batched(x, w, y, interpret=True)
    if backend == "jnp":
        return ref.wcp_partials_batched_ref(x, w, y)
    raise ValueError(f"unknown backend {backend!r}")


def fused_weighted_partials_multi(x, w, y, *, backend: str | None = None):
    """Shared-x weighted multi-pivot variant: ``x``/``w`` (n,), ``y`` (K,)."""
    backend = _resolve_backend_weighted(backend, x, w)
    if backend == "pallas":
        return cp_objective.wcp_partials_multi(x, w, y)
    if backend == "pallas_interpret":
        return cp_objective.wcp_partials_multi(x, w, y, interpret=True)
    if backend == "jnp":
        return ref.wcp_partials_multi_ref(x, w, y)
    raise ValueError(f"unknown backend {backend!r}")


def fused_weighted_histogram(x, w, edges, *, backend: str | None = None,
                             impl: str | None = None,
                             want_sums: bool = True):
    """Weighted binned pass: ``(cnt, wcnt, wsum)`` per bracket sub-interval
    (slot weight mass next to the count — the weighted narrowing signal).

    ``impl`` selects the jnp-path slotting (see :func:`_resolve_impl`);
    ``want_sums=False`` skips the per-slot ``sum(w*x)`` on every backend
    (only the polish reads it) — the kernels drop the accumulator and its
    HBM writeback, the jnp arithmetic path the extra value row."""
    backend = _resolve_backend_weighted(backend, x, w)
    if backend == "pallas":
        return cp_objective.wcp_histogram(x, w, edges, want_sums=want_sums)
    if backend == "pallas_interpret":
        return cp_objective.wcp_histogram(x, w, edges, interpret=True,
                                          want_sums=want_sums)
    if backend == "jnp":
        return ref.wcp_histogram_ref(x, w, edges, impl=_resolve_impl(impl),
                                     want_sums=want_sums)
    raise ValueError(f"unknown backend {backend!r}")


def fused_weighted_histogram_batched(x, w, edges, *,
                                     backend: str | None = None,
                                     impl: str | None = None,
                                     want_sums: bool = True):
    """Row-wise weighted binned pass: ``x``/``w`` (B, n), per-row edges
    ``(B, nbins+1)``."""
    backend = _resolve_backend_weighted(backend, x, w)
    if backend == "pallas":
        return cp_objective.wcp_histogram_batched(x, w, edges,
                                                   want_sums=want_sums)
    if backend == "pallas_interpret":
        return cp_objective.wcp_histogram_batched(x, w, edges,
                                                   interpret=True,
                                                   want_sums=want_sums)
    if backend == "jnp":
        return ref.wcp_histogram_batched_ref(x, w, edges,
                                             impl=_resolve_impl(impl),
                                             want_sums=want_sums)
    raise ValueError(f"unknown backend {backend!r}")


def fused_weighted_histogram_multi(x, w, edges, *,
                                   backend: str | None = None,
                                   impl: str | None = None,
                                   want_sums: bool = True):
    """Shared-x weighted multi-bracket binned pass: ``x``/``w`` (n,),
    per-pivot edges ``(K, nbins+1)``."""
    backend = _resolve_backend_weighted(backend, x, w)
    if backend == "pallas":
        return cp_objective.wcp_histogram_multi(x, w, edges,
                                                 want_sums=want_sums)
    if backend == "pallas_interpret":
        return cp_objective.wcp_histogram_multi(x, w, edges, interpret=True,
                                                 want_sums=want_sums)
    if backend == "jnp":
        return ref.wcp_histogram_multi_ref(x, w, edges,
                                           impl=_resolve_impl(impl),
                                           want_sums=want_sums)
    raise ValueError(f"unknown backend {backend!r}")


def fused_histogram(x, edges, *, backend: str | None = None,
                    impl: str | None = None, want_sums: bool = True):
    """Binned data pass: (count, sum) per bracket sub-interval.

    ``x`` (n,), realized bracket edges ``(nbins+1,)`` built ONCE by the
    caller via ``kernels.ref.bin_edges`` (the exactness contract: every
    consumer compares against the same edge array, nobody recomputes edge
    arithmetic — the arithmetic slotting's candidate is verified against
    that same array, see ``ref.bin_slots``).  Returns ``(cnt, bsum)`` of
    shape ``(nbins + 2,)`` (slot layout in
    ``kernels.ref.searchsorted_slots``).  One sweep buys log2(nbins)
    bisection-equivalents of bracket narrowing.  ``want_sums=False`` skips
    ``bsum`` (returns ``None``) on every backend — plain binned
    sweeps never read it, only the polish does.
    """
    backend = _resolve_backend(backend, x)
    if backend == "pallas":
        return cp_objective.cp_histogram(x, edges, want_sums=want_sums)
    if backend == "pallas_interpret":
        return cp_objective.cp_histogram(x, edges, interpret=True,
                                         want_sums=want_sums)
    if backend == "jnp":
        return ref.cp_histogram_ref(x, edges, impl=_resolve_impl(impl),
                                    want_sums=want_sums)
    raise ValueError(f"unknown backend {backend!r}")


def fused_histogram_batched(x, edges, *, backend: str | None = None,
                            impl: str | None = None,
                            want_sums: bool = True):
    """Row-wise binned pass: ``x`` (B, n), per-row edges ``(B, nbins+1)``."""
    backend = _resolve_backend(backend, x)
    if backend == "pallas":
        return cp_objective.cp_histogram_batched(x, edges,
                                                  want_sums=want_sums)
    if backend == "pallas_interpret":
        return cp_objective.cp_histogram_batched(x, edges, interpret=True,
                                                  want_sums=want_sums)
    if backend == "jnp":
        return ref.cp_histogram_batched_ref(x, edges,
                                            impl=_resolve_impl(impl),
                                            want_sums=want_sums)
    raise ValueError(f"unknown backend {backend!r}")


def fused_histogram_multi(x, edges, *, backend: str | None = None,
                          impl: str | None = None, want_sums: bool = True):
    """Shared-x multi-bracket binned pass: ``x`` (n,), per-pivot edges
    ``(K, nbins+1)``.

    On TPU each x tile is read into VMEM once for all K live brackets,
    exactly like the multi-pivot FG kernel.
    """
    backend = _resolve_backend(backend, x)
    if backend == "pallas":
        return cp_objective.cp_histogram_multi(x, edges,
                                                want_sums=want_sums)
    if backend == "pallas_interpret":
        return cp_objective.cp_histogram_multi(x, edges, interpret=True,
                                                want_sums=want_sums)
    if backend == "jnp":
        return ref.cp_histogram_multi_ref(x, edges,
                                          impl=_resolve_impl(impl),
                                          want_sums=want_sums)
    raise ValueError(f"unknown backend {backend!r}")
