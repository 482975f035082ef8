"""Public kernel API with backend dispatch.

On a TPU the Pallas kernels run compiled (``python chip_smoke.py``
checks each against its oracle there).  On the CPU, where the tests run
with ``JAX_PLATFORMS=cpu``, the default is the jitted jnp oracle and
``use_pallas=True`` runs the kernel body in interpret mode; both are
validated against ``ref.py`` by the test suite, and
``tests/test_tpu_compile.py`` compiles the kernels for a described v5e.

    estimate_entropies(updates, T)          (N, C) -> (N,)
    hics_selection_step(updates, T, lam)    (N, C) -> ((N,), (N, N))
    hics_selection_step_cached(...)         K-row incremental refresh
    cached_feature_step(feats, ...)         K-row refresh, cosine/L2
                                            metric (CS / DivFL)
    gram_row_update(updates, stats, ids)    (K, N) distance strip
                                            (arccos / cosine / l2)
    pairwise_distances(updates, T, lam)     (N, C) -> (N, N)   [Eq. 9]
    gqa_decode_attention(q, k, v, length)   one-token flash decode

Each entry point runs under a ``jax.named_scope`` of its own name, and
each Pallas kernel carries a ``name=`` (``gram_strip``,
``pairwise_gram``, ``fused_row_stats``, ``estimate_entropies``,
``gqa_decode_attention``), so the compiled program's metadata says
which entry point and kernel an operation belongs to.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention_pallas
from repro.kernels.fused_stats import fused_stats_pallas
from repro.kernels.gram_update import (cached_feature_step_pallas,
                                       cached_selection_step_pallas,
                                       gram_row_update_pallas)
from repro.kernels.hetero_entropy import entropy_pallas
from repro.kernels.pairwise import (hics_selection_step_pallas,
                                    pairwise_distance_pallas)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@jax.named_scope("estimate_entropies")
def estimate_entropies(updates: jnp.ndarray, temperature: float,
                       use_pallas: bool | None = None) -> jnp.ndarray:
    """Ĥ over N clients' bias updates; Pallas on TPU, oracle on CPU."""
    use = _on_tpu() if use_pallas is None else use_pallas
    if use:
        return entropy_pallas(updates, temperature,
                              interpret=not _on_tpu())
    return ref.entropy_ref(updates, temperature)


@jax.named_scope("fused_row_stats")
def fused_row_stats(updates: jnp.ndarray, temperature: float,
                    use_pallas: bool | None = None):
    """(Ĥ, |Δb|₂, RMS) per client in one HBM sweep over (N, C)."""
    use = _on_tpu() if use_pallas is None else use_pallas
    if use:
        return fused_stats_pallas(updates, temperature,
                                  interpret=not _on_tpu())
    return ref.fused_stats_ref(updates, temperature)


@jax.named_scope("hics_selection_step")
def hics_selection_step(updates: jnp.ndarray, temperature: float,
                        lam: float = 10.0, normalize: bool = False,
                        gram_in_bf16: bool = False,
                        use_pallas: bool | None = None):
    """The entire pre-cluster selection pipeline in one jitted step:

        (N, C) Δb  ->  (Ĥ (N,), Eq. 9 distance (N, N))

    One pad, one pre-Gram sweep (fused entropy+norm+RMS), then the
    Gram kernel and the arccos tail with no host round trip.  ``normalize=True``
    uses the RMS-normalized estimator (one extra stats sweep on the
    kernel path).  Pallas on TPU, jitted oracle on CPU.
    """
    use = _on_tpu() if use_pallas is None else use_pallas
    if use:
        return hics_selection_step_pallas(
            updates, temperature, lam=lam, normalize=normalize,
            gram_in_bf16=gram_in_bf16, interpret=not _on_tpu())
    return _selection_step_ref_jit(updates, temperature, lam, normalize)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _selection_step_ref_jit(updates, temperature, lam, normalize):
    return ref.selection_step_ref(updates, temperature, lam,
                                  normalize=normalize)


@jax.named_scope("hics_selection_step_cached")
def hics_selection_step_cached(updates: jnp.ndarray, dist: jnp.ndarray,
                               stats: jnp.ndarray, ids: jnp.ndarray,
                               temperature: float, lam: float = 10.0,
                               normalize: bool = False,
                               gram_in_bf16: bool = False,
                               use_pallas: bool | None = None):
    """Incremental HiCS selection step (Alg. 1's K-row replacement):

        (N, C) Δb, cached (dist (N, N), stats (N, 2) = [norm, Ĥ]),
        (K,) refreshed ids  ->  (Ĥ (N,), dist, stats)

    Only the rows/cols of ``ids`` are recomputed and re-symmetrized —
    O(K·N·C) per round instead of the full step's O(N²·C).  The caller
    owns the invariant that every row was refreshed since its Δb row
    last changed (the functional hics selector refreshes the previous
    round's participants at the top of every ``select``, which covers
    the strict select→update alternation all drivers use).  Duplicate
    ids are harmless; K = 0 returns the cache unchanged.  Pallas on
    TPU, jitted oracle on CPU — each path reproduces its from-scratch
    counterpart row-for-row.  ``gram_in_bf16`` only affects the kernel
    path (the CPU oracle stays f32, like ``hics_selection_step``).
    """
    use = _on_tpu() if use_pallas is None else use_pallas
    if use:
        return cached_selection_step_pallas(
            updates, dist, stats, ids, temperature, lam=lam,
            normalize=normalize, gram_in_bf16=gram_in_bf16,
            interpret=not _on_tpu())
    return _cached_step_ref_jit(updates, dist, stats, ids, temperature,
                                lam, normalize)


@functools.partial(jax.jit, static_argnums=(4, 5, 6))
def _cached_step_ref_jit(updates, dist, stats, ids, temperature, lam,
                         normalize):
    return ref.cached_selection_step_ref(updates, dist, stats, ids,
                                         temperature, lam,
                                         normalize=normalize)


@jax.named_scope("gram_row_update")
def gram_row_update(updates: jnp.ndarray, stats: jnp.ndarray,
                    ids: jnp.ndarray, lam: float = 10.0,
                    gram_in_bf16: bool = False,
                    epilogue: str = "arccos",
                    use_pallas: bool | None = None) -> jnp.ndarray:
    """(N, C), (N, 2) current [norm, Ĥ], (K,) ids -> (K, N) distance
    strip — the raw K×N Gram product + epilogue behind the cached
    steps, for callers that manage their own scatter.  ``epilogue``
    picks the distance: "arccos" (Eq. 9, HiCS), "cosine" (CS) or "l2"
    (DivFL).  Pallas (MXU tiles, optional bf16 operands / f32
    accumulation) on TPU; jitted lax fallback on CPU."""
    use = _on_tpu() if use_pallas is None else use_pallas
    if use:
        return gram_row_update_pallas(updates, stats, ids, lam=lam,
                                      gram_in_bf16=gram_in_bf16,
                                      epilogue=epilogue,
                                      interpret=not _on_tpu())
    return _gram_row_update_lax(updates, stats, ids, lam, epilogue)


@functools.partial(jax.jit, static_argnums=(3, 4))
def _gram_row_update_lax(updates, stats, ids, lam, epilogue):
    return ref.distance_strip_ref(updates, stats, ids, lam,
                                  epilogue=epilogue)


@jax.named_scope("cached_feature_step")
def cached_feature_step(feats: jnp.ndarray, dist: jnp.ndarray,
                        stats: jnp.ndarray, ids: jnp.ndarray,
                        metric: str = "cosine",
                        gram_in_bf16: bool = False,
                        use_pallas: bool | None = None):
    """Incremental full-update distance step (the CS/DivFL analogue of
    ``hics_selection_step_cached``):

        (N, F) features, cached (dist (N, N), stats (N, 2) = [norm, 0]),
        (K,) refreshed ids  ->  (dist, stats)

    Only the rows/cols of ``ids`` are recomputed through the strip
    kernel and re-symmetrized — O(K·N·F) per round instead of the
    from-scratch O(N²·F) matrix build.  ``metric`` is the selector's
    own distance ("cosine" for Clustered Sampling, "l2" for DivFL).
    Same caller-owned invariant as the HiCS step: every row must have
    been refreshed since its feature row last changed (the functional
    cs/divfl selectors stale exactly the rows ``update`` writes and
    refresh them at the top of the next ``select``).  Duplicate ids are
    harmless; K = 0 returns the cache unchanged.  Pallas on TPU, jitted
    oracle on CPU.
    """
    use = _on_tpu() if use_pallas is None else use_pallas
    if use:
        return cached_feature_step_pallas(
            feats, dist, stats, ids, metric=metric,
            gram_in_bf16=gram_in_bf16, interpret=not _on_tpu())
    return _cached_feature_step_ref_jit(feats, dist, stats, ids, metric)


@functools.partial(jax.jit, static_argnums=(4,))
def _cached_feature_step_ref_jit(feats, dist, stats, ids, metric):
    return ref.cached_feature_step_ref(feats, dist, stats, ids,
                                       metric=metric)


@jax.named_scope("pairwise_distances")
def pairwise_distances(updates: jnp.ndarray, temperature: float,
                       lam: float = 10.0,
                       use_pallas: bool | None = None) -> jnp.ndarray:
    """Full Eq. 9 matrix: one fused stats sweep + Gram kernel."""
    use = _on_tpu() if use_pallas is None else use_pallas
    if use:
        _, dist = hics_selection_step_pallas(updates, temperature,
                                             lam=lam,
                                             interpret=not _on_tpu())
        return dist
    h = ref.entropy_ref(updates, temperature)
    return ref.pairwise_distance_ref(updates, h, lam)


@jax.named_scope("gqa_decode_attention")
def gqa_decode_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                         length, scale: float | None = None,
                         use_pallas: bool | None = None) -> jnp.ndarray:
    """One-token GQA attention against a (B, S, KV, dh) cache."""
    use = _on_tpu() if use_pallas is None else use_pallas
    if use:
        return decode_attention_pallas(q, k, v, length, scale=scale,
                                       interpret=not _on_tpu())
    return ref.decode_attention_ref(q, k, v, length, scale=scale)
