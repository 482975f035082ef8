"""HiCS-FL (Algorithm 1) as a functional triple + its OO shim.

Rounds with a non-empty coverage pool: random sweep without
replacement (S₀, Alg. 1 lines 14-15).  Afterwards: agglomerative
clustering into M = K groups on the Eq. 9 distance and the two-stage
Eq. 10 sampler, all on-device (``agglomerate_device`` /
``hierarchical_sample_device``), so ``select`` is one jit-compatible
function with no host round trip — the piece that makes the fully
scanned server round loop possible.

Two distance paths feed the clustering:

* ``incremental=True`` (default) — Alg. 1 line 17 replaces only the K
  participants' Δb rows per round, so the state carries a cached
  (N, N) distance + (N, 2) [norm, Ĥ] stats and ``select`` starts by
  refreshing just the rows ``update`` staled
  (``repro.kernels.hics_selection_step_cached``): O(K·N·C) per round.
* ``incremental=False`` — the from-scratch fused device step
  (``repro.kernels.hics_selection_step``): one pre-Gram HBM sweep over
  (N, C) into the MXU-tiled Gram kernel, O(N²·C) per round.
  Kept as the parity oracle (tests/test_incremental_selection.py locks
  the two paths together) and for drivers that mutate Δb out-of-band.

The cache refresh runs at the top of any select with pending staleness
(``state.stale_fill > 0``) — including coverage-sweep rounds — and
covers the whole staled-id ring (``stale_slots`` cohorts' worth, one
by default); refreshing an already-fresh row is idempotent, so both
the strict select→update alternation of the sync drivers and the
buffered-async server's skipped/merged updates keep the cache exact.
(Contract: at most ``stale_slots``·K ids staled between ``select``s —
the ring's capacity.)
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.clustering import (_agglomerate_device, agglomerate_device,
                                   cluster_means_device)
from repro.core.hetero import estimate_entropy
from repro.core.sampling import (anneal_device, coverage_sweep_device,
                                 hierarchical_sample_device)
from repro.core.selectors.base import ClientSelector
from repro.core.selectors.functional import (FunctionalSelector,
                                             Observations, SelectorState,
                                             init_state, mark_seen,
                                             stale_append, stale_clear,
                                             take_key)
from repro.kernels import hics_selection_step, hics_selection_step_cached

REQUIRES = frozenset({"bias_sel"})


def hics_functional(num_clients: int, num_select: int, total_rounds: int,
                    weights=None, temperature: float = 0.0025,
                    lam: float = 10.0, gamma0: float = 4.0,
                    num_clusters: Optional[int] = None,
                    linkage: str = "ward", normalize: bool = False,
                    gram_in_bf16: bool = False, num_classes: int = 1,
                    incremental: bool = True, stale_slots: int = 1,
                    **_kw) -> FunctionalSelector:
    n = int(num_clients)
    k = min(int(num_select), n)
    m = int(num_clusters) if num_clusters else k
    temperature = float(temperature)
    lam, gamma0 = float(lam), float(gamma0)
    tr = float(total_rounds)
    num_classes = max(1, int(num_classes))
    incremental = bool(incremental)
    stale_len = k * max(1, int(stale_slots))

    def init(key) -> SelectorState:
        return init_state(key, n, weights, num_classes=num_classes,
                          dist_cache=incremental,
                          stale_len=stale_len if incremental else 0)

    def select(state: SelectorState, t, key=None):
        state, key = take_key(state, key)

        if incremental:
            # ring refresh of the cached distance/stats (idempotent on
            # fresh rows) — the only Δb-dependent compute of the
            # round.  Skipped entirely when no update staled anything
            # since the last refresh (async ticks without an
            # aggregation, masked empty cohorts).
            def _refresh(_):
                _, d, s = hics_selection_step_cached(
                    state.delta_b, state.dist_cache, state.row_stats,
                    state.stale_ids, temperature, lam=lam,
                    normalize=normalize, gram_in_bf16=gram_in_bf16)
                return d, s

            with jax.named_scope("strip"):
                dist_c, stats_c = jax.lax.cond(
                    state.stale_fill > 0, _refresh,
                    lambda _: (state.dist_cache, state.row_stats), 0)
                state = stale_clear(state._replace(
                    dist_cache=dist_c, row_stats=stats_c))

        def sweep(key):
            with jax.named_scope("sample"):
                ids = coverage_sweep_device(key, state.seen, k)
                return ids, state.seen.at[ids].set(True)

        def clustered(key):
            with jax.named_scope("cluster"):
                if incremental:
                    ent, dist = state.row_stats[:, 1], state.dist_cache
                else:
                    ent, dist = hics_selection_step(
                        state.delta_b, temperature, lam=lam,
                        normalize=normalize, gram_in_bf16=gram_in_bf16)
                # the cache scatter (and the fused kernel) keep the
                # matrix exactly symmetric, so clustering may skip
                # re-symmetrizing
                labels = agglomerate_device(dist, m, linkage=linkage,
                                            precomputed=True)
                means = cluster_means_device(ent, labels, m)
            with jax.named_scope("sample"):
                gamma_t = anneal_device(gamma0, t, tr)
                ids = hierarchical_sample_device(
                    key, labels, means, state.weights, k, gamma_t)
            return ids, state.seen

        ids, seen = jax.lax.cond(state.unseen_count > 0, sweep,
                                 clustered, key)
        state = state._replace(
            seen=seen, unseen_count=jnp.sum(~seen).astype(jnp.int32))
        return ids, state

    def update(state: SelectorState, t, ids, obs: Observations
               ) -> SelectorState:
        if obs.bias_updates is None:
            return state
        db = state.delta_b.at[ids].set(          # Alg. 1 line 17: replace
            jnp.asarray(obs.bias_updates, state.delta_b.dtype))
        state = mark_seen(state._replace(
            delta_b=db, hist_count=state.hist_count + 1), ids)
        if incremental:
            # stale the replaced rows; the next select refreshes them
            state = stale_append(state, ids)
        return state

    def entropies(state: SelectorState) -> jnp.ndarray:
        return estimate_entropy(state.delta_b, temperature,
                                normalize=normalize)

    def diagnostics(state: SelectorState) -> dict:
        # clustering-health observables for the telemetry ``selection``
        # group: re-cluster the cached Eq. 9 distance (incremental path
        # only — from-scratch mode has no resident distance to read)
        # and report cluster sizes, the within-cluster Ĥ RMS spread and
        # how many stale rows the merge loop's min cache repaired.
        ent = state.row_stats[:, 1]
        labels, repairs = _agglomerate_device(
            state.dist_cache, m, linkage=linkage, precomputed=True)
        means = cluster_means_device(ent, labels, m)
        return {
            "cluster_sizes": jnp.bincount(labels, length=m),
            "cluster_repairs": repairs,
            "cluster_ent_spread": jnp.sqrt(
                jnp.mean(jnp.square(ent - means[labels]))),
        }

    return FunctionalSelector("hics", REQUIRES, init, select, update,
                              jit_capable=True, entropies=entropies,
                              diagnostics=diagnostics if incremental
                              else None)


class HiCSFLSelector(ClientSelector):
    """Algorithm 1 — thin shim over :func:`hics_functional`."""

    name = "hics"
    requires = REQUIRES

    def _make_functional(self, **kw) -> FunctionalSelector:
        return hics_functional(**kw)

    @property
    def _delta_b(self) -> jnp.ndarray:
        """Back-compat view of the device-resident Δb buffer (N, C)."""
        return self.state.delta_b
