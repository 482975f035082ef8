"""The functional selector protocol: pytree state, pure transitions.

A selector is a ``FunctionalSelector`` triple

    state = fn.init(key)                       # SelectorState pytree
    ids, state = fn.select(state, t, key)      # pure, jit-compatible
    state = fn.update(state, t, ids, obs)      # pure, jit-compatible

operating on an explicit :class:`SelectorState` pytree.  Every field is
a device array, so a whole federated round (select → vmapped local
update → aggregate → stacked Δb → selector update) jits into one
``round_step`` that ``FederatedServer`` can drive through ``lax.scan``
with zero host transfers — and whole experiments (multi-seed sweeps)
become one ``vmap`` over stacked states.

:class:`Observations` replaces the legacy ``bias_updates=/
full_updates=/losses=`` kwarg soup: the server produces it on-device
and ``update`` consumes whichever fields the selector's ``requires``
declares.  Unused fields stay ``None`` (an empty pytree — the
structure is static per trace).

Shape/staticness contract: client count N, cohort size K, cluster
count M, and the feature widths C/P are fixed at construction
(closures of the triple); hyper-parameters that only scale arithmetic
(γ⁰, T, λ) are plain floats baked into the closure.  The state carries
only per-experiment *data* — Δb buffer, seen-mask/coverage pool,
feature buffer, loss history ring, client weights, PRNG key — which is
exactly what varies across the experiments a ``vmap`` batches.
"""
from __future__ import annotations

from typing import Callable, FrozenSet, NamedTuple, Optional

import jax
import jax.numpy as jnp


class Observations(NamedTuple):
    """What the server computed for the selector this round.

    bias_updates : (K, C) Δb (or bias-free ΔW surrogate) of the round's
                   participants, row-aligned with ``ids`` — HiCS-FL.
    full_updates : (K, P) or (N, P) flattened model updates — CS/DivFL.
    losses       : (N,) current global-model loss per client — pow-d,
                   FedCor.
    """
    bias_updates: Optional[jnp.ndarray] = None
    full_updates: Optional[jnp.ndarray] = None
    losses: Optional[jnp.ndarray] = None


class SelectorState(NamedTuple):
    """One pytree carrying every selector's round-to-round data.

    Selectors use the subset of fields they need; unused array fields
    are allocated with a zero-width trailing axis so the pytree
    structure is uniform and cheap.  The coverage pool is represented
    as (seen mask, unseen count) — an O(N) packed form equivalent to an
    explicit shrinking id list, but scatter/reduce-friendly under jit.
    """
    key: jax.Array            # PRNG key (used when select gets key=None)
    weights: jnp.ndarray      # (N,) normalized p_k
    seen: jnp.ndarray         # (N,) bool — coverage pool complement
    unseen_count: jnp.ndarray  # () int32
    delta_b: jnp.ndarray      # (N, C) device-resident Δb buffer
    feats: jnp.ndarray        # (N, P) full-update buffer
    losses: jnp.ndarray       # (N,) latest loss poll
    loss_hist: jnp.ndarray    # (H, N) loss-history ring (newest last)
    hist_count: jnp.ndarray   # () int32 — observations received
    # --- incremental-selection cache (hics incremental=True; width 0
    # otherwise).  Alg. 1 replaces K Δb rows per round, so the Eq. 9
    # distance and the per-row [norm, Ĥ] stats are cached and only the
    # refreshed rows recomputed (O(K·N·C) vs O(N²·C) per round).
    dist_cache: jnp.ndarray   # (N, N) cached Eq. 9 distance (or (N, 0))
    row_stats: jnp.ndarray    # (N, 2) cached [L2 norm, Ĥ] (or (N, 0))
    # per-client staleness: a ring of the ids whose cached rows
    # `update` wrote since the last refresh.  (L,) int32 with
    # L = stale_slots·K (one slot-cohort by default), or (0,).
    # `stale_fill` counts ids appended since the last refresh — the
    # next `select` refreshes the whole ring iff it is > 0, then
    # resets it (slots beyond the fill hold previously refreshed ids;
    # re-refreshing a fresh row is idempotent, so the over-refresh is
    # harmless).
    stale_ids: jnp.ndarray
    stale_fill: jnp.ndarray   # () int32 — ids appended since last refresh


class FunctionalSelector(NamedTuple):
    """(init, select, update) + metadata; see the module docstring."""
    name: str
    requires: FrozenSet[str]
    init: Callable[[jax.Array], SelectorState]
    select: Callable[..., tuple]     # (state, t, key=None) -> (ids, state)
    update: Callable[..., SelectorState]  # (state, t, ids, obs) -> state
    jit_capable: bool = True
    #: optional (state) -> (N,) Ĥ, for history recording inside the scan
    entropies: Optional[Callable[[SelectorState], jnp.ndarray]] = None
    #: optional (state) -> {"cluster_sizes": (M,), "cluster_ent_spread":
    #: (), "cluster_repairs": ()} — clustering-health observables for
    #: the telemetry ``selection`` group.  Pure/jit-compatible like
    #: ``entropies``.
    diagnostics: Optional[Callable[[SelectorState], dict]] = None
    #: optional observed-full-update-width -> stored-feature-width map.
    #: Selectors that down-project |θ|-sized updates (cs/divfl with
    #: ``proj_dim``) store features narrower than the observations; the
    #: OO shim's lazy buffer growth sizes ``state.feats`` through this.
    feat_width: Optional[Callable[[int], int]] = None


def init_state(key: jax.Array, num_clients: int, weights=None,
               num_classes: int = 0, feat_dim: int = 0,
               hist_len: int = 0, dist_cache: bool = False,
               stale_len: int = 0) -> SelectorState:
    """Allocate a fresh :class:`SelectorState` with the given widths.

    ``dist_cache=True`` sizes the incremental-selection cache — an
    (N, N) distance matrix plus (N, 2) row stats — and ``stale_len``
    the staleness index buffer (the selector's K).  The cache starts at
    zero: every entry is rewritten by a K-row refresh before the first
    clustered selection reads it (a client only leaves the coverage
    pool by participating, which stales — then refreshes — its rows).
    """
    n = int(num_clients)
    w = (jnp.ones(n, jnp.float32) if weights is None
         else jnp.asarray(weights, jnp.float32))
    w = w / jnp.sum(w)
    return SelectorState(
        key=key,
        weights=w,
        seen=jnp.zeros(n, bool),
        unseen_count=jnp.int32(n),
        delta_b=jnp.zeros((n, int(num_classes)), jnp.float32),
        feats=jnp.zeros((n, int(feat_dim)), jnp.float32),
        losses=jnp.zeros(n, jnp.float32),
        loss_hist=jnp.zeros((int(hist_len), n), jnp.float32),
        hist_count=jnp.int32(0),
        dist_cache=jnp.zeros((n, n if dist_cache else 0), jnp.float32),
        row_stats=jnp.zeros((n, 2 if dist_cache else 0), jnp.float32),
        stale_ids=jnp.zeros(int(stale_len), jnp.int32),
        stale_fill=jnp.int32(0),
    )


def state_entropies(fn: FunctionalSelector,
                    state: SelectorState) -> jnp.ndarray:
    """(N,) Ĥ estimate from a selector's state, or a zero-width (0,)
    array when the selector doesn't estimate entropies.

    The single entropy-extraction point shared by the host loop
    (``ClientSelector.estimated_entropies``), the scanned round step,
    the sweep engine, and the telemetry ``selection`` group — all four
    see the same values by construction.  Pure/jit-compatible.
    """
    if fn.entropies is None:
        return jnp.zeros((0,), jnp.float32)
    return fn.entropies(state)


def take_key(state: SelectorState, key: Optional[jax.Array]):
    """Resolve select()'s key argument: an explicit key leaves the
    state's own key untouched (scan path — the server supplies the
    round's key); ``None`` splits the state key (standalone use)."""
    if key is None:
        new_key, sub = jax.random.split(state.key)
        return state._replace(key=new_key), sub
    return state, key


def mark_seen(state: SelectorState, ids: jnp.ndarray) -> SelectorState:
    """Fold ``ids`` into the coverage pool (idempotent)."""
    seen = state.seen.at[ids].set(True)
    return state._replace(
        seen=seen, unseen_count=jnp.sum(~seen).astype(jnp.int32))


def stale_append(state: SelectorState, ids) -> SelectorState:
    """Append ``ids`` to the staled-row ring the next refresh must
    cover.  Shared by every incremental selector (hics on Δb, cs/divfl
    on full-update features).

    The ring is fixed at (L,) with L = ``stale_slots``·K: appends land
    at ``stale_fill mod L`` onward and bump the fill counter, so up to
    ``stale_slots`` cohorts can accumulate between refreshes — the
    buffered-async server's out-of-order arrivals.  The refreshing
    ``select`` covers every slot (slots beyond the fill hold ids whose
    rows are already fresh; re-refreshing them is idempotent) and
    resets the counter via :func:`stale_clear`.  An empty id list
    leaves pending staleness untouched.  More than L ids in ONE call
    cannot be represented (static error); more than L ids ACROSS calls
    wrap around and silently overwrite pending entries — sizing the
    ring for the driver's update cadence is the caller's contract (the
    OO shim fails fast on that hazard host-side).
    """
    ids_arr = jnp.asarray(ids, jnp.int32).reshape(-1)
    kk = ids_arr.shape[0]
    ring = state.stale_ids.shape[0]
    if kk == 0:
        return state
    if kk > ring:
        raise ValueError(
            f"incremental selector's staleness ring holds {ring} ids "
            f"but one update staled {kk}; construct the selector with "
            "a larger stale_slots (the ring must cover the largest "
            "single cohort)")
    pos = jnp.mod(state.stale_fill + jnp.arange(kk, dtype=jnp.int32),
                  ring)
    return state._replace(
        stale_ids=state.stale_ids.at[pos].set(ids_arr),
        stale_fill=state.stale_fill + jnp.int32(kk))


def stale_clear(state: SelectorState) -> SelectorState:
    """Reset the staleness counter after a refresh covered the ring."""
    return state._replace(stale_fill=jnp.int32(0))
