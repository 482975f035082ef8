"""The paper's five baseline selectors as functional triples + shims.

    random : FedProx-style multinomial ∝ p_k without replacement
    pow-d  : sample d candidates ∝ p_k, keep the K largest-loss [8]
    cs     : Clustered Sampling [11] — arccos clustering of FULL updates
    divfl  : DivFL [2] — greedy facility location on update distances
    fedcor : FedCor [28] — GP over loss-history embeddings

All five are expressed in pure jax over the shared ``SelectorState``
pytree, so the OO shims and the functional path draw from the same
transition functions — and ALL of them are ``jit_capable``: the server
scans whole rounds through ``lax.scan`` and the sweep engine vmaps
whole experiments for every selector.

CS and DivFL operate on flattened full-update features (``full_sel`` /
``full_all``) — the O(N²|θ|) similarity cost Table 3 charges them
with.  Two mechanisms keep that family honest AND device-resident:

* ``proj_dim`` bounds the (N, F) feature buffer the state carries: raw
  |θ|-wide updates are sign-hashed into F buckets (feature hashing —
  inner products are preserved in expectation, so cosine/L2 geometry
  survives), which is what makes the scan-carry footprint acceptable
  at production |θ|.  ``proj_dim=None`` stores updates verbatim.
* ``incremental=True`` gives both selectors the K-row distance caching
  HiCS got in PR 4: the state carries a cached (N, N) matrix + (N, 2)
  [norm, 0] row stats, and ``select`` refreshes only the rows the last
  ``update`` wrote (``repro.kernels.cached_feature_step`` — the strip
  kernel with the selector's own cosine/L2 epilogue), O(K·N·F) per
  round instead of O(N²·F).  ``incremental=False`` rebuilds the matrix
  from the feature buffer each round — kept as the parity oracle.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.core.clustering import agglomerate_device
from repro.core.sampling import coverage_sweep_device, weighted_sample_device
from repro.core.selectors.base import ClientSelector
from repro.core.selectors.functional import (FunctionalSelector,
                                             init_state, mark_seen,
                                             stale_append, stale_clear,
                                             take_key)
from repro.kernels import cached_feature_step

_LOG_FLOOR = 1e-30


def _make_projector(proj_dim: Optional[int], proj_seed: int
                    ) -> tuple[Callable, Callable[[int], int]]:
    """(project, feat_width) for the full-update selectors.

    ``project`` maps (..., P) raw flattened updates to the (..., F)
    stored features, F = min(P, proj_dim): a signed feature hash —
    Rademacher signs drawn from ``proj_seed`` (a compile-time constant,
    identical across host/scan/sweep drivers), then contiguous buckets
    summed — so ⟨h(u), h(v)⟩ is an unbiased estimate of ⟨u, v⟩ and the
    cosine/L2 distances the selectors cluster on survive the
    compression.  ``proj_dim=None`` is the identity.  ``feat_width``
    exposes the P -> F map so buffer sizing (server init, OO shim lazy
    growth) agrees with ``project`` without calling it.
    """
    if proj_dim is None:
        return (lambda u: u), (lambda p: p)
    f_cap = int(proj_dim)

    def feat_width(p: int) -> int:
        return min(int(p), f_cap)

    def project(u: jnp.ndarray) -> jnp.ndarray:
        p = u.shape[-1]
        f = feat_width(p)
        if f == p:
            return u
        signs = jax.random.rademacher(
            jax.random.PRNGKey(proj_seed), (p,), jnp.float32)
        chunk = -(-p // f)
        u = u * signs
        u = jnp.pad(u, [(0, 0)] * (u.ndim - 1) + [(0, f * chunk - p)])
        return u.reshape(u.shape[:-1] + (f, chunk)).sum(axis=-1)

    return project, feat_width


# ---------------------------------------------------------------------------
# random
# ---------------------------------------------------------------------------


def random_functional(num_clients: int, num_select: int, total_rounds: int,
                      weights=None, **_kw) -> FunctionalSelector:
    n = int(num_clients)
    k = min(int(num_select), n)

    def init(key):
        return init_state(key, n, weights)

    def select(state, t, key=None):
        state, key = take_key(state, key)
        return weighted_sample_device(key, state.weights, k), state

    def update(state, t, ids, obs):
        return state

    return FunctionalSelector("random", frozenset(), init, select, update)


# ---------------------------------------------------------------------------
# pow-d
# ---------------------------------------------------------------------------


def powd_functional(num_clients: int, num_select: int, total_rounds: int,
                    weights=None, d: Optional[int] = None,
                    **_kw) -> FunctionalSelector:
    n = int(num_clients)
    k = min(int(num_select), n)
    d = n if d is None else min(int(d), n)

    def init(key):
        return init_state(key, n, weights)

    def select(state, t, key=None):
        state, key = take_key(state, key)

        def cold(key):
            return weighted_sample_device(key, state.weights, k)

        def warm(key):
            cand = weighted_sample_device(key, state.weights, d)
            in_cand = jnp.zeros(n, bool).at[cand].set(True)
            masked = jnp.where(in_cand, state.losses, -jnp.inf)
            return jax.lax.top_k(masked, k)[1]

        ids = jax.lax.cond(jnp.any(state.losses != 0), warm, cold, key)
        return ids, state

    def update(state, t, ids, obs):
        if obs.losses is None:
            return state
        return state._replace(losses=jnp.asarray(obs.losses, jnp.float32),
                              hist_count=state.hist_count + 1)

    return FunctionalSelector("pow-d", frozenset({"loss_all"}), init,
                              select, update)


# ---------------------------------------------------------------------------
# cs (Clustered Sampling)
# ---------------------------------------------------------------------------


def cs_functional(num_clients: int, num_select: int, total_rounds: int,
                  weights=None, feat_dim: int = 1,
                  proj_dim: Optional[int] = None, proj_seed: int = 0,
                  incremental: bool = True, stale_slots: int = 1,
                  **_kw) -> FunctionalSelector:
    """Clustered Sampling [11]: ward clustering of the participants'
    full updates under the angular (arccos cosine) distance, one pick
    per cluster ∝ p_k.  ``feat_dim`` is the RAW flattened-update width
    the server observes; ``proj_dim``/``proj_seed`` bound the stored
    features, ``incremental`` enables the K-row distance cache and
    ``stale_slots`` sizes its staled-id ring (see the module
    docstring and ``functional.stale_append``)."""
    n = int(num_clients)
    k = min(int(num_select), n)
    project, feat_width = _make_projector(proj_dim, int(proj_seed))
    f_dim = max(1, feat_width(int(feat_dim)))
    incremental = bool(incremental)
    stale_len = k * max(1, int(stale_slots))

    def init(key):
        return init_state(key, n, weights, feat_dim=f_dim,
                          dist_cache=incremental,
                          stale_len=stale_len if incremental else 0)

    def select(state, t, key=None):
        state, key = take_key(state, key)

        if incremental:
            # ring refresh of the cached angular distance (idempotent
            # on fresh rows) — the only feature-dependent compute;
            # skipped when nothing staled since the last refresh
            def _refresh(_):
                return cached_feature_step(
                    state.feats, state.dist_cache, state.row_stats,
                    state.stale_ids, metric="cosine")

            with jax.named_scope("strip"):
                dist_c, stats_c = jax.lax.cond(
                    state.stale_fill > 0, _refresh,
                    lambda _: (state.dist_cache, state.row_stats), 0)
                state = stale_clear(state._replace(
                    dist_cache=dist_c, row_stats=stats_c))

        def warmup(key):
            # deterministic coverage like Alg. 1's first rounds
            with jax.named_scope("sample"):
                return coverage_sweep_device(key, state.seen, k)

        def clustered(key):
            with jax.named_scope("cluster"):
                if incremental:
                    ang = state.dist_cache
                else:
                    f = state.feats
                    norms = jnp.linalg.norm(f, axis=-1, keepdims=True)
                    unit = f / jnp.clip(norms, 1e-8, None)
                    cos = jnp.clip(unit @ unit.T, -1.0 + 1e-7, 1.0 - 1e-7)
                    ang = jnp.arccos(cos)
                    ang = jnp.where(jnp.eye(n, dtype=bool), 0.0, ang)
                # exactly symmetric by construction — skip re-symmetrizing
                labels = agglomerate_device(ang, k, linkage="ward",
                                            precomputed=True)
            # one client per cluster, ∝ p_k within the cluster
            with jax.named_scope("sample"):
                logw = jnp.log(jnp.clip(state.weights, _LOG_FLOOR, None))
                logit = jnp.where(
                    labels[None, :] == jnp.arange(k)[:, None],
                    logw[None, :], -jnp.inf)
                g = jax.random.gumbel(key, (k, n), jnp.float32)
                return jnp.argmax(logit + g, axis=1).astype(jnp.int32)

        ids = jax.lax.cond(state.unseen_count > 0, warmup, clustered, key)
        return ids, state

    def update(state, t, ids, obs):
        if obs.full_updates is None:
            return state
        feats = state.feats.at[ids].set(
            project(jnp.asarray(obs.full_updates, jnp.float32)))
        state = mark_seen(state._replace(
            feats=feats, hist_count=state.hist_count + 1), ids)
        if incremental:
            state = stale_append(state, ids)
        return state

    return FunctionalSelector("cs", frozenset({"full_sel"}), init, select,
                              update, jit_capable=True,
                              feat_width=feat_width)


# ---------------------------------------------------------------------------
# divfl
# ---------------------------------------------------------------------------


def divfl_functional(num_clients: int, num_select: int, total_rounds: int,
                     weights=None, feat_dim: int = 1,
                     proj_dim: Optional[int] = None, proj_seed: int = 0,
                     refresh: str = "all", incremental: bool = True,
                     stale_slots: int = 1, tie_quant: float = 1e-5,
                     **_kw) -> FunctionalSelector:
    """DivFL [2]: greedy facility location on pairwise L2 distances of
    flattened updates.

    ``refresh`` picks the polling regime:

      "all"      — ideal setting (the Table 3 cost): a one-step
                   gradient from EVERY client each round replaces the
                   whole feature buffer (``requires = full_all``).
                   Every row changes per round, so the K-row cache
                   cannot help — ``incremental`` is ignored and the
                   distance matrix is built from the buffer each round.
      "selected" — practical setting: only the participants' updates
                   refresh their feature rows (``requires =
                   full_sel``), everyone else keeps a stale
                   representation — exactly the K-rows-per-round
                   pattern the distance cache accelerates, O(K·N·F).
                   A coverage sweep polls every client once before the
                   first facility-location round so no distance is ever
                   computed against a never-observed row.

    ``feat_dim`` is the RAW flattened-update width; ``proj_dim``/
    ``proj_seed`` bound the stored features (module docstring);
    ``stale_slots`` sizes the incremental cache's staled-id ring.

    ``tie_quant`` makes the greedy argmax deterministic across
    backends: marginal gains are quantized to ``tie_quant`` × max|gain|
    before the argmax, so floating-point ulp noise (which differs
    between the host loop's per-round XLA programs and the fused
    scan/sweep programs) cannot flip near-ties — and exact ties break
    lexicographically toward the smallest client id (``argmax`` returns
    the first maximum).  ``tie_quant=0`` restores raw-gain argmax.
    """
    n = int(num_clients)
    k = min(int(num_select), n)
    if refresh not in ("all", "selected"):
        raise ValueError(f"refresh must be 'all' or 'selected', "
                         f"got {refresh!r}")
    selected_only = refresh == "selected"
    project, feat_width = _make_projector(proj_dim, int(proj_seed))
    f_dim = max(1, feat_width(int(feat_dim)))
    incremental = bool(incremental) and selected_only
    stale_len = k * max(1, int(stale_slots))
    tie_quant = float(tie_quant)

    def init(key):
        return init_state(key, n, weights, feat_dim=f_dim,
                          dist_cache=incremental,
                          stale_len=stale_len if incremental else 0)

    def select(state, t, key=None):
        state, key = take_key(state, key)

        if incremental:
            def _refresh(_):
                return cached_feature_step(
                    state.feats, state.dist_cache, state.row_stats,
                    state.stale_ids, metric="l2")

            with jax.named_scope("strip"):
                dist_c, stats_c = jax.lax.cond(
                    state.stale_fill > 0, _refresh,
                    lambda _: (state.dist_cache, state.row_stats), 0)
                state = stale_clear(state._replace(
                    dist_cache=dist_c, row_stats=stats_c))

        def cold(key):
            if selected_only:
                # poll everyone once before trusting the distances
                return coverage_sweep_device(key, state.seen, k)
            return weighted_sample_device(key, state.weights, k)

        def warm(key):
            if incremental:
                dist = state.dist_cache
            else:
                g = state.feats
                sq = jnp.sum(g * g, axis=1)
                dist = jnp.sqrt(jnp.clip(
                    sq[:, None] + sq[None, :] - 2.0 * (g @ g.T), 0.0,
                    None))

            # greedy facility location: minimize Σ_i min_{j∈S} dist(i,j)
            def body(i, carry):
                chosen, taken, cover = carry
                gains = jnp.sum(jnp.maximum(cover[None, :] - dist, 0.0),
                                axis=1)
                if tie_quant > 0.0:
                    # quantize so ulp noise can't flip near-ties; exact
                    # ties then break toward the smallest client id
                    scale = jnp.maximum(jnp.max(jnp.abs(gains)),
                                        _LOG_FLOOR) * tie_quant
                    gains = jnp.round(gains / scale)
                j = jnp.argmax(jnp.where(taken, -jnp.inf, gains))
                return (chosen.at[i].set(j.astype(jnp.int32)),
                        taken.at[j].set(True),
                        jnp.minimum(cover, dist[j]))

            chosen, _, _ = jax.lax.fori_loop(
                0, k, body, (jnp.zeros(k, jnp.int32),
                             jnp.zeros(n, bool), jnp.full(n, jnp.inf)))
            return chosen

        warm_ok = (state.unseen_count == 0 if selected_only
                   else state.hist_count > 0)
        with jax.named_scope("sample"):
            ids = jax.lax.cond(warm_ok, warm, cold, key)
        return ids, state

    def update(state, t, ids, obs):
        if obs.full_updates is None:
            return state
        if selected_only:
            # practical setting: participants' rows only (gather before
            # project — hashing all N |θ|-wide rows to keep K is waste)
            raw = jnp.asarray(obs.full_updates, jnp.float32)
            rows = project(raw[ids] if raw.shape[0] == n else raw)
            state = mark_seen(state._replace(
                feats=state.feats.at[ids].set(rows),
                hist_count=state.hist_count + 1), ids)
            if incremental:
                state = stale_append(state, ids)
            return state
        # ideal setting: only a full (N, P) poll refreshes the buffer
        if obs.full_updates.shape[0] != n:
            return state
        return state._replace(
            feats=project(jnp.asarray(obs.full_updates, jnp.float32)),
            hist_count=state.hist_count + 1)

    requires = frozenset({"full_sel" if selected_only else "full_all"})
    return FunctionalSelector("divfl", requires, init, select, update,
                              jit_capable=True, feat_width=feat_width)


# ---------------------------------------------------------------------------
# fedcor
# ---------------------------------------------------------------------------


def fedcor_functional(num_clients: int, num_select: int, total_rounds: int,
                      weights=None, warmup: int = 10, beta: float = 0.9,
                      length_scale: float = 1.0, hist_len: int = 8,
                      **_kw) -> FunctionalSelector:
    n = int(num_clients)
    k = min(int(num_select), n)
    warmup, beta, ls = int(warmup), float(beta), float(length_scale)
    h_len = int(hist_len)

    def init(key):
        return init_state(key, n, weights, hist_len=h_len)

    def select(state, t, key=None):
        state, key = take_key(state, key)

        def cold(key):
            return weighted_sample_device(key, state.weights, k)

        def warm(key):
            # standardized loss-history embedding over the valid ring
            x = state.loss_hist.T                      # (N, H), newest last
            valid = (jnp.arange(h_len)
                     >= h_len - jnp.minimum(state.hist_count, h_len))
            cnt = jnp.maximum(jnp.sum(valid.astype(jnp.float32)), 1.0)
            mu = jnp.sum(x * valid, axis=1, keepdims=True) / cnt
            var = jnp.sum(jnp.square((x - mu) * valid), axis=1,
                          keepdims=True) / cnt
            xs = (x - mu) / (jnp.sqrt(var) + 1e-8) * valid
            d2 = jnp.sum(jnp.square(xs[:, None, :] - xs[None, :, :]), -1)
            kmat = jnp.exp(-d2 / (2.0 * ls * ls))
            w_t = jnp.power(beta, jnp.maximum(t - warmup, 0))
            kmat = w_t * kmat + (1.0 - w_t) * jnp.eye(n)

            # greedy max variance-reduction weighted by current losses
            def body(i, carry):
                chosen, taken, var_d, cov = carry
                score = jnp.where(taken, -jnp.inf,
                                  var_d * (1.0 + state.losses))
                j = jnp.argmax(score)
                cj = cov[:, j]
                denom = cov[j, j] + 1e-8
                return (chosen.at[i].set(j.astype(jnp.int32)),
                        taken.at[j].set(True),
                        var_d - cj * cj / denom,
                        cov - jnp.outer(cj, cj) / denom)

            chosen, _, _, _ = jax.lax.fori_loop(
                0, k, body, (jnp.zeros(k, jnp.int32), jnp.zeros(n, bool),
                             jnp.diagonal(kmat), kmat))
            return chosen

        ids = jax.lax.cond((t >= warmup) & (state.hist_count >= 2),
                           warm, cold, key)
        return ids, state

    def update(state, t, ids, obs):
        if obs.losses is None:
            return state
        losses = jnp.asarray(obs.losses, jnp.float32)
        hist = jnp.roll(state.loss_hist, -1, axis=0).at[-1].set(losses)
        return state._replace(losses=losses, loss_hist=hist,
                              hist_count=state.hist_count + 1)

    return FunctionalSelector("fedcor", frozenset({"loss_all"}), init,
                              select, update)


# ---------------------------------------------------------------------------
# OO shims
# ---------------------------------------------------------------------------


class RandomSelector(ClientSelector):
    """FedProx-style multinomial sampling ∝ p_k, without replacement."""
    name = "random"
    requires = frozenset()

    def _make_functional(self, **kw):
        return random_functional(**kw)


class PowerOfChoiceSelector(ClientSelector):
    """pow-d [8], ideal setting (App. A.1.2): d = N — the server asks
    *all* clients for their current local loss each round."""
    name = "pow-d"
    requires = frozenset({"loss_all"})

    def _make_functional(self, **kw):
        return powd_functional(**kw)


class ClusteredSamplingSelector(ClientSelector):
    """Clustered Sampling [11] (Alg. 2 flavour) on *full* updates —
    the O(N²|θ|) similarity cost Table 3 charges it with.  The K-row
    distance cache (``incremental=True``, default) amortizes that to
    O(K·N·F) per round; ``proj_dim`` bounds F."""
    name = "cs"
    requires = frozenset({"full_sel"})

    def _make_functional(self, **kw):
        return cs_functional(**kw)


class DivFLSelector(ClientSelector):
    """DivFL [2]: greedy facility-location submodular maximization;
    ideal setting (``refresh="all"``) = 1-step gradients from all
    clients each round; ``refresh="selected"`` polls participants only
    and rides the K-row distance cache."""
    name = "divfl"
    requires = frozenset({"full_all"})

    def _make_functional(self, **kw):
        return divfl_functional(**kw)


class FedCorSelector(ClientSelector):
    """FedCor [28]: GP over running loss-history embeddings with
    annealing β; warm-up polls all clients' losses (Table 3 cost)."""
    name = "fedcor"
    requires = frozenset({"loss_all"})

    def _make_functional(self, **kw):
        return fedcor_functional(**kw)
