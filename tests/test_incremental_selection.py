"""Incremental selection parity battery.

The cached K-row path (``hics_selection_step_cached`` + the
``dist_cache``/``row_stats``/``stale_ids`` state fields) must be
indistinguishable from from-scratch recomputation everywhere it can be
observed: the refreshed matrix itself (property test over random
shapes/index sets, both backends, bf16 included), the cluster labels it
feeds, the participant sets of whole federated runs (host loop, scanned
loop, vmapped sweep — ≥50 rounds), and under availability masking
(masked-out clients never poison cached rows; no NaNs leak into
entropies or sampling weights).
"""
import dataclasses
import functools

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

import jax
import jax.numpy as jnp

from repro.core import Observations, agglomerate_device, make_functional
from repro.core.selectors.functional import SelectorState
from repro.data import SyntheticSpec
from repro.fed import ExperimentSpec, LocalSpec, build
from repro.kernels import (gram_row_update, hics_selection_step,
                           hics_selection_step_cached)
from repro.scenarios import (SweepSpec, availability_mask, build_pair,
                             get_scenario, masked_select,
                             run_host_reference, seed_keychain)

T_SOFT, LAM = 0.0025, 10.0


def _fresh_cache(x, normalize=False, use_pallas=False):
    """Build a valid cache by refreshing ALL rows from the zero cache."""
    n = x.shape[0]
    _, dist, stats = hics_selection_step_cached(
        x, jnp.zeros((n, n)), jnp.zeros((n, 2)),
        jnp.arange(n, dtype=jnp.int32), T_SOFT, lam=LAM,
        normalize=normalize, use_pallas=use_pallas)
    return dist, stats


# ---------------------------------------------------------------------------
# property test: incremental == full recompute, labels identical
# ---------------------------------------------------------------------------


@settings(deadline=None, max_examples=25)
@given(st.integers(4, 32), st.integers(2, 40), st.integers(0, 40),
       st.booleans(), st.integers(0, 2**31 - 1))
def test_incremental_matches_full_recompute(n, c, k, normalize, seed):
    """Random (N, C, K) and random replacement index sets — duplicates
    included, K clipped into [0, N] — leave the cached matrix within fp
    tolerance of from-scratch recompute, with identical cluster labels
    and exact symmetry."""
    k = min(k, n)
    r = np.random.default_rng(seed)
    x0 = jnp.asarray(r.normal(size=(n, c)) * 0.02, jnp.float32)
    dist, stats = _fresh_cache(x0, normalize=normalize)
    # two successive replacement rounds (drift must not accumulate)
    x = x0
    for _ in range(2):
        ids = jnp.asarray(r.integers(0, n, size=k), jnp.int32)
        rows = jnp.asarray(r.normal(size=(k, c)) * 0.02, jnp.float32)
        x = x.at[ids].set(rows)
        ent, dist, stats = hics_selection_step_cached(
            x, dist, stats, ids, T_SOFT, lam=LAM, normalize=normalize,
            use_pallas=False)
    ent_f, dist_f = hics_selection_step(x, T_SOFT, lam=LAM,
                                        normalize=normalize,
                                        use_pallas=False)
    np.testing.assert_allclose(np.asarray(dist), np.asarray(dist_f),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(ent), np.asarray(ent_f),
                               atol=1e-6)
    d = np.asarray(dist)
    np.testing.assert_array_equal(d, d.T)          # exactly symmetric
    np.testing.assert_array_equal(np.diag(d), 0.0)
    m = max(1, min(4, n - 1))
    lab_c = np.asarray(agglomerate_device(dist, m, precomputed=True))
    lab_f = np.asarray(agglomerate_device(dist_f, m))
    np.testing.assert_array_equal(lab_c, lab_f)


def test_k_equals_zero_returns_cache_unchanged(rng):
    x = jnp.asarray(rng.normal(size=(10, 6)) * 0.02, jnp.float32)
    dist, stats = _fresh_cache(x)
    ent, d2, s2 = hics_selection_step_cached(
        x, dist, stats, jnp.zeros(0, jnp.int32), T_SOFT, lam=LAM,
        use_pallas=False)
    np.testing.assert_array_equal(np.asarray(d2), np.asarray(dist))
    np.testing.assert_array_equal(np.asarray(s2), np.asarray(stats))
    np.testing.assert_array_equal(np.asarray(ent),
                                  np.asarray(stats[:, 1]))


def test_k_equals_n_equals_full_step(rng):
    """Replacing every row IS the from-scratch step (fp tolerance)."""
    x = jnp.asarray(rng.normal(size=(17, 9)) * 0.02, jnp.float32)
    ent, dist, _ = hics_selection_step_cached(
        x, jnp.zeros((17, 17)), jnp.zeros((17, 2)),
        jnp.arange(17, dtype=jnp.int32), T_SOFT, lam=LAM,
        use_pallas=False)
    ent_f, dist_f = hics_selection_step(x, T_SOFT, lam=LAM,
                                        use_pallas=False)
    np.testing.assert_allclose(np.asarray(dist), np.asarray(dist_f),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(ent), np.asarray(ent_f),
                               atol=1e-6)


def test_duplicate_ids_are_harmless(rng):
    x0 = jnp.asarray(rng.normal(size=(12, 5)) * 0.02, jnp.float32)
    dist, stats = _fresh_cache(x0)
    rows = jnp.asarray(rng.normal(size=(4, 5)) * 0.02, jnp.float32)
    dup = jnp.asarray([3, 7, 3, 3], jnp.int32)
    x1 = x0.at[dup].set(rows)      # scatter resolves the duplicates
    _, d_dup, _ = hics_selection_step_cached(x1, dist, stats, dup,
                                             T_SOFT, lam=LAM,
                                             use_pallas=False)
    _, d_full = hics_selection_step(x1, T_SOFT, lam=LAM,
                                    use_pallas=False)
    np.testing.assert_allclose(np.asarray(d_dup), np.asarray(d_full),
                               atol=1e-5)


@pytest.mark.parametrize("gram_in_bf16", [False, True])
def test_pallas_cached_matches_pallas_full(rng, gram_in_bf16):
    """Kernel path (interpret mode), f32 and bf16-Gram variants: the
    cached strip kernel agrees with the full fused kernel."""
    n, c, k = 20, 260, 6
    x0 = jnp.asarray(rng.normal(size=(n, c)) * 0.02, jnp.float32)
    dist, stats = _fresh_cache(x0, use_pallas=True)
    ids = jnp.asarray(rng.integers(0, n, size=k), jnp.int32)
    x1 = x0.at[ids].set(jnp.asarray(rng.normal(size=(k, c)) * 0.02,
                                    jnp.float32))
    ent, d_c, s_c = hics_selection_step_cached(
        x1, dist, stats, ids, T_SOFT, lam=LAM,
        gram_in_bf16=gram_in_bf16, use_pallas=True)
    ent_f, d_f = hics_selection_step(x1, T_SOFT, lam=LAM,
                                     gram_in_bf16=gram_in_bf16,
                                     use_pallas=True)
    tol = 1e-4 if not gram_in_bf16 else 3e-2
    np.testing.assert_allclose(np.asarray(d_c), np.asarray(d_f),
                               atol=tol, rtol=tol)
    np.testing.assert_allclose(np.asarray(ent), np.asarray(ent_f),
                               atol=1e-4)
    m = 4
    np.testing.assert_array_equal(
        np.asarray(agglomerate_device(d_c, m, precomputed=True)),
        np.asarray(agglomerate_device(d_f, m)))


def test_gram_row_update_strip_matches_cache_rows(rng):
    """The raw strip op equals the rows the cached step writes."""
    n, c, k = 15, 33, 5
    x = jnp.asarray(rng.normal(size=(n, c)) * 0.02, jnp.float32)
    dist, stats = _fresh_cache(x)
    ids = jnp.asarray(rng.choice(n, size=k, replace=False), jnp.int32)
    strip = gram_row_update(x, stats, ids, lam=LAM, use_pallas=False)
    assert strip.shape == (k, n)
    np.testing.assert_allclose(np.asarray(strip),
                               np.asarray(dist[ids]), atol=1e-6)
    strip_p = gram_row_update(x, stats, ids, lam=LAM, use_pallas=True)
    np.testing.assert_allclose(np.asarray(strip_p), np.asarray(strip),
                               atol=1e-4)


# ---------------------------------------------------------------------------
# selector-level parity: incremental triple == from-scratch triple
# ---------------------------------------------------------------------------


def _drive(fn, t_max, n, c, seed):
    r = np.random.default_rng(seed)
    key = jax.random.PRNGKey(seed)
    key, k0 = jax.random.split(key)
    state = fn.init(k0)
    picks = []
    for t in range(t_max):
        key, kt = jax.random.split(key)
        ids, state = fn.select(state, t, kt)
        picks.append(np.asarray(ids).tolist())
        obs = Observations(bias_updates=jnp.asarray(
            r.normal(size=(ids.shape[0], c)) * 0.02, jnp.float32))
        state = fn.update(state, t, ids, obs)
    return picks, state


@settings(deadline=None, max_examples=8)
@given(st.integers(6, 20), st.integers(1, 5), st.integers(2, 12),
       st.integers(0, 2**31 - 1))
def test_functional_triple_parity_shape_sweep(n, k, c, seed):
    """Hypothesis sweep: the incremental and from-scratch selectors
    pick identical participant sets from the same key/observation
    chain (the obs chain is identical because the picks are)."""
    k = min(k, n)
    kw = dict(num_clients=n, num_select=k, total_rounds=12,
              num_classes=c)
    fn_inc = make_functional("hics", incremental=True, **kw)
    fn_full = make_functional("hics", incremental=False, **kw)
    p_inc, s_inc = _drive(fn_inc, 12, n, c, seed % 9973)
    p_full, _ = _drive(fn_full, 12, n, c, seed % 9973)
    assert p_inc == p_full
    # the incremental state really carries the cache
    assert s_inc.dist_cache.shape == (n, n)
    assert s_inc.row_stats.shape == (n, 2)
    assert s_inc.stale_ids.shape == (k,)


def test_from_scratch_state_skips_cache_memory():
    fn = make_functional("hics", num_clients=8, num_select=2,
                         total_rounds=5, num_classes=4,
                         incremental=False)
    state = fn.init(jax.random.PRNGKey(0))
    assert state.dist_cache.shape == (8, 0)
    assert state.row_stats.shape == (8, 0)
    assert state.stale_ids.shape == (0,)


# ---------------------------------------------------------------------------
# long-horizon drift: 50 rounds through host / scanned / sweep loops
# ---------------------------------------------------------------------------

ROUNDS = 50


def _spec(incremental, jit_rounds):
    return ExperimentSpec(
        arch="paper-mlp", num_clients=12, num_select=3, rounds=ROUNDS,
        alphas=(0.05, 5.0), selector="hics",
        selector_kw={"incremental": incremental},
        local=LocalSpec(algo="fedavg", optimizer="sgd", lr=0.1,
                        epochs=1, batch_size=32),
        samples_train=400, samples_test=120, eval_every=10 ** 6,
        seed=0, jit_rounds=jit_rounds)


@pytest.fixture(scope="module")
def host_runs():
    inc, _ = build(_spec(True, False))
    full, _ = build(_spec(False, False))
    return inc.run(), full.run()


def test_host_loop_50_round_drift(host_runs):
    """Acceptance: 50 host-loop rounds of incremental HiCS produce
    participant sets identical to the from-scratch selector."""
    h_inc, h_full = host_runs
    assert len(h_inc["selected"]) == ROUNDS
    assert h_inc["selected"] == h_full["selected"]
    np.testing.assert_allclose(h_inc["train_loss"], h_full["train_loss"],
                               atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(h_inc["bias_entropy"][-1]),
        np.asarray(h_full["bias_entropy"][-1]), atol=1e-5)


def test_scanned_loop_50_round_drift_single_compile(host_runs):
    """The scanned (jit_rounds=True) incremental run matches the host
    loops round-for-round AND its cached round_step traces exactly
    once across all 50 rounds."""
    h_inc, _ = host_runs
    server, _ = build(_spec(True, True))
    traces = []
    step = server._make_round_step()

    def counting(carry, xs):
        traces.append(1)
        return step(carry, xs)

    server._round_step = counting
    h_scan = server.run()
    assert h_scan["selected"] == h_inc["selected"]
    assert len(traces) == 1, f"round_step traced {len(traces)} times"
    # scan leaves a live, fully-refreshed cache behind
    state = server.selector.state
    assert np.isfinite(np.asarray(state.dist_cache)).all()
    assert np.isfinite(np.asarray(state.row_stats)).all()


SWEEP = SweepSpec(
    scenarios=("dir_mild",), selectors=("hics",), seeds=(0, 1),
    num_clients=10, num_select=3, rounds=ROUNDS,
    samples_train=400, samples_test=120,
    data=SyntheticSpec(dim=16, rank=2, noise=0.5),
    local=LocalSpec(algo="fedavg", optimizer="sgd", lr=0.1, epochs=1,
                    batch_size=32))


def test_vmapped_sweep_50_round_drift():
    """The cache rides the vmapped seed axis: per-seed participant
    sets of the incremental sweep equal the from-scratch sweep AND the
    host-loop oracle over 50 rounds."""
    spec_inc = dataclasses.replace(
        SWEEP, selector_kw={"incremental": True})
    spec_full = dataclasses.replace(
        SWEEP, selector_kw={"incremental": False})
    pair_inc = build_pair(spec_inc, "dir_mild", "hics")
    pair_full = build_pair(spec_full, "dir_mild", "hics")
    assert pair_inc.sstate0.dist_cache.shape == (2, 10, 10)  # seed axis
    out_inc = pair_inc.vmapped()(pair_inc.params0, pair_inc.sstate0,
                                 pair_inc.parts, pair_inc.round_keys)
    out_full = pair_full.vmapped()(pair_full.params0, pair_full.sstate0,
                                   pair_full.parts,
                                   pair_full.round_keys)
    np.testing.assert_array_equal(np.asarray(out_inc["selected"]),
                                  np.asarray(out_full["selected"]))
    for i, seed in enumerate(SWEEP.seeds):
        host = run_host_reference(spec_inc, "dir_mild", "hics", seed)
        assert host["selected"] == \
            np.asarray(out_inc["selected"][i]).tolist()


# ---------------------------------------------------------------------------
# availability / masking: the cache never sees masked-out clients
# ---------------------------------------------------------------------------


def _masked_drive(scenario_name, incremental, t_max=14, n=10, k=3, c=6,
                  seed=0):
    scn = get_scenario(scenario_name)
    fn = make_functional("hics", num_clients=n, num_select=k,
                         total_rounds=t_max, num_classes=c,
                         incremental=incremental)
    _, k_sel, round_keys = seed_keychain(seed, t_max)
    state = fn.init(k_sel)
    r = np.random.default_rng(seed)
    # one compile for all rounds: called eagerly, every round compiled
    # its own cond branches, and that many XLA:CPU compiles has
    # segfaulted the compiler mid-suite
    select = jax.jit(functools.partial(masked_select, fn))
    update = jax.jit(fn.update)
    picks, states = [], []
    for t in range(t_max):
        kr = round_keys[t]
        k_s, _ = jax.random.split(kr)
        avail = availability_mask(scn, n, t, jax.random.fold_in(kr, 1))
        prev = state
        ids, state = select(state, jnp.int32(t), k_s, avail,
                            jax.random.fold_in(kr, 2))
        picks.append(np.asarray(ids).tolist())
        states.append((np.asarray(avail), np.asarray(prev.delta_b),
                       np.asarray(prev.row_stats), np.asarray(ids),
                       np.asarray(prev.stale_ids), state))
        obs = Observations(bias_updates=jnp.asarray(
            r.normal(size=(k, c)) * 0.02, jnp.float32))
        state = update(state, jnp.int32(t), ids, obs)
    return picks, states, state


@pytest.mark.parametrize("scenario", ["flaky_severe", "diurnal_mixed"])
def test_masked_cache_no_nans_and_no_poisoning(scenario):
    """Dropout/diurnal masks interacting with the cache leak no NaNs
    into entropies, distances or sampling weights, and only the rows
    staled by the previous update are ever rewritten — masked-out
    bystanders keep their cached rows bit-for-bit."""
    picks, states, final = _masked_drive(scenario, incremental=True)
    for avail, db_prev, stats_prev, ids, stale_prev, out in states:
        out_stats = np.asarray(out.row_stats)
        assert np.isfinite(out_stats).all()
        assert np.isfinite(np.asarray(out.dist_cache)).all()
        # masking is per-round: original weights restored, finite
        w = np.asarray(out.weights)
        assert np.isfinite(w).all() and w.sum() > 0
        # rows whose stats changed across this select ⊆ staled rows
        changed = np.flatnonzero(
            np.any(out_stats != stats_prev, axis=-1))
        assert set(changed) <= set(stale_prev.tolist())
        if avail.sum() >= len(ids):
            assert avail[ids].all()
    ent = np.asarray(final.row_stats[:, 1])
    assert np.isfinite(ent).all()


@pytest.mark.parametrize("scenario", ["flaky_severe", "diurnal_mixed"])
def test_masked_parity_incremental_vs_full(scenario):
    """Same key/obs chain under masking: incremental == from-scratch."""
    p_inc, _, _ = _masked_drive(scenario, incremental=True)
    p_full, _, _ = _masked_drive(scenario, incremental=False)
    assert p_inc == p_full


def test_masked_sweep_runs_finite_with_incremental_cache():
    """The whole dropout scenario through the vmapped sweep engine with
    the cache on the seed axis stays finite end-to-end."""
    spec = dataclasses.replace(
        SWEEP, scenarios=("flaky_severe",), rounds=8,
        selector_kw={"incremental": True})
    pair = build_pair(spec, "flaky_severe", "hics")
    out = pair.vmapped()(pair.params0, pair.sstate0, pair.parts,
                         pair.round_keys)
    assert np.isfinite(np.asarray(out["test_acc"])).all()
    assert np.isfinite(np.asarray(out["mean_entropy"])).all()


# ---------------------------------------------------------------------------
# OO shim / entropy-history integration
# ---------------------------------------------------------------------------


def test_shim_rejects_double_update_without_select(rng):
    """The (K,) staleness buffer only covers one update; a second
    update before the next select would silently leave the first
    cohort's cached rows stale — the shim fails fast instead.  The
    from-scratch selector has no such restriction."""
    from repro.core import make_selector
    db = rng.normal(0, 0.02, (8, 4))
    sel = make_selector("hics", num_clients=8, num_select=2,
                        total_rounds=6, seed=0, num_classes=4)
    ids = sel.select(0)
    sel.update(0, ids, bias_updates=db[ids])
    with pytest.raises(RuntimeError, match="intervening select"):
        sel.update(0, ids, bias_updates=db[ids])
    sel.select(1)                       # refresh clears the hazard
    sel.update(1, ids, bias_updates=db[ids])
    full = make_selector("hics", num_clients=8, num_select=2,
                         total_rounds=6, seed=0, num_classes=4,
                         incremental=False)
    ids = full.select(0)
    full.update(0, ids, bias_updates=db[ids])
    full.update(0, ids, bias_updates=db[ids])   # no cache, no hazard


def test_shim_incremental_parity_with_full(rng):
    """Through the legacy OO shim (standalone key discipline, width
    growth via _ensure_dims): incremental == from-scratch."""
    from repro.core import make_selector
    n, k, c, t_max = 16, 4, 8, 10
    db = rng.normal(0, 0.02, (n, c))
    picks = {}
    for inc in (True, False):
        sel = make_selector("hics", num_clients=n, num_select=k,
                            total_rounds=t_max, seed=3,
                            incremental=inc)
        got = []
        for t in range(t_max):
            ids = sel.select(t)
            got.append(list(ids))
            sel.update(t, ids, bias_updates=db[ids])
        picks[inc] = got
        ent = sel.estimated_entropies()
        assert ent is not None and np.isfinite(ent).all()
    assert picks[True] == picks[False]
