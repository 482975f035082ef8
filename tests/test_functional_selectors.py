"""Functional selector protocol: OO-shim/functional parity, purity,
and the device sampling/clustering primitives behind it."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import (SELECTORS, Observations, agglomerate,
                        agglomerate_device, cluster_means,
                        cluster_means_device, hierarchical_sample_device,
                        make_functional, make_selector,
                        weighted_sample_device)
from repro.core.clustering import _agglomerate_device


def _drive_functional(name, n, k, t_max, c, seed, db, full, losses):
    """Replicate the shim's exact key discipline on the raw triple."""
    fn = make_functional(name, num_clients=n, num_select=k,
                         total_rounds=t_max, num_classes=c,
                         feat_dim=full.shape[-1])
    key = jax.random.PRNGKey(seed)
    key, k0 = jax.random.split(key)
    state = fn.init(k0)
    out = []
    for t in range(t_max):
        key, kt = jax.random.split(key)
        ids, state = fn.select(state, t, kt)
        ids_list = [int(i) for i in np.asarray(ids)]
        out.append(ids_list)
        obs = Observations(
            bias_updates=jnp.asarray(db[ids_list], jnp.float32),
            full_updates=jnp.asarray(
                full if "full_all" in fn.requires else full[ids_list],
                jnp.float32),
            losses=jnp.asarray(losses[t], jnp.float32))
        state = fn.update(state, t, ids, obs)
    return out, state


@pytest.mark.parametrize("name", sorted(SELECTORS))
def test_shim_functional_parity(name, rng):
    """N rounds through the OO shim and through the raw functional
    triple from the same seed produce identical participant sets."""
    n, k, t_max, c, seed = 24, 4, 10, 10, 11
    db = rng.normal(0, 0.02, (n, c))
    full = rng.normal(size=(n, 16))
    losses = rng.random((t_max, n))

    sel = make_selector(name, num_clients=n, num_select=k,
                        total_rounds=t_max, seed=seed)
    shim_ids = []
    for t in range(t_max):
        ids = sel.select(t)
        shim_ids.append(list(ids))
        sel.update(t, ids, bias_updates=db[ids],
                   full_updates=(full if "full_all" in sel.requires
                                 else full[ids]),
                   losses=losses[t])

    fn_ids, _ = _drive_functional(name, n, k, t_max, c, seed, db, full,
                                  losses)
    assert shim_ids == fn_ids


@pytest.mark.parametrize("name", sorted(SELECTORS))
def test_functional_transitions_are_pure(name, rng):
    """Same (state, t, key) twice -> same ids and same new state."""
    n, k, c = 16, 3, 8
    fn = make_functional(name, num_clients=n, num_select=k,
                         total_rounds=20, num_classes=c, feat_dim=c)
    state = fn.init(jax.random.PRNGKey(0))
    # push one observation through so warm branches have data
    ids0 = jnp.arange(k, dtype=jnp.int32)
    full_rows = n if "full_all" in fn.requires else k
    obs = Observations(bias_updates=jnp.asarray(rng.normal(size=(k, c)),
                                                jnp.float32),
                       full_updates=jnp.asarray(
                           rng.normal(size=(full_rows, c)), jnp.float32),
                       losses=jnp.asarray(rng.random(n), jnp.float32))
    state = fn.update(state, 0, ids0, obs)
    key = jax.random.PRNGKey(42)
    ids_a, state_a = fn.select(state, 5, key)
    ids_b, state_b = fn.select(state, 5, key)
    np.testing.assert_array_equal(np.asarray(ids_a), np.asarray(ids_b))
    for la, lb in zip(jax.tree_util.tree_leaves(state_a),
                      jax.tree_util.tree_leaves(state_b)):
        np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


@pytest.mark.parametrize("name", sorted(SELECTORS))
def test_functional_select_jits_and_vmaps(name, rng):
    """select is jit-compatible, and vmaps over stacked states (the
    multi-seed sweep shape)."""
    n, k, c, b = 12, 3, 6, 4
    fn = make_functional(name, num_clients=n, num_select=k,
                         total_rounds=10, num_classes=c, feat_dim=c)
    jitted = jax.jit(fn.select)
    state = fn.init(jax.random.PRNGKey(0))
    ids, state = jitted(state, 0, jax.random.PRNGKey(1))
    assert np.asarray(ids).shape == (k,)
    # vmap over a batch of per-seed states
    states = jax.vmap(fn.init)(jax.random.split(jax.random.PRNGKey(2), b))
    keys = jax.random.split(jax.random.PRNGKey(3), b)
    ids_b, states_b = jax.vmap(lambda s, kk: fn.select(s, 0, kk))(states,
                                                                  keys)
    assert np.asarray(ids_b).shape == (b, k)
    for row in np.asarray(ids_b):
        assert len(set(row.tolist())) == k


@settings(deadline=None, max_examples=10)
@given(st.integers(6, 24), st.integers(1, 5), st.integers(2, 12),
       st.integers(0, 2**31 - 1))
def test_hics_parity_shape_sweep(n, k, c, seed):
    """Hypothesis sweep over (N, K, C): shim == functional for hics."""
    k = min(k, n)
    r = np.random.default_rng(seed)
    db = r.normal(0, 0.02, (n, c))
    full = r.normal(size=(n, 4))
    t_max = 6
    losses = r.random((t_max, n))
    sel = make_selector("hics", num_clients=n, num_select=k,
                        total_rounds=t_max, seed=seed % 997)
    shim_ids = []
    for t in range(t_max):
        ids = sel.select(t)
        shim_ids.append(list(ids))
        sel.update(t, ids, bias_updates=db[ids])
    fn_ids, _ = _drive_functional("hics", n, k, t_max, c, seed % 997,
                                  db, full, losses)
    assert shim_ids == fn_ids


@pytest.mark.parametrize("name", sorted(SELECTORS))
def test_num_select_clamped_to_num_clients(name):
    """num_select > num_clients selects all N (legacy behaviour)."""
    sel = make_selector(name, num_clients=4, num_select=9, total_rounds=6)
    ids = sel.select(0)
    assert sorted(ids) == [0, 1, 2, 3]


# ---------------------------------------------------------------------------
# Device primitives
# ---------------------------------------------------------------------------


LINKAGES = ("ward", "average", "complete", "single")


def _agglomerate_flat(dist, num_clusters, linkage="ward"):
    """Oracle: the merge loop ``agglomerate_device`` ran before its
    row-minimum cache — each merge takes the row-major argmin over the
    whole matrix and writes rows and columns i and j."""
    n = dist.shape[0]
    d = jnp.asarray(dist, jnp.float32)
    d = 0.5 * (d + d.T)
    if linkage == "ward":
        d = d * d
    d = jnp.where(jnp.eye(n, dtype=bool), jnp.inf, d)

    def body(_, carry):
        d, sizes, labels = carry
        flat = jnp.argmin(d)
        i, j = flat // n, flat % n
        dij = d[i, j]
        ni, nj = sizes[i], sizes[j]
        di, dj = d[i], d[j]
        if linkage == "ward":
            new = ((ni + sizes) * di + (nj + sizes) * dj
                   - sizes * dij) / (ni + nj + sizes)
        elif linkage == "average":
            new = (ni * di + nj * dj) / (ni + nj)
        elif linkage == "complete":
            new = jnp.maximum(di, dj)
        else:
            new = jnp.minimum(di, dj)
        new = new.at[i].set(jnp.inf).at[j].set(jnp.inf)
        d = d.at[i, :].set(new).at[:, i].set(new)
        d = d.at[j, :].set(jnp.inf).at[:, j].set(jnp.inf)
        sizes = sizes.at[i].set(ni + nj).at[j].set(0.0)
        labels = jnp.where(labels == j, i, labels)
        return d, sizes, labels

    _, _, labels = jax.lax.fori_loop(
        0, n - num_clusters, body,
        (d, jnp.ones(n, jnp.float32), jnp.arange(n)))
    reps = jnp.unique(labels, size=num_clusters)
    return np.asarray(jnp.searchsorted(reps, labels))


def _distances(rng, n, kind):
    """``points``: distances of Gaussian points; ``ties``: integer-
    rounded distances of a small integer grid with every point
    duplicated, so many pairs tie exactly."""
    if kind == "points":
        pts = rng.normal(size=(n, 3))
    else:
        pts = rng.integers(0, 5, size=(n, 2)).astype(float)
        pts[n // 2:] = pts[:n - n // 2]
    d = np.linalg.norm(pts[:, None] - pts[None, :], axis=-1)
    return np.round(d) if kind == "ties" else d


def _eq9_like(rng, n, c=10):
    """Angles between Dirichlet(0.05) vectors, as Eq. 9 builds them."""
    p = rng.dirichlet(np.full(c, 0.05), size=n)
    p /= np.linalg.norm(p, axis=1, keepdims=True)
    return np.arccos(np.clip(p @ p.T, -1.0, 1.0)).astype(np.float32)


_MERGE_CASES = (
    [pytest.param(lk, 18, "points", id=lk) for lk in LINKAGES]
    + [pytest.param(lk, n, kind, id=f"{lk}-{n}-{kind}")
       for lk in LINKAGES for n, kind in ((18, "ties"), (257, "points"),
                                          (257, "ties"))])


@pytest.mark.parametrize("linkage,n,kind", _MERGE_CASES)
def test_agglomerate_device_matches_numpy(linkage, n, kind, rng):
    """Bit-identical labels against the numpy twin and against the
    whole-matrix argmin oracle: the same merge order, ties included."""
    d = _distances(rng, n, kind)
    for m in (1, 2, 4, 9, n):
        a = agglomerate(d, m, linkage=linkage)
        b = np.asarray(agglomerate_device(jnp.asarray(d), m,
                                          linkage=linkage))
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(_agglomerate_flat(d, m, linkage), b)


def test_agglomerate_device_in_scan_and_vmap(rng):
    """Under ``jit`` inside ``lax.scan`` and under ``vmap`` over three
    matrices (the repair loop then runs until the slowest is done) the
    labels and repair counts equal the one-matrix calls."""
    ds = jnp.asarray(np.stack([_eq9_like(rng, 40) for _ in range(2)]
                              + [_distances(rng, 40, "ties")]),
                     jnp.float32)
    m = 5
    one = [_agglomerate_device(d, m) for d in ds]

    @jax.jit
    def scanned(ds):
        return jax.lax.scan(
            lambda c, d: (c, _agglomerate_device(d, m)), 0, ds)[1]

    for labels, repairs in (scanned(ds),
                            jax.vmap(lambda d: _agglomerate_device(d, m))(
                                ds)):
        for s, (lab, rep) in enumerate(one):
            np.testing.assert_array_equal(np.asarray(labels[s]),
                                          np.asarray(lab))
            np.testing.assert_array_equal(
                np.asarray(labels[s]), _agglomerate_flat(ds[s], m))
            assert int(repairs[s]) == int(rep)


def test_cluster_means_device_matches_numpy(rng):
    vals = rng.normal(size=20)
    labels = rng.integers(0, 4, 20)
    a = cluster_means(vals, labels, 4)
    b = np.asarray(cluster_means_device(jnp.asarray(vals),
                                        jnp.asarray(labels), 4))
    np.testing.assert_allclose(a, b, atol=1e-6)


def test_weighted_sample_device_distribution():
    """Gumbel top-1 over log w reproduces ∝ w frequencies."""
    w = jnp.asarray([1.0, 2.0, 7.0])
    keys = jax.random.split(jax.random.PRNGKey(0), 4000)
    draws = jax.vmap(lambda k: weighted_sample_device(k, w, 1)[0])(keys)
    freq = np.bincount(np.asarray(draws), minlength=3) / 4000
    np.testing.assert_allclose(freq, np.asarray(w) / 10.0, atol=0.03)


def test_weighted_sample_device_distinct():
    w = jnp.ones(10)
    ids = weighted_sample_device(jax.random.PRNGKey(1), w, 10)
    assert sorted(np.asarray(ids).tolist()) == list(range(10))


def test_hierarchical_sample_device_two_stage():
    """Stage 1 prefers the high-entropy cluster; draws are distinct."""
    labels = jnp.asarray([0] * 20 + [1] * 5)
    means = jnp.asarray([0.1, 2.2])
    w = jnp.ones(25)
    keys = jax.random.split(jax.random.PRNGKey(0), 300)
    draws = jax.vmap(lambda k: hierarchical_sample_device(
        k, labels, means, w, 1, 4.0)[0])(keys)
    assert int(np.sum(np.asarray(draws) >= 20)) > 270
    # without-replacement exhaustion across clusters
    ids = hierarchical_sample_device(jax.random.PRNGKey(7), labels, means,
                                     w, 25, 1.0)
    assert sorted(np.asarray(ids).tolist()) == list(range(25))
