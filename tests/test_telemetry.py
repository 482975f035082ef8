"""Telemetry subsystem acceptance.

The contract (src/repro/telemetry/metrics.py): enabling any metric
group combination changes ONLY what is recorded, never what is
computed — participant sets, losses, and final parameters stay
bit-identical to the telemetry-off run on every driver (host loop,
scanned loop, vmapped sweep, async tick scan); the scanned drivers
still compile exactly once; and every driver emits the same flat
``{"group/field": array}`` schema, with zero-width arrays for
disabled/unavailable fields.  Plus the JSONL export round-trip.
"""
import dataclasses
import json

import numpy as np
import pytest

import jax

from repro.data import SyntheticSpec
from repro.fed import (AsyncConfig, AsyncFederatedServer, ExperimentSpec,
                       LocalSpec, build)
from repro.scenarios import SweepSpec, make_dataset, materialize, run_sweep
from repro.scenarios.sweep import _make_model
from repro.configs import get_config
from repro.telemetry import (GROUPS, MetricsSpec, TelemetryCtx,
                             make_metrics, read_jsonl, summarize,
                             telemetry_from_records, write_run)

SYNC_GROUPS = ("selection", "training", "fairness")


def _spec(telemetry=(), jit_rounds=True, rounds=8):
    return ExperimentSpec(
        arch="paper-mlp", num_clients=12, num_select=3, rounds=rounds,
        alphas=(0.05, 5.0), selector="hics",
        local=LocalSpec(algo="fedavg", optimizer="sgd", lr=0.1,
                        epochs=1, batch_size=32),
        samples_train=400, samples_test=120, eval_every=4, seed=0,
        jit_rounds=jit_rounds, telemetry=telemetry)


def _run(telemetry=(), jit_rounds=True, rounds=8):
    server, _ = build(_spec(telemetry, jit_rounds, rounds))
    hist = server.run()
    return server, hist


SWEEP_SPEC = SweepSpec(
    scenarios=("dir_mild",), selectors=("hics",), seeds=(0, 1),
    num_clients=10, num_select=3, rounds=6,
    samples_train=400, samples_test=120,
    data=SyntheticSpec(dim=16, rank=2, noise=0.5),
    local=LocalSpec(algo="fedavg", optimizer="sgd", lr=0.1, epochs=1,
                    batch_size=32))


def _make_async_server(telemetry):
    spec = SWEEP_SPEC
    scn = spec.scenario("dir_mild")
    cfg = get_config(spec.arch)
    train, test, _ = make_dataset(scn, spec.samples_train,
                                  spec.samples_test, cfg.vocab_size,
                                  spec.data_seed)
    part = materialize(scn, 0, train, cfg.vocab_size, spec.num_clients,
                       spec.capacity())
    init_fn, apply_fn, _ = _make_model(spec, cfg, scn.data.dim)
    idx = np.asarray(part.idx)
    acfg = AsyncConfig(num_clients=spec.num_clients, num_select=3,
                       ticks=spec.rounds, selector="hics",
                       local=spec.local, eval_every=spec.rounds,
                       seed=0, telemetry=telemetry)
    return AsyncFederatedServer(
        init_fn, apply_fn, acfg, np.asarray(train["x"])[idx],
        np.asarray(train["y"])[idx], np.asarray(part.mask),
        test={k: np.asarray(v) for k, v in test.items()})


def _async_servers(telemetry):
    out = []
    for tel in ((), telemetry):
        srv = _make_async_server(tel)
        out.append((srv, srv.run()))
    return out


def _assert_trees_equal(a, b):
    for x, y in zip(jax.tree_util.tree_leaves(a),
                    jax.tree_util.tree_leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# spec / schema basics
# ---------------------------------------------------------------------------


def test_unknown_group_rejected():
    with pytest.raises(ValueError, match="unknown metric group"):
        MetricsSpec(("selektion",))


def test_all_spec_covers_registry():
    assert MetricsSpec.all().groups == GROUPS


def test_disabled_groups_zero_width_stable_structure():
    """Off and on runs of the raw (init, step) pair produce the same
    pytree structure; disabled fields are (0,)-shaped."""
    off = make_metrics(MetricsSpec())
    on = make_metrics(MetricsSpec(("training", "fairness")),
                      num_clients=8, num_select=2)
    ctx = TelemetryCtx(t=0, ids=np.array([1, 3]), train_loss=0.5)
    _, tel_off = off.step(off.init(), ctx)
    _, tel_on = on.step(on.init(), ctx)
    assert set(tel_off) == set(tel_on)          # identical field set
    assert all(v.shape == (0,) for v in tel_off.values())
    assert tel_on["training/loss"].shape == ()
    assert tel_on["fairness/sel_counts"].shape == (8,)
    # training fields the ctx didn't supply stay zero-width even when
    # the group is enabled
    assert tel_on["training/update_norm"].shape == (0,)


def test_fairness_counts_accumulate():
    m = make_metrics(MetricsSpec(("fairness",)), num_clients=6,
                     num_select=2)
    carry = m.init()
    for ids in ([0, 1], [1, 2], [1, 5]):
        carry, tel = m.step(carry, TelemetryCtx(ids=np.asarray(ids)))
    np.testing.assert_array_equal(np.asarray(tel["fairness/sel_counts"]),
                                  [1, 3, 1, 0, 0, 1])
    assert float(tel["fairness/participation"]) == pytest.approx(4 / 6)
    assert 0.0 < float(tel["fairness/eff_participation"]) <= 1.0


def _ward_repairs(d, m):
    """Stale-row repairs of a numpy walk of Ward's merge loop with a
    lower-bound row-minimum cache (``core.clustering.agglomerate``)."""
    d = np.array(d, float) ** 2
    n = len(d)
    np.fill_diagonal(d, np.inf)
    sizes, row_min, repairs = np.ones(n), d.min(axis=1), 0
    for _ in range(n - m):
        while True:
            i = int(np.argmin(row_min))
            j = int(np.argmin(d[i]))
            if d[i, j] == row_min[i]:
                break
            row_min[i] = d[i, j]
            repairs += 1
        i, j = min(i, j), max(i, j)
        ni, nj = sizes[i], sizes[j]
        new = ((ni + sizes) * d[i] + (nj + sizes) * d[j]
               - sizes * d[i, j]) / (ni + nj + sizes)
        new[i] = new[j] = np.inf
        d[i], d[:, i], d[:, j] = new, new, np.inf
        sizes[i], sizes[j] = ni + nj, 0.0
        np.minimum(row_min, new, out=row_min)
        row_min[i], row_min[j] = new.min(), np.inf
    return repairs


def test_selection_cluster_repairs_counter():
    """``selection/cluster_repairs`` counts the stale-row repairs of one
    clustering of the cached distance, as a numpy walk counts them."""
    from repro.core import make_functional
    pts = np.array([0.0, 2.0, 3.0, 7.0, 8.0, 13.0])
    d = np.abs(pts[:, None] - pts[None, :])
    fn = make_functional("hics", num_clients=6, num_select=2,
                         total_rounds=4)
    state = fn.init(jax.random.PRNGKey(0))
    state = state._replace(dist_cache=jax.numpy.asarray(d,
                                                        jax.numpy.float32))
    m = make_metrics(MetricsSpec(("selection",)), fn=fn, num_clients=6,
                     num_select=2)
    _, tel = m.step(m.init(), TelemetryCtx(ids=np.array([0, 1]),
                                           state=state))
    rep = tel["selection/cluster_repairs"]
    assert rep.shape == () and np.issubdtype(rep.dtype, np.integer)
    assert int(rep) >= 0
    assert int(rep) == _ward_repairs(d, 2) == 2
    off_m = make_metrics(MetricsSpec())
    _, off = off_m.step(off_m.init(), TelemetryCtx())
    assert off["selection/cluster_repairs"].shape == (0,)


# ---------------------------------------------------------------------------
# invariance: telemetry never perturbs the run (the core guarantee)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("jit_rounds", [False, True],
                         ids=["host", "scanned"])
def test_server_invariant_under_telemetry(jit_rounds):
    s_off, h_off = _run((), jit_rounds)
    s_on, h_on = _run(SYNC_GROUPS, jit_rounds)
    assert h_off["selected"] == h_on["selected"]
    np.testing.assert_array_equal(h_off["train_loss"], h_on["train_loss"])
    _assert_trees_equal(s_off.params, s_on.params)
    # and the recording itself materialized, (T,)-shaped
    tel = s_on.telemetry
    assert tel["training/loss"].shape == (8,)
    assert tel["selection/ent_mean"].shape == (8,)
    assert tel["fairness/sel_counts"].shape == (8, 12)
    # final-round histogram == the actual selection counts
    counts = np.bincount(np.concatenate(h_on["selected"]), minlength=12)
    np.testing.assert_array_equal(tel["fairness/sel_counts"][-1], counts)


def test_partial_group_combo_invariant():
    _, h_off = _run((), True)
    s_on, h_on = _run(("fairness",), True)
    assert h_off["selected"] == h_on["selected"]
    # disabled groups stay zero-width in the stacked output
    assert s_on.telemetry["training/loss"].shape == (8, 0)
    assert s_on.telemetry["fairness/participation"].shape == (8,)


def test_sweep_invariant_under_telemetry():
    off = run_sweep(SWEEP_SPEC)
    on = run_sweep(dataclasses.replace(SWEEP_SPEC,
                                       telemetry=SYNC_GROUPS))
    c_off = off["grid"]["dir_mild/hics"]
    c_on = on["grid"]["dir_mild/hics"]
    np.testing.assert_array_equal(c_off["selected"], c_on["selected"])
    np.testing.assert_array_equal(c_off["train_loss"], c_on["train_loss"])
    tel = c_on["telemetry"]                 # {field: (seeds, T, ...)}
    assert tel["training/loss"].shape == (2, 6)
    assert tel["fairness/sel_counts"].shape == (2, 6, 10)
    assert tel["selection/ent_rank_corr"].shape == (2, 6)
    assert np.all(np.abs(tel["selection/ent_rank_corr"]) <= 1.0 + 1e-6)
    assert np.all(tel["selection/ent_mae"] >= 0.0)


def test_async_invariant_under_telemetry():
    (s_off, h_off), (s_on, h_on) = _async_servers(GROUPS)
    assert h_off["selected"] == h_on["selected"]
    np.testing.assert_array_equal(h_off["train_loss"], h_on["train_loss"])
    _assert_trees_equal(s_off.params, s_on.params)
    tel = s_on.telemetry
    T = SWEEP_SPEC.rounds
    assert tel["async/fill"].shape == (T,)
    assert tel["async/version"].shape == (T,)
    assert tel["training/loss"].shape == (T,)
    # identity latency at B = M = K: every tick fires, lag stays 0
    assert np.all(tel["async/fired"] == 1.0)
    assert np.all(tel["async/version_lag"] == 0.0)
    # staleness ages: (T, M) with −1 padding only when a tick idles
    assert tel["async/agg_ages"].ndim == 2
    assert np.all(tel["async/agg_ages"] >= -1.0)


# ---------------------------------------------------------------------------
# single compilation with telemetry enabled
# ---------------------------------------------------------------------------


def test_scanned_round_step_compiles_once_with_telemetry():
    server, _ = build(_spec(SYNC_GROUPS, True))
    traces = []
    step = server._make_round_step()

    def counting(carry, xs):
        traces.append(1)
        return step(carry, xs)

    server._round_step = counting
    hist = server.run()
    assert len(hist["round"]) == 8
    assert len(traces) == 1, f"round_step traced {len(traces)} times"


def test_vmapped_sweep_compiles_once_with_telemetry():
    """The whole per-seed program (telemetry included) traces once
    under the seed vmap."""
    from repro.scenarios import build_pair
    pair = build_pair(dataclasses.replace(SWEEP_SPEC,
                                          telemetry=SYNC_GROUPS),
                      "dir_mild", "hics")
    traces = []

    def counting(*args):
        traces.append(1)
        return pair.run_seed(*args)

    out = jax.jit(jax.vmap(counting))(pair.params0, pair.sstate0,
                                      pair.parts, pair.round_keys)
    assert out["telemetry"]["training/loss"].shape == (2, 6)
    assert len(traces) == 1, f"run_seed traced {len(traces)} times"


def test_async_tick_step_compiles_once_with_telemetry():
    srv = _make_async_server(GROUPS)     # fresh — nothing compiled yet
    traces = []
    step = srv._tick_step

    def counting(carry, xs):
        traces.append(1)
        return step(carry, xs)

    srv._tick_step = counting
    hist = srv.run()
    assert len(hist["round"]) == SWEEP_SPEC.rounds
    assert len(traces) == 1, f"tick_step traced {len(traces)} times"


# ---------------------------------------------------------------------------
# shared schema across drivers
# ---------------------------------------------------------------------------


def test_drivers_emit_identical_field_set():
    s_scan, _ = _run(SYNC_GROUPS, True)
    s_host, _ = _run(SYNC_GROUPS, False)
    on = run_sweep(dataclasses.replace(SWEEP_SPEC,
                                       telemetry=SYNC_GROUPS))
    sweep_tel = on["grid"]["dir_mild/hics"]["telemetry"]
    (_, _), (s_async, _) = _async_servers(GROUPS)
    fields = set(s_scan.telemetry)
    assert set(s_host.telemetry) == fields
    assert set(sweep_tel) == fields
    assert set(s_async.telemetry) == fields


# ---------------------------------------------------------------------------
# JSONL export round-trip
# ---------------------------------------------------------------------------


def test_write_run_roundtrip(tmp_path):
    s_on, _ = _run(SYNC_GROUPS, True)
    path = tmp_path / "run.jsonl"
    summary = write_run(path, s_on.telemetry, meta={"driver": "test"})
    recs = read_jsonl(path)
    header, rounds = recs[0], recs[1:]
    assert header["kind"] == "header"
    assert header["meta"]["driver"] == "test"
    assert {"backend", "device_kind", "cpu_count"} <= set(header["env"])
    assert len(rounds) == 8
    back = telemetry_from_records(rounds)
    live = {k: v for k, v in s_on.telemetry.items() if 0 not in v.shape}
    assert set(back) == set(live)
    for k in live:
        np.testing.assert_allclose(back[k], live[k], rtol=1e-6)
    # summary covers every live scalar field
    assert summary["training/loss"]["last"] == pytest.approx(
        float(s_on.telemetry["training/loss"][-1]))


def test_summarize_matches_numpy():
    tel = {"training/loss": np.asarray([3.0, 2.0, 1.0], np.float32)}
    s = summarize(tel)["training/loss"]
    assert s["last"] == 1.0 and s["min"] == 1.0 and s["max"] == 3.0
    assert s["mean"] == pytest.approx(2.0)


def test_jsonl_is_plain_json_lines(tmp_path):
    s_on, _ = _run(("training",), True)
    path = tmp_path / "run.jsonl"
    write_run(path, s_on.telemetry, meta={})
    for line in path.read_text().splitlines():
        json.loads(line)                      # every line parses alone
