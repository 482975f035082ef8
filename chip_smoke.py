"""Chip smoke test: the HiCS-FL federated main path on one TPU chip.

    python chip_smoke.py

One process, six phases, in this order; any failure raises and the
script exits non-zero without printing a result:

  device   JAX must see a TPU (never falls back to the CPU).
  kernels  every main-path entry point of ``repro.kernels.ops`` at
           N=512, K=10, C in {10, 32768} with its default dispatch:
           the compiled program must hold a Pallas kernel
           (``tpu_custom_call``) and its result must match the same
           call with ``use_pallas=False`` (the jnp oracle, run at
           "highest" matmul precision) within ``KERNEL_TOL``.
  ward     Ward's clustering of the oracle's N=512 Eq. 9 matrix into
           K clusters: the compiled program must hold the
           ``ward_merge`` kernel, and its labels and repair count must
           equal the XLA merge loop's exactly.
  sync     ``repro.fed.build`` + ``server.run()`` on the scanned round
           loop, selector hics then cs, at ``paper-cnn``'s published
           shape (``ExperimentSpec`` defaults: N=50, K=5, §4.1
           setting-1 α mix, 10,000 samples of dim 196, C=10).
  sweep    ``scenarios.sweep.run_sweep``: 2 seeds vmapped, hics, each
           seed matched against the scanned server on its partition.
  async    ``AsyncFederatedServer`` with hics, identity latency, which
           must reproduce the sync hics run.

Every driver phase checks K distinct in-range ids per round and finite
train and test metrics.  At these defaults (SGD, lr 0.001) 15 rounds
barely move the loss, so the reference for a driver is another driver
on the same keys, not a falling loss.

Lines before the last carry compile seconds and steady rounds/s per
phase, labelled with the device kind; they are informational.  The last
line of standard output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
"""
from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import json  # noqa: E402
import time  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.launch.cache import enable_compile_cache  # noqa: E402

KERNEL_N, KERNEL_K, KERNEL_WIDTHS = 512, 10, (10, 32_768)
#: kernel vs oracle, both f32 end to end: differences come from the
#: Gram's summation order and the online softmax, not from precision
KERNEL_TOL = dict(atol=1e-3, rtol=1e-3)
#: a driver vs the scanned server on the same keys and data
DRIVER_TOL = dict(atol=1e-4, rtol=1e-4)
TEMPERATURE, LAM = 0.0025, 10.0         # hics selector defaults
#: N/K = 10 rounds of hics's and cs's coverage sweep come first; the
#: last 5 rounds run the clustered selection
ROUNDS, EVAL_EVERY = 15, 5
TELEMETRY = ("selection", "training", "fairness")


def _say(kind: str, msg: str) -> None:
    print(f"[{kind}] {msg}", flush=True)


def phase_device():
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: JAX found no TPU (platform "
                         f"{dev.platform!r}); refusing to run on it")
    _say(dev.device_kind, f"device: platform=tpu count={len(devices)}")
    return dev


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------


def _kernel_cases(key, n: int, k: int, c: int):
    """(name, fn, args): ``fn(*args, use_pallas=...)`` calls one entry
    point of ``repro.kernels.ops``; the inputs are a realistic cache
    state (built by the oracle on x0) and a cohort ``ids`` whose rows
    changed in x1."""
    from repro.kernels import ops
    kx, kn, ki = jax.random.split(key, 3)
    x0 = 0.02 * jax.random.normal(kx, (n, c), jnp.float32)
    ids = jax.random.permutation(ki, n)[:k].astype(jnp.int32)
    x1 = x0.at[ids].set(0.02 * jax.random.normal(kn, (k, c), jnp.float32))
    with jax.default_matmul_precision("highest"):
        h0, d0 = ops.hics_selection_step(x0, TEMPERATURE, lam=LAM,
                                         use_pallas=False)
        _, n0, _ = ops.fused_row_stats(x0, TEMPERATURE, use_pallas=False)
        h1, n1, _ = ops.fused_row_stats(x1, TEMPERATURE, use_pallas=False)
        zeros = jnp.zeros((n, n), jnp.float32), jnp.zeros((n, 2))
        feat_cache = {m: ops.cached_feature_step(
            x0, *zeros, jnp.arange(n), metric=m, use_pallas=False)
            for m in ("cosine", "l2")}
    stats0 = jnp.stack([n0, h0], axis=-1)
    stats1 = jnp.stack([n1, h1], axis=-1)
    cases = [
        ("fused_row_stats",
         lambda x, **kw: ops.fused_row_stats(x, TEMPERATURE, **kw), (x1,)),
        ("hics_selection_step",
         lambda x, **kw: ops.hics_selection_step(x, TEMPERATURE, lam=LAM,
                                                 **kw), (x1,)),
        ("hics_selection_step_cached",
         lambda x, d, s, i, **kw: ops.hics_selection_step_cached(
             x, d, s, i, TEMPERATURE, lam=LAM, **kw),
         (x1, d0, stats0, ids)),
    ]
    for ep in ("arccos", "cosine", "l2"):
        cases.append((f"gram_row_update[{ep}]",
                      lambda x, s, i, ep=ep, **kw: ops.gram_row_update(
                          x, s, i, lam=LAM, epilogue=ep, **kw),
                      (x1, stats1, ids)))
    for m in ("cosine", "l2"):
        cases.append((f"cached_feature_step[{m}]",
                      lambda x, d, s, i, m=m, **kw: ops.cached_feature_step(
                          x, d, s, i, metric=m, **kw),
                      (x1, *feat_cache[m], ids)))
    return cases


def phase_kernels(dev, n: int = KERNEL_N, k: int = KERNEL_K,
                  widths=KERNEL_WIDTHS) -> None:
    failures = []
    for c in widths:
        for name, fn, args in _kernel_cases(jax.random.PRNGKey(c), n, k, c):
            t0 = time.perf_counter()
            compiled = jax.jit(fn).lower(*args).compile()
            t_compile = time.perf_counter() - t0
            if "tpu_custom_call" not in compiled.as_text():
                raise AssertionError(f"{name} C={c}: no tpu_custom_call in "
                                     "the compiled program; the Pallas "
                                     "kernel did not run compiled")
            got = jax.block_until_ready(compiled(*args))
            with jax.default_matmul_precision("highest"):
                want = fn(*args, use_pallas=False)
            errs = []
            for g, w in zip(jax.tree_util.tree_leaves(got),
                            jax.tree_util.tree_leaves(want)):
                g, w = np.asarray(g), np.asarray(w)
                errs.append(float(np.max(np.abs(g - w))))
                if not np.allclose(g, w, **KERNEL_TOL):
                    failures.append(f"{name} C={c}: max|err|={errs[-1]:.3g}"
                                    f" exceeds {KERNEL_TOL}")
            _say(dev.device_kind,
                 f"kernel {name} C={c}: compile {t_compile:.2f}s, "
                 f"max|kernel-oracle| {max(errs):.3g}")
    if failures:
        raise AssertionError("kernel/oracle mismatch:\n  "
                             + "\n  ".join(failures))


def phase_ward(dev, n: int = KERNEL_N, k: int = KERNEL_K) -> None:
    from repro.core.clustering import _agglomerate_device, _merge_loop
    from repro.kernels import ops
    x = 0.02 * jax.random.normal(jax.random.PRNGKey(0), (n, 10))
    with jax.default_matmul_precision("highest"):
        _, d = ops.hics_selection_step(x, TEMPERATURE, lam=LAM,
                                       use_pallas=False)
    run = jax.jit(lambda d: _agglomerate_device(d, k, precomputed=True))
    t0 = time.perf_counter()
    compiled = run.lower(d).compile()
    t_compile = time.perf_counter() - t0
    if "%ward_merge" not in compiled.as_text():
        raise AssertionError("Ward's clustering did not compile to the "
                             "ward_merge kernel")
    labels, repairs, in_vmem = compiled(d)
    want, want_repairs = jax.jit(_merge_loop, static_argnums=(1, 2))(
        d, k, "ward")
    uniq = jnp.unique(want, size=k)
    want = jnp.searchsorted(uniq, want)
    if not (bool(jnp.array_equal(labels, want)) and int(in_vmem) == 1
            and int(repairs) == int(want_repairs)):
        raise AssertionError("ward_merge differs from the XLA merge loop")
    _say(dev.device_kind, f"kernel ward_merge N={n}: compile "
         f"{t_compile:.2f}s, labels and {int(repairs)} repairs as the "
         "XLA loop's")


# ---------------------------------------------------------------------------
# federated drivers
# ---------------------------------------------------------------------------


def _check_trajectory(label: str, selected, train_loss, n: int, k: int,
                      rounds: int) -> None:
    sel = np.asarray(selected)
    loss = np.asarray(train_loss, np.float64)
    if sel.shape != (rounds, k):
        raise AssertionError(f"{label}: selected shape {sel.shape}, "
                             f"expected {(rounds, k)}")
    if sel.min() < 0 or sel.max() >= n:
        raise AssertionError(f"{label}: client id out of [0, {n})")
    if any(len(set(row.tolist())) != k for row in sel):
        raise AssertionError(f"{label}: a round selected a client twice")
    if not np.all(np.isfinite(loss)):
        raise AssertionError(f"{label}: non-finite train loss {loss}")


def _check_test(label: str, values) -> None:
    v = np.asarray(values, np.float64)
    if not np.all(np.isfinite(v)):
        raise AssertionError(f"{label}: non-finite test metric {v}")


def _check_matches(label: str, hist, ref) -> None:
    """Cross-driver parity, the repo's own reference for a driver: the
    same key chain gives the same participant sets and train losses."""
    if np.asarray(hist["selected"]).tolist() != \
            np.asarray(ref["selected"]).tolist():
        raise AssertionError(f"{label}: participant sets differ from the "
                             f"scanned server's")
    np.testing.assert_allclose(hist["train_loss"], ref["train_loss"],
                               **DRIVER_TOL, err_msg=label)


def _segment_timing(hist) -> str:
    """Segment 0 pays the compile; later segments are steady."""
    walls, rounds = hist["segment_wall_s"], hist["segment_rounds"]
    steady = sum(rounds[1:]) / sum(walls[1:])
    compile_s = walls[0] - rounds[0] / steady
    return f"compile ~{compile_s:.2f}s, steady {steady:.2f} rounds/s"


def phase_sync(dev, selector: str, **spec_kw):
    """Returns (server, spec, history) for the async phase."""
    from repro.fed import ExperimentSpec, build
    spec = ExperimentSpec(selector=selector, jit_rounds=True,
                          rounds=ROUNDS, eval_every=EVAL_EVERY,
                          telemetry=TELEMETRY, **spec_kw)
    server, _ = build(spec)
    hist = server.run()
    label = f"sync/{selector}"
    _check_trajectory(label, hist["selected"], hist["train_loss"],
                      spec.num_clients, spec.num_select, spec.rounds)
    _check_test(label, hist["test_loss"])
    _say(dev.device_kind, f"{label} {spec.arch} N={spec.num_clients} "
         f"K={spec.num_select}: {_segment_timing(hist)}")
    return server, spec, hist


def phase_sweep(dev, **spec_kw) -> None:
    """The sync phase's shape on the vmapped sweep engine
    (``mixed_80_20`` is the §4.1 setting-1 scenario), each seed checked
    against the scanned server on the same partition."""
    from repro.fed import ExperimentSpec
    from repro.scenarios import SweepSpec, run_host_reference, run_sweep
    base = ExperimentSpec()
    kw = dict(arch=base.arch, num_clients=base.num_clients,
              num_select=base.num_select, samples_train=base.samples_train,
              samples_test=base.samples_test, local=base.local)
    kw.update(spec_kw)
    spec = SweepSpec(scenarios=("mixed_80_20",), selectors=("hics",),
                     seeds=(0, 1), rounds=ROUNDS, telemetry=TELEMETRY, **kw)
    t0 = time.perf_counter()
    cell = run_sweep(spec)["grid"]["mixed_80_20/hics"]
    wall = time.perf_counter() - t0
    for s, seed in enumerate(spec.seeds):
        label = f"sweep/seed{seed}"
        _check_trajectory(label, cell["selected"][s], cell["train_loss"][s],
                          spec.num_clients, spec.num_select, spec.rounds)
        _check_test(label, cell["test_acc"][s])
        ref = run_host_reference(spec, "mixed_80_20", "hics", seed,
                                 jit_rounds=True)
        _check_matches(label, {"selected": cell["selected"][s],
                               "train_loss": cell["train_loss"][s]}, ref)
    _say(dev.device_kind, f"sweep hics {spec.arch} seeds={len(spec.seeds)}"
         f" N={spec.num_clients} K={spec.num_select}: {wall:.2f}s for "
         f"{spec.rounds} rounds, compile included")


def phase_async(dev, server, spec, sync_hist) -> None:
    """hics on the buffered-async tick scan, on the sync phase's data.
    Under identity latency every tick aggregates its own cohort, so the
    run must reproduce the sync scanned loop."""
    from repro.configs import get_config
    from repro.fed import AsyncConfig, AsyncFederatedServer
    from repro.models.classifier import make_classifier
    init_fn, apply_fn, _ = make_classifier(get_config(spec.arch),
                                           input_dim=spec.data.dim)
    acfg = AsyncConfig(num_clients=spec.num_clients,
                       num_select=spec.num_select, ticks=ROUNDS,
                       selector="hics", local=spec.local,
                       eval_every=EVAL_EVERY, seed=spec.seed,
                       telemetry=TELEMETRY)
    srv = AsyncFederatedServer(init_fn, apply_fn, acfg,
                               np.asarray(server.x), np.asarray(server.y),
                               np.asarray(server.mask), test=server.test)
    hist = srv.run()
    if hist["aggregations"] != acfg.ticks:
        raise AssertionError(f"async: {hist['aggregations']} of "
                             f"{acfg.ticks} ticks aggregated")
    _check_trajectory("async", hist["selected"], hist["train_loss"],
                      acfg.num_clients, acfg.num_select, acfg.ticks)
    _check_test("async", hist["test_loss"])
    _check_matches("async", hist, sync_hist)
    _say(dev.device_kind, f"async hics identity latency: "
         f"{hist['aggregations']}/{acfg.ticks} ticks aggregated, "
         f"{_segment_timing(hist)}")


def main() -> None:
    enable_compile_cache()
    dev = phase_device()
    phase_kernels(dev)
    phase_ward(dev)
    server, spec, hist = phase_sync(dev, "hics")
    phase_sync(dev, "cs")
    phase_sweep(dev)
    phase_async(dev, server, spec, hist)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
