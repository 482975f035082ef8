"""Device-resident metric groups: the in-scan ``Telemetry`` pytree.

A :class:`MetricsSpec` names which metric *groups* a run records;
:func:`make_metrics` compiles that choice into a pure
``(init, step)`` pair the drivers thread through their jitted round
bodies:

    telc            = metrics.init()                # scan-carry pytree
    telc, telemetry = metrics.step(telc, ctx)       # inside round_step

``telemetry`` is a flat ``{"group/field": array}`` dict — an ordinary
scan output, so ``lax.scan`` stacks it to ``(T, ...)`` per field and
``vmap`` batches it over the sweep's seed axis with zero host
callbacks.  The carry holds the few metrics that accumulate across
rounds (the fairness times-selected histogram).

Schema contract: the field SET is identical for every group
combination — disabled groups (and fields whose inputs a driver cannot
supply, e.g. ``async/*`` on the sync loop) materialize zero-width
``(0,)`` arrays, exactly like ``SelectorState.stale_ids`` does for
non-incremental selectors.  Enabling a group therefore never changes
the pytree *structure*, only array widths, and the training
computation is untouched: every metric is derived from values the
round body already produced, so telemetry-on and telemetry-off runs
take bit-identical trajectories (pinned by tests/test_telemetry.py).

Groups:

  selection — Ĥ-estimate health: mean/std spread, cohort mean,
              Ĥ-vs-true-partition-entropy MAE + Spearman rank
              correlation (the Eq. 9 estimation-quality observable;
              needs ``ctx.true_entropy``), distance-cache staleness
              fill, and — when the selector exposes ``diagnostics`` —
              cluster sizes, within-cluster Ĥ spread and the stale-row
              repairs of one clustering of the cached distance.
  training  — per-round train loss, mean ‖Δb‖ row norm, global update
              norm ‖θ^{t+1} − θ^t‖, lr scale.
  fairness  — cumulative times-selected histogram, participation rate
              (fraction ever selected), effective participation
              exp(H(counts))/N.
  async     — buffer fill, accepted/overflow-dropped counts,
              aggregation trigger, server version, version lag of the
              oldest buffered entry, staleness ages of the aggregated
              cohort (−1-padded when the tick didn't fire).

Imports from ``repro.core`` are deliberately lazy (inside functions):
``repro.kernels`` pulls in :mod:`repro.telemetry.trace` at import
time, so a module-level ``repro.core`` import here would close an
import cycle.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

#: every registered metric group, in schema order.
GROUPS: Tuple[str, ...] = ("selection", "training", "fairness", "async")


@dataclasses.dataclass(frozen=True)
class MetricsSpec:
    """Which metric groups a run records.  ``groups=()`` is telemetry
    off: every field in the schema is emitted zero-width."""
    groups: Tuple[str, ...] = ()

    def __post_init__(self):
        unknown = set(self.groups) - set(GROUPS)
        if unknown:
            raise ValueError(f"unknown metric groups {sorted(unknown)}; "
                             f"known: {list(GROUPS)}")
        object.__setattr__(self, "groups", tuple(self.groups))

    def enabled(self, group: str) -> bool:
        return group in self.groups

    @classmethod
    def all(cls) -> "MetricsSpec":
        return cls(groups=GROUPS)


class TelemetryCtx(NamedTuple):
    """What the round/tick body hands the metrics step.  Every field a
    driver cannot supply stays ``None`` — the corresponding metrics
    come out zero-width (the decision is static per trace, so the scan
    still compiles once)."""
    t: Any = None                    # round / tick index
    ids: Any = None                  # (K,) dispatched cohort
    state: Any = None                # post-update SelectorState
    train_loss: Any = None           # () cohort mean train loss
    true_entropy: Any = None         # (N,) H(D_k) of the true partition
    params_before: Any = None        # θ^t   (pre-aggregation)
    params_after: Any = None         # θ^{t+1}
    bias_updates: Any = None         # (K, C) cohort Δb
    lr_scale: Any = None             # () decay factor
    # -- async tick extras ------------------------------------------------
    fired: Any = None                # () bool — aggregation triggered
    fill: Any = None                 # () buffer fill after the tick
    accepted: Any = None             # () arrivals buffered this tick
    dropped: Any = None              # () arrivals overflow-dropped
    version: Any = None              # () server version after the tick
    version_lag: Any = None          # () version − oldest buffered
    agg_ages: Any = None             # (M,) popped ages, −1 when idle


class Metrics(NamedTuple):
    """The compiled ``(init, step)`` pair plus its spec."""
    spec: MetricsSpec
    init: Callable[[], Dict[str, jnp.ndarray]]
    step: Callable[..., tuple]   # (carry, ctx) -> (carry, telemetry)


def _zf() -> jnp.ndarray:
    return jnp.zeros((0,), jnp.float32)


def _zi() -> jnp.ndarray:
    return jnp.zeros((0,), jnp.int32)


def _f32(v) -> jnp.ndarray:
    return jnp.asarray(v, jnp.float32)


def _flat_norm_sq(a, b) -> jnp.ndarray:
    leaves_a = jax.tree_util.tree_leaves(a)
    leaves_b = jax.tree_util.tree_leaves(b)
    return sum(jnp.sum(jnp.square(_f32(x) - _f32(y)))
               for x, y in zip(leaves_a, leaves_b))


def _ranks(v: jnp.ndarray) -> jnp.ndarray:
    order = jnp.argsort(v)
    return jnp.zeros(v.shape, jnp.float32).at[order].set(
        jnp.arange(v.shape[0], dtype=jnp.float32))


def spearman(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Spearman rank correlation of two (N,) vectors (ties broken by
    argsort order — Ĥ ties are measure-zero for real updates).  The
    ordering-consistency observable Thm 3.3 actually promises, unlike
    a raw value comparison."""
    ra, rb = _ranks(a), _ranks(b)
    ra = ra - jnp.mean(ra)
    rb = rb - jnp.mean(rb)
    denom = jnp.sqrt(jnp.sum(ra * ra) * jnp.sum(rb * rb))
    return jnp.where(denom > 0, jnp.sum(ra * rb) / denom, 0.0)


def client_true_entropy(y, mask, num_classes: int) -> jnp.ndarray:
    """(N,) true label entropy H(D_k) from padded labels + sample mask
    — the ground truth the ``selection`` group scores Ĥ against
    (Wang et al.'s Ĥ-vs-true-distribution comparison, per round).
    Pure device ops, so the sweep engine computes it per seed inside
    the vmapped runner."""
    from repro.core.hetero import label_entropy
    onehot = jax.nn.one_hot(jnp.asarray(y, jnp.int32),
                            int(num_classes)) \
        * _f32(mask)[..., None]
    return label_entropy(onehot.sum(axis=-2))


def make_metrics(spec: MetricsSpec, fn=None, num_clients: int = 0,
                 num_select: int = 0) -> Metrics:
    """Compile a :class:`MetricsSpec` for one experiment shape.

    ``fn`` is the :class:`~repro.core.selectors.functional.
    FunctionalSelector` whose ``entropies`` / ``diagnostics`` hooks the
    ``selection`` group reads (optional — without it the selection
    fields are zero-width).  ``num_clients`` sizes the fairness
    histogram.
    """
    n = int(num_clients)
    want_sel = spec.enabled("selection")
    want_train = spec.enabled("training")
    want_fair = spec.enabled("fairness")
    want_async = spec.enabled("async")

    def init() -> Dict[str, jnp.ndarray]:
        return {"fairness/counts":
                jnp.zeros((n,), jnp.int32) if want_fair else _zi()}

    def step(carry: Dict[str, jnp.ndarray], ctx: TelemetryCtx):
        from repro.core.selectors.functional import state_entropies
        out: Dict[str, jnp.ndarray] = {}

        # -- selection ----------------------------------------------------
        ent = (state_entropies(fn, ctx.state)
               if want_sel and fn is not None and ctx.state is not None
               else _zf())
        have_ent = ent.shape[0] > 0
        if have_ent:
            out["selection/ent_mean"] = jnp.mean(ent)
            out["selection/ent_std"] = jnp.std(ent)
            out["selection/ent_selected_mean"] = (
                jnp.mean(ent[ctx.ids]) if ctx.ids is not None
                else jnp.mean(ent))
        else:
            out["selection/ent_mean"] = _zf()
            out["selection/ent_std"] = _zf()
            out["selection/ent_selected_mean"] = _zf()
        if have_ent and ctx.true_entropy is not None:
            te = _f32(ctx.true_entropy)
            out["selection/ent_mae"] = jnp.mean(jnp.abs(ent - te))
            out["selection/ent_rank_corr"] = spearman(ent, te)
        else:
            out["selection/ent_mae"] = _zf()
            out["selection/ent_rank_corr"] = _zf()
        ring = (int(ctx.state.stale_ids.shape[0])
                if want_sel and ctx.state is not None else 0)
        out["selection/stale_frac"] = (
            _f32(ctx.state.stale_fill) / ring if ring else _zf())
        if want_sel and fn is not None and fn.diagnostics is not None \
                and ctx.state is not None:
            diag = fn.diagnostics(ctx.state)
            out["selection/cluster_sizes"] = jnp.asarray(
                diag["cluster_sizes"], jnp.int32)
            out["selection/cluster_ent_spread"] = _f32(
                diag["cluster_ent_spread"])
            out["selection/cluster_repairs"] = jnp.asarray(
                diag["cluster_repairs"], jnp.int32)
        else:
            out["selection/cluster_sizes"] = _zi()
            out["selection/cluster_ent_spread"] = _zf()
            out["selection/cluster_repairs"] = _zi()

        # -- training -----------------------------------------------------
        out["training/loss"] = (
            _f32(ctx.train_loss)
            if want_train and ctx.train_loss is not None else _zf())
        out["training/delta_b_norm"] = (
            jnp.mean(jnp.linalg.norm(_f32(ctx.bias_updates), axis=-1))
            if want_train and ctx.bias_updates is not None else _zf())
        out["training/update_norm"] = (
            jnp.sqrt(_flat_norm_sq(ctx.params_after, ctx.params_before))
            if want_train and ctx.params_before is not None
            and ctx.params_after is not None else _zf())
        out["training/lr_scale"] = (
            _f32(ctx.lr_scale)
            if want_train and ctx.lr_scale is not None else _zf())

        # -- fairness -----------------------------------------------------
        counts = carry["fairness/counts"]
        if want_fair and ctx.ids is not None:
            counts = counts.at[jnp.asarray(ctx.ids, jnp.int32)].add(1)
            total = jnp.sum(counts)
            p = _f32(counts) / _f32(jnp.maximum(total, 1))
            hp = -jnp.sum(jnp.where(
                counts > 0, p * jnp.log(jnp.clip(p, 1e-12, None)), 0.0))
            out["fairness/sel_counts"] = counts
            out["fairness/participation"] = jnp.mean(
                (counts > 0).astype(jnp.float32))
            out["fairness/eff_participation"] = jnp.where(
                total > 0, jnp.exp(hp) / max(1, n), 0.0)
        else:
            out["fairness/sel_counts"] = _zi()
            out["fairness/participation"] = _zf()
            out["fairness/eff_participation"] = _zf()

        # -- async --------------------------------------------------------
        for field, val in (("fired", ctx.fired), ("fill", ctx.fill),
                           ("accepted", ctx.accepted),
                           ("dropped", ctx.dropped),
                           ("version", ctx.version),
                           ("version_lag", ctx.version_lag)):
            out[f"async/{field}"] = (
                _f32(val) if want_async and val is not None else _zf())
        out["async/agg_ages"] = (
            _f32(ctx.agg_ages)
            if want_async and ctx.agg_ages is not None else _zf())

        return {"fairness/counts": counts}, out

    return Metrics(spec, init, step)
