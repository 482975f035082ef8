"""Federated server: the round loop of Algorithm 1 with pluggable client
selection, for any (init, apply[, features]) model triple.

Per round t:
  1. S^t ← select (functional core: ids, state = fn.select(state, t, key))
  2. LocalUpdate for the selected clients (one vmapped jit'd cohort step)
  3. θ^{t+1} ← (1/K) Σ_{k∈S^t} θ_k^t   (unbiased-sampling aggregation)
  4. whatever the selector ``requires`` is computed server-side:
       loss_all  — global-model loss on every client's data (pow-d, FedCor
                   ideal setting); one vmapped forward
       full_all  — 1-step gradient from every client (DivFL ideal setting)
       full_sel  — participants' flattened θ_k − θ^{t+1} (CS, DivFL's
                   practical refresh="selected" setting)
  5. Δb^{(k)} stacked from the head; state = fn.update(state, t, ids, obs)

Two drivers over the same functional selector core:

  * ``run()`` (host loop) — one Python iteration per round; the
    selector shim executes the jitted select/update transitions.
  * ``run(jit_rounds=True)`` — the whole round is ONE jitted
    ``round_step`` (select → vmapped local update → aggregate → stacked
    Δb / full-update observations → selector update) driven through
    ``lax.scan`` in ``eval_every``-sized segments: zero
    device→host→device transfers between ``select`` and ``update``.
    Every requirement class is computable inside the step — including
    DivFL's all-clients gradient poll, whose per-round key rides the
    scan inputs — so all six selectors scan.  Both paths consume the
    same PRNG-key chain, so they produce identical participant sets
    (for DivFL's ideal mode, up to fp tie-breaking in the greedy
    facility-location argmax once gradients converge — see
    tests/test_full_update_selectors.py).

The selector state is an opaque pytree in both drivers, so selector-
side caches — e.g. incremental HiCS's (N, N) distance cache with K-row
staleness (PR 4) — ride the scan carry and the host-loop shim without
any server-side wiring; tests/test_incremental_selection.py pins the
three drivers to identical 50-round participant sets either way.

History records per-round train loss / selected ids / Δb-derived
entropies and periodic test accuracy — everything the paper's
figures/tables need.
"""
from __future__ import annotations

import dataclasses
import time
import weakref
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (SELECTORS, Observations, head_bias_updates_stacked,
                        make_selector)
from repro.core.hetero import head_num_classes
from repro.core.selectors.functional import state_entropies
from repro.fed.client import (LocalSpec, init_extra, make_eval_fn,
                              make_local_update)
from repro.telemetry import (MetricsSpec, TelemetryCtx, client_true_entropy,
                             make_metrics, register_program, trace_enabled,
                             trace_span)

#: requirements the scanned round loop can satisfy on-device.  All four
#: are computable inside the jitted round step: loss_all is a vmapped
#: forward, full_sel flattens the cohort's params delta, full_all runs
#: the one-step all-clients gradient poll (DivFL's ideal setting) —
#: so every registered selector can ride ``jit_rounds=True``.
_SCANNABLE = frozenset({"bias_sel", "loss_all", "full_sel", "full_all"})


@dataclasses.dataclass(frozen=True)
class FedConfig:
    num_clients: int = 50
    num_select: int = 5
    rounds: int = 100
    selector: str = "hics"
    selector_kw: Optional[Dict[str, Any]] = None
    local: LocalSpec = dataclasses.field(default_factory=LocalSpec)
    eval_every: int = 5
    seed: int = 0
    lr_decay_every: int = 10     # paper: lr halves every 10 rounds
    lr_decay: float = 0.5
    jit_rounds: bool = False     # scan whole rounds instead of host loop
    #: telemetry metric groups to record (see repro.telemetry.GROUPS);
    #: () = off.  Enabled groups ride the jitted round step as an extra
    #: scan output — the training trajectory is bit-identical either way.
    telemetry: tuple = ()


def _tree_stack_gather(stacked, ids):
    return jax.tree_util.tree_map(lambda a: a[ids], stacked)


def _tree_stack_scatter(stacked, ids, values):
    return jax.tree_util.tree_map(
        lambda a, v: a.at[ids].set(v), stacked, values)


def _flatten_params(tree) -> jnp.ndarray:
    return jnp.concatenate([jnp.ravel(x) for x in
                            jax.tree_util.tree_leaves(tree)])


def aggregate_params(new_params, weights=None):
    """θ^{t+1} from the cohort's stacked local params (K, ...).

    ``weights=None`` is the sync drivers' unbiased-sampling mean
    (1/K) Σ θ_k.  With a (K,) ``weights`` vector the normalized
    weighted mean Σ w_k θ_k / Σ w_k is computed as
    ``mean(θ_k · w̃_k)`` with ``w̃ = w·K/Σw`` — the form the async
    server's staleness weighting uses, because when every weight is
    exactly equal (all ages 0 ⇒ w_k = 1.0) ``w̃ ≡ 1.0`` exactly and
    the weighted program is bit-identical to the unweighted mean.
    That identity is the parity oracle's contract: ``jnp.mean`` and
    ``sum/denom`` lower differently under XLA for non-power-of-two K,
    so ONE definition here is shared by the host loop, the scanned
    round step, the sweep engine and the async server."""
    if weights is None:
        return jax.tree_util.tree_map(
            lambda stacked: jnp.mean(stacked, axis=0), new_params)
    w = jnp.asarray(weights, jnp.float32)
    scale = w * (w.shape[0] / jnp.sum(w))
    return jax.tree_util.tree_map(
        lambda stacked: jnp.mean(
            stacked * scale.reshape((stacked.shape[0],)
                                    + (1,) * (stacked.ndim - 1)),
            axis=0), new_params)


def full_sel_updates(params, new_params) -> jnp.ndarray:
    """The ``full_sel`` observation: participants' flattened
    θ_k − θ^{t+1} against the aggregated global params, (K, P).  ONE
    definition shared by the host loop, the scanned round step and the
    sweep engine — three-way participant-set parity depends on these
    drivers computing bit-identical observations."""
    flat_global = _flatten_params(params)
    return jax.vmap(lambda p: _flatten_params(p) - flat_global)(
        new_params)


def make_grad_all(apply_fn, local: LocalSpec):
    """The ``full_all`` observation (DivFL's ideal setting): a vmapped
    one-step fedavg gradient poll over all clients,
    ``(params, x, y, mask, rngs) -> (N, P)`` flattened θ_k − θ.
    Shared by the server and the sweep engine (see
    :func:`full_sel_updates` on why)."""
    one_step = dataclasses.replace(local, epochs=1, algo="fedavg")
    lu1 = make_local_update(apply_fn, one_step)
    return jax.vmap(
        lambda p, x, y, m, r: _flatten_params(
            jax.tree_util.tree_map(
                lambda a, b: a - b, lu1(p, {}, x, y, m, r)[0], p)),
        in_axes=(None, 0, 0, 0, 0))


class FederatedServer:
    """Drives T rounds of federated training over padded client data."""

    def __init__(self, init_fn, apply_fn, cfg: FedConfig,
                 client_x: np.ndarray, client_y: np.ndarray,
                 client_mask: np.ndarray,
                 test: Optional[Dict[str, np.ndarray]] = None,
                 features_fn=None):
        assert client_x.shape[0] == cfg.num_clients
        self.cfg = cfg
        self.x = jnp.asarray(client_x)
        self.y = jnp.asarray(client_y)
        self.mask = jnp.asarray(client_mask)
        self.test = test
        self.rng = jax.random.PRNGKey(cfg.seed)
        self.rng, k0 = jax.random.split(self.rng)
        self.params = init_fn(k0)
        self.apply_fn = apply_fn
        # client weights p_k ∝ |B_k|
        sizes = np.asarray(client_mask.sum(axis=1))
        kw = dict(cfg.selector_kw or {})
        # size the selector's device buffers up-front so the state
        # pytree never changes shape (scan-carry requirement)
        if cfg.selector not in SELECTORS:
            raise KeyError(f"unknown selector {cfg.selector!r}; known: "
                           f"{sorted(SELECTORS)}")
        requires = SELECTORS[cfg.selector].requires
        if "bias_sel" in requires:
            kw.setdefault("num_classes", head_num_classes(self.params) or 1)
        if requires & {"full_all", "full_sel"}:
            kw.setdefault("feat_dim", sum(
                x.size for x in jax.tree_util.tree_leaves(self.params)))
        self.selector = make_selector(
            cfg.selector, num_clients=cfg.num_clients,
            num_select=cfg.num_select, total_rounds=cfg.rounds,
            weights=sizes, seed=cfg.seed, **kw)
        self._lu = make_local_update(apply_fn, cfg.local, features_fn)
        # lr_scale rides along as a TRACED scalar (in_axes None), so the
        # paper's lr-decay schedule never re-jits the cohort step
        self._lu_vmapped = jax.jit(jax.vmap(
            self._lu, in_axes=(None, 0, 0, 0, 0, 0, None)))
        self._eval = make_eval_fn(apply_fn)
        self._eval_vmapped = jax.jit(jax.vmap(
            lambda p, x, y, m: self._eval(p, x, y, m),
            in_axes=(None, 0, 0, 0)))
        ex0 = init_extra(cfg.local, self.params)
        self._extras = jax.tree_util.tree_map(
            lambda l: jnp.broadcast_to(l, (cfg.num_clients,) + l.shape),
            ex0) if ex0 else {}
        # DivFL ideal setting: one-step gradients from all clients
        if "full_all" in self.selector.requires:
            self._grad_all = jax.jit(make_grad_all(apply_fn, cfg.local))
        self._round_step: Optional[Callable] = None
        self._scan_jit: Optional[Callable] = None
        # device-resident telemetry (repro.telemetry): compiled once for
        # this experiment's shape; with cfg.telemetry == () every field
        # is zero-width and the step is free
        self._metrics = make_metrics(
            MetricsSpec(tuple(cfg.telemetry)), fn=self.selector.fn,
            num_clients=cfg.num_clients, num_select=cfg.num_select)
        self._telc = self._metrics.init()
        # ground truth for the selection group's Ĥ-error fields: the
        # true label entropy of each client's partition (device const)
        self._true_ent = (
            client_true_entropy(self.y, self.mask,
                                int(np.max(np.asarray(client_y))) + 1)
            if "selection" in cfg.telemetry else None)
        self._tel_step = jax.jit(self._metrics.step)
        self._tel_segments: list = []
        self.telemetry: Dict[str, np.ndarray] = {}
        # history timing semantics:
        #   wall_s        — host loop only: per-round wall time (includes
        #                   the first round's compile).  Empty in scanned
        #                   mode, where rounds never hit the host.
        #   segment_wall_s / segment_rounds — scanned mode only: wall
        #                   time of each eval_every-round scan segment
        #                   and its round count (segment 0 includes the
        #                   compile).
        #   rounds_per_s  — derived throughput over all rounds, set by
        #                   _finish() for both drivers.
        self.history: Dict[str, list] = {
            "round": [], "train_loss": [], "selected": [],
            "test_round": [], "test_loss": [], "test_acc": [],
            "bias_entropy": [], "wall_s": [],
            "segment_wall_s": [], "segment_rounds": [],
        }

    # ------------------------------------------------------------------
    @classmethod
    def from_partition(cls, init_fn, apply_fn, cfg: FedConfig,
                       x, y, partition,
                       test: Optional[Dict[str, np.ndarray]] = None,
                       features_fn=None) -> "FederatedServer":
        """Build a server from a dataset + fixed-capacity partition
        (e.g. a ``repro.scenarios`` device :class:`Partition` with
        ``idx``/``mask`` fields).  Client tensors are materialized by
        gathering rows through the index layout — exactly the arrays
        the vmapped sweep engine gathers on the fly, so a host-loop
        run over this server is the sweep's parity oracle."""
        idx = np.asarray(partition.idx)
        return cls(init_fn, apply_fn, cfg, np.asarray(x)[idx],
                   np.asarray(y)[idx],
                   np.asarray(partition.mask, dtype=np.float32),
                   test=test, features_fn=features_fn)

    # ------------------------------------------------------------------
    def run(self, progress: bool = False,
            jit_rounds: Optional[bool] = None) -> Dict[str, list]:
        if self.cfg.jit_rounds if jit_rounds is None else jit_rounds:
            return self._run_scanned(progress)
        cfg = self.cfg
        for t in range(cfg.rounds):
            t_start = time.perf_counter()
            # one key per round, split between selection and the cohort
            # — the SAME chain the scanned path consumes
            self.rng, kr = jax.random.split(self.rng)
            k_sel, k_loc = jax.random.split(kr)
            ids = np.asarray(self.selector.select(t, key=k_sel))
            rngs = jax.random.split(k_loc, len(ids))
            # paper's lr schedule: decay 0.5 every 10 rounds — passed as
            # a traced array so a new value is just new data, not a
            # retrace of the cohort step
            decay = jnp.float32(cfg.lr_decay) ** (t // cfg.lr_decay_every)
            extras = (_tree_stack_gather(self._extras, ids)
                      if self._extras else {})
            new_params, new_extras, metrics = self._lu_vmapped(
                self.params, extras, self.x[ids], self.y[ids],
                self.mask[ids], rngs, decay)
            if self._extras:
                self._extras = _tree_stack_scatter(self._extras, ids,
                                                   new_extras)
            # Δb per participant (before aggregation overwrites params)
            bias_updates = head_bias_updates_stacked(self.params,
                                                     new_params)
            params_before = self.params
            # aggregate: θ^{t+1} = (1/K) Σ θ_k
            self.params = aggregate_params(new_params)

            losses = full_updates = None
            if "loss_all" in self.selector.requires:
                losses, _ = self._eval_vmapped(self.params, self.x, self.y,
                                               self.mask)
            if "full_all" in self.selector.requires:
                self.rng, kg = jax.random.split(self.rng)
                full_updates = self._grad_all(
                    self.params, self.x, self.y, self.mask,
                    jax.random.split(kg, cfg.num_clients))
            elif "full_sel" in self.selector.requires:
                full_updates = full_sel_updates(self.params, new_params)
            self.selector.update(t, list(ids), Observations(
                bias_updates=bias_updates, full_updates=full_updates,
                losses=losses))
            if cfg.telemetry:
                # same compiled metrics step the scanned driver embeds,
                # driven one round at a time
                self._telc, tel = self._tel_step(self._telc, TelemetryCtx(
                    t=jnp.int32(t), ids=jnp.asarray(ids, jnp.int32),
                    state=self.selector.state,
                    train_loss=jnp.mean(metrics["train_loss"]),
                    true_entropy=self._true_ent,
                    params_before=params_before, params_after=self.params,
                    bias_updates=bias_updates, lr_scale=decay))
                self._tel_segments.append(jax.tree_util.tree_map(
                    lambda a: np.asarray(a)[None], tel))

            self.history["round"].append(t)
            self.history["train_loss"].append(
                float(np.mean(np.asarray(metrics["train_loss"]))))
            self.history["selected"].append(ids.tolist())
            ent = self.selector.estimated_entropies()
            self.history["bias_entropy"].append(
                None if ent is None else ent.tolist())
            self.history["wall_s"].append(time.perf_counter() - t_start)

            if self.test is not None and (t % cfg.eval_every == 0
                                          or t == cfg.rounds - 1):
                self._eval_round(t, progress)
        return self._finish()

    # ------------------------------------------------------------------
    def _make_round_step(self) -> Callable:
        """One fully-jitted federated round over the functional selector
        core: (params, extras, selector state, telemetry) carry,
        (t, key[, grad key]) input.  Mirrors the host loop op-for-op —
        including the post-aggregation full-update observations the
        CS/DivFL selectors consume — so both drivers produce identical
        participant sets from the same key chain.  The telemetry step
        only READS round values, so with groups disabled its zero-width
        outputs are dead code XLA removes."""
        cfg = self.cfg
        fn = self.selector.fn
        has_extras = bool(self._extras)
        need_losses = "loss_all" in fn.requires
        need_full_sel = "full_sel" in fn.requires
        need_full_all = "full_all" in fn.requires
        lu_v = jax.vmap(self._lu, in_axes=(None, 0, 0, 0, 0, 0, None))
        tel_step, true_ent = self._metrics.step, self._true_ent

        def round_step(carry, xs):
            params, extras, sstate, telc = carry
            if need_full_all:
                t, kr, kg = xs
            else:
                t, kr = xs
            # each phase carries a named scope: HLO metadata only, so
            # the compiled program is the same with or without tracing,
            # and a device trace's operations map back to their phase
            with jax.named_scope("select"):
                k_sel, k_loc = jax.random.split(kr)
                ids, sstate = fn.select(sstate, t, k_sel)
            with jax.named_scope("local"):
                rngs = jax.random.split(k_loc, cfg.num_select)
                decay = jnp.float32(cfg.lr_decay) ** (
                    t // cfg.lr_decay_every)
                ex_sel = (_tree_stack_gather(extras, ids) if has_extras
                          else {})
                params_before = params
                new_params, new_extras, metrics = lu_v(
                    params, ex_sel, self.x[ids], self.y[ids],
                    self.mask[ids], rngs, decay)
                if has_extras:
                    extras = _tree_stack_scatter(extras, ids, new_extras)
            with jax.named_scope("delta_b"):
                bias_updates = head_bias_updates_stacked(params,
                                                         new_params)
            with jax.named_scope("aggregate"):
                params = aggregate_params(new_params)
            losses = full_updates = None
            with jax.named_scope("observe"):
                if need_losses:
                    losses, _ = self._eval_vmapped(params, self.x, self.y,
                                                   self.mask)
                if need_full_all:
                    full_updates = self._grad_all(
                        params, self.x, self.y, self.mask,
                        jax.random.split(kg, cfg.num_clients))
                elif need_full_sel:
                    full_updates = full_sel_updates(params, new_params)
            with jax.named_scope("selector_update"):
                sstate = fn.update(sstate, t, ids, Observations(
                    bias_updates=bias_updates, full_updates=full_updates,
                    losses=losses))
            with jax.named_scope("telemetry"):
                train_loss = jnp.mean(metrics["train_loss"])
                telc, tel = tel_step(telc, TelemetryCtx(
                    t=t, ids=ids, state=sstate, train_loss=train_loss,
                    true_entropy=true_ent, params_before=params_before,
                    params_after=params, bias_updates=bias_updates,
                    lr_scale=decay))
                ent = state_entropies(fn, sstate)
            out = (ids, train_loss, ent, tel)
            return (params, extras, sstate, telc), out

        return round_step

    def _run_scanned(self, progress: bool = False) -> Dict[str, list]:
        cfg = self.cfg
        fn = self.selector.fn
        unmet = fn.requires - _SCANNABLE
        if unmet or not fn.jit_capable:
            raise ValueError(
                f"jit_rounds=True unsupported for selector {fn.name!r} "
                f"(needs host-side {sorted(unmet)})")
        if self._round_step is None:
            self._round_step = self._make_round_step()
        register = self._scan_jit is None and trace_enabled()
        if self._scan_jit is None:
            def scan_segment(carry, xs):
                return jax.lax.scan(self._round_step, carry, xs)
            self._scan_jit = jax.jit(scan_segment)
        carry = (self.params, self._extras, self.selector.state,
                 self._telc)
        # segments of eval_every rounds; evaluation lands after each
        # segment's LAST round (the host loop evals after rounds
        # 0, ee, 2ee, ... — same cadence, one round offset).  Equal
        # segment lengths keep the scanned round_step at one compile.
        seg_len = cfg.eval_every if self.test is not None else cfg.rounds
        need_gk = "full_all" in fn.requires
        t = 0
        with trace_span("fed/run"):
            while t < cfg.rounds:
                n = min(seg_len, cfg.rounds - t)
                with trace_span("fed/keys"):
                    # same key chain as the host loop: kr, then the
                    # grad-poll key
                    keys, gkeys = [], []
                    for _ in range(n):
                        self.rng, kr = jax.random.split(self.rng)
                        keys.append(kr)
                        if need_gk:
                            self.rng, kg = jax.random.split(self.rng)
                            gkeys.append(kg)
                    ts = jnp.arange(t, t + n, dtype=jnp.int32)
                    xs = ((ts, jnp.stack(keys), jnp.stack(gkeys))
                          if need_gk else (ts, jnp.stack(keys)))
                if register:
                    self._register_scan_program(carry, xs)
                    register = False
                t_start = time.perf_counter()
                with trace_span(f"fed/scan_segment[{n}]"):
                    carry, (ids_seg, loss_seg, ent_seg, tel_seg) = \
                        self._scan_jit(carry, xs)
                    jax.block_until_ready(carry)
                # per-SEGMENT wall time: rounds never surface to the
                # host here, so a per-round number would be fiction
                self.history["segment_wall_s"].append(
                    time.perf_counter() - t_start)
                self.history["segment_rounds"].append(n)
                with trace_span("fed/history"):
                    ids_np = np.asarray(ids_seg)
                    loss_np = np.asarray(loss_seg)
                    ent_np = np.asarray(ent_seg)
                    for i in range(n):
                        self.history["round"].append(t + i)
                        self.history["train_loss"].append(
                            float(loss_np[i]))
                        self.history["selected"].append(ids_np[i].tolist())
                        self.history["bias_entropy"].append(
                            ent_np[i].tolist() if ent_np.shape[-1]
                            else None)
                    self._tel_segments.append(jax.tree_util.tree_map(
                        np.asarray, tel_seg))
                t += n
                (self.params, self._extras, self.selector.state,
                 self._telc) = carry
                if self.test is not None:
                    with trace_span("fed/eval"):
                        self._eval_round(t - 1, progress)
        return self._finish()

    def _register_scan_program(self, carry, xs) -> None:
        """Hand the tracing module a thunk that compiles ``scan_segment``
        for these arguments' shapes and returns its optimized HLO text,
        from which a trace's device operations map to their scopes.  It
        holds shapes and a weak reference to the server, no arrays, and
        runs only when a reader asks (the compile cache usually has the
        program by then)."""
        def spec(a):    # the jit cache's own key: no sharding unless
            # committed, so asking for the text finds the run's program
            committed = getattr(a, "committed", False)
            return jax.ShapeDtypeStruct(
                a.shape, a.dtype, weak_type=getattr(a, "weak_type", False),
                sharding=a.sharding if committed else None)

        specs = jax.tree_util.tree_map(spec, (carry, xs))
        server = weakref.ref(self)

        def hlo_text() -> Optional[str]:
            s = server()
            if s is None:
                return None
            return s._scan_jit.lower(*specs).compile().as_text()
        register_program("scan_segment", hlo_text)

    # ------------------------------------------------------------------
    def _eval_round(self, t: int, progress: bool) -> None:
        tl, ta = self._eval(self.params, self.test["x"],
                            self.test["y"], self.test["mask"])
        self.history["test_round"].append(t)
        self.history["test_loss"].append(float(tl))
        self.history["test_acc"].append(float(ta))
        if progress:
            print(f"round {t:4d} loss={self.history['train_loss'][-1]:.4f} "
                  f"test_acc={float(ta):.4f}", flush=True)

    def _finish(self) -> Dict[str, list]:
        self.history["select_seconds"] = self.selector.select_seconds
        self.history["update_seconds"] = self.selector.update_seconds
        # throughput over every timed round, whichever driver ran
        wall = (sum(self.history["segment_wall_s"])
                or sum(self.history["wall_s"]))
        rounds = (sum(self.history["segment_rounds"])
                  or len(self.history["wall_s"]))
        self.history["rounds_per_s"] = rounds / wall if wall else None
        if self._tel_segments:
            self.telemetry = {
                k: np.concatenate([seg[k] for seg in self._tel_segments])
                for k in self._tel_segments[0]}
        return self.history

def rounds_to_accuracy(history: Dict[str, list], target: float
                       ) -> Optional[int]:
    """First round at which test accuracy reached `target` (Table 2)."""
    for r, a in zip(history["test_round"], history["test_acc"]):
        if a >= target:
            return int(r)
    return None
