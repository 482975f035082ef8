"""One run of one cell: set-up, the measured window (or the traced
stretch), then the comparison with the reference.

Timed path: the program's scanned federated driver as a user drives it,
``FederatedServer(..., FedConfig(jit_rounds=True))`` over the model and
the data of the configuration's family (``catalog.family``), and successive
``server.run()`` calls, each a job of ``job_rounds`` rounds that goes on
from where the last one stopped (parameters, selector state and the
distance cache carry over).  Set-up builds the cell's data from the
seed, builds the server, and makes the calls that compile the program
and finish the coverage sweep, so every timed round is a clustered one.

The reference follows the run from the seed: every round of the calls
up to and including the first that starts with a clustered round.  In
that call the job also keeps the selector state that the program's
segments left (host copies of Δb and the seen set, made when the server
evaluates after the segment) before the rounds ``mid_rounds``, so
selections inside the call, at t > 0, are checked too.

A run holds no device array of the program's state past the call that
made it: what the comparison reads of a followed call is copied to the
host as the call returns (``Followed``), so a program that donates its
carry to the next call loses nothing the comparison needs.  Neither
side keeps a float64 parameter set: each keeps the norm of every leaf's
change from θ₀, and θ₀ is kept once, as float32.

Set-up ends with a full collection and ``gc.freeze()``: the objects
set-up made (traced programs, the data's host copies) stay out of the
collector's later passes, so a full collection inside the window walks
only what the window made.  The window's own garbage is collected as
ever; the collector's pauses are counted by call on standard error.
"""
from __future__ import annotations

import dataclasses
import gc
import json
import sys
import tempfile
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from benchlib import catalog, compare, peaks, reference, traffic
from benchlib import tracefile
from repro.fed.client import LocalSpec
from repro.fed.server import FedConfig, FederatedServer
from repro.launch.cache import enable_compile_cache

#: calls of the live selector's jitted ``select`` the traced run makes
SELECT_CALLS = 20


class NoAccelerator(RuntimeError):
    pass


def check_devices(chips: int):
    """The run's devices; fails unless JAX sees ``chips`` TPU chips."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoAccelerator(
            f"no TPU: JAX's devices are {devs[0].platform} "
            f"({devs[0].device_kind}); the benchmark never falls back")
    if len(devs) < chips:
        raise NoAccelerator(f"the cell asks for {chips} chips, JAX sees "
                            f"{len(devs)}")
    return devs[:chips]


def device_entry(devs) -> dict:
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


@dataclasses.dataclass
class Followed:
    """What one followed call left behind, copied to the host as it
    returned (``mid`` as each of its segments did): numpy arrays alone."""
    ids: np.ndarray          # (R, K)
    loss: np.ndarray         # (R,)
    ent: np.ndarray          # (R, N)
    params: dict             # global parameters: float32 leaves
    delta_b: np.ndarray      # (N, C)
    dist: np.ndarray         # (N, N) cache
    seen: np.ndarray         # (N,) bool
    fresh: np.ndarray        # (N,) bool: seen, no refresh pending
    mid: list                # [(t, Δb, seen)] before round t

    def record(self, p0) -> compare.Record:
        """The comparison's program side, each leaf's change from
        ``p0`` (float32 host leaves) taken one leaf at a time."""
        kept = {f.name: getattr(self, f.name)
                for f in dataclasses.fields(self) if f.name != "params"}
        return compare.Record(norms=compare.change_norms(self.params, p0),
                              **kept)


class Job:
    """The server of one cell and the calls made on it."""

    def __init__(self, cell: catalog.Cell, seed: int,
                 selector_kw: Optional[dict] = None):
        wl = cell.workload
        family = catalog.family(cell.config)
        self.prog_seed = traffic.split_seed(seed)[1]
        self.data = family.make_traffic(wl, seed)
        init, apply = family.program_model(cell.config, wl)
        loc = wl["local"]
        self.fed_cfg = FedConfig(
            num_clients=int(wl["num_clients"]),
            num_select=int(wl["num_select"]),
            rounds=int(wl["job_rounds"]), selector=wl["selector"],
            selector_kw=selector_kw,
            local=LocalSpec(algo=loc["algo"], optimizer=loc["optimizer"],
                            lr=float(loc["lr"]), epochs=int(loc["epochs"]),
                            batch_size=int(loc["batch_size"])),
            eval_every=int(wl["eval_every"]), seed=self.prog_seed,
            jit_rounds=True)
        self.server = FederatedServer(init, apply, self.fed_cfg,
                                      self.data.x, self.data.y,
                                      self.data.mask, test=self.data.test)
        self.followed: List[Followed] = []
        self.following = True
        ee, r = self.fed_cfg.eval_every, self.fed_cfg.rounds
        #: the rounds inside the first clustered call whose selections
        #: are checked: the segment boundaries nearest below a quarter,
        #: a half and three quarters of the call
        self.mid_rounds = sorted({r * q // 4 // ee * ee
                                  for q in (1, 2, 3)} - {0})

    @property
    def rounds_done(self) -> int:
        return len(self.server.history["round"])

    def call(self) -> None:
        """One ``server.run()``: a job of ``job_rounds`` rounds.  Calls
        are followed up to and including the first that starts with a
        clustered round, whose first selection is then checked against
        the reference's, made from the state the call before it left."""
        starts_clustered = self.coverage_done()
        h0 = self.rounds_done
        kept = []
        if self.following and starts_clustered:
            self._keep_states_before(self.mid_rounds, kept)
        try:
            self.server.run()
        finally:
            self.server.__dict__.pop("_eval_round", None)
        if self.following:
            self.followed.append(self._host_copy(h0, kept))
            self.following = not starts_clustered

    def _host_copy(self, h0: int, mid: list) -> Followed:
        """The call that began at round ``h0`` as it left the server:
        its history, and host copies of the parameters (leaf by leaf)
        and of the selector's Δb, distance cache and seen set."""
        s = self.server
        h, st = s.history, s.selector.state
        pending = np.zeros(self.fed_cfg.num_clients, bool)
        if int(st.stale_fill):
            pending[np.asarray(st.stale_ids)] = True
        seen = np.array(st.seen)
        return Followed(
            ids=np.asarray(h["selected"][h0:], np.int64),
            loss=np.asarray(h["train_loss"][h0:], np.float64),
            ent=np.asarray(h["bias_entropy"][h0:], np.float64),
            params=jax.tree_util.tree_map(np.array, s.params),
            delta_b=np.asarray(st.delta_b, np.float64),
            dist=np.array(st.dist_cache), seen=seen,
            fresh=seen & ~pending, mid=mid)

    def _keep_states_before(self, rounds, kept: list) -> None:
        """For the next call only: appends to ``kept`` (t, Δb, seen),
        host copies of the two as the segment that ends before each
        round t in ``rounds`` leaves them.  The server evaluates after
        every segment; the wrapper adds one Python call to each."""
        server = self.server
        evaluate = server._eval_round

        def evaluate_and_keep(t, progress):
            evaluate(t, progress)
            if t + 1 in rounds:
                st = server.selector.state
                kept.append((t + 1, np.asarray(st.delta_b, np.float64),
                             np.array(st.seen)))
        server._eval_round = evaluate_and_keep

    def coverage_done(self) -> bool:
        return int(self.server.selector.state.unseen_count) == 0


def follow(cell: catalog.Cell, job_seed: int, data: traffic.Traffic,
           followed: List[Followed], dtype=None, p0=None):
    """The reference over the followed calls; returns (refs, θ₀, the
    reference's job, the clients' weights).  θ₀ is kept once, as float32
    host leaves; ``p0`` gives it instead (the control's changes are
    taken from the float32 reference's θ₀).  Each call donates the carry
    to the next, and of a call's parameters only each leaf's change
    norm comes to the host, one leaf at a time."""
    wl = cell.workload
    block = wl.get("reference_cohort_block")
    rj = reference.Job(
        cfg=cell.config, family=catalog.family(cell.config),
        num_clients=int(wl["num_clients"]),
        num_select=int(wl["num_select"]),
        job_rounds=int(wl["job_rounds"]), lr=float(wl["local"]["lr"]),
        epochs=int(wl["local"]["epochs"]),
        batch_size=int(wl["local"]["batch_size"]),
        dtype=dtype or jnp.float32,
        cohort_block=None if block is None else int(block))
    carry = reference.start(rj, job_seed, int(cell.config["num_classes"]))
    if p0 is None:
        p0 = jax.tree_util.tree_map(lambda a: np.array(a, np.float32),
                                    carry[0])
    w = data.sizes.astype(np.float64)
    weights = jnp.asarray(w / w.sum(), jnp.float32)
    refs = []
    for f in followed:
        rng0 = np.array(carry[2])
        carry, loss, ent, mid_db = reference.follow_call(
            rj, data.x, data.y, data.mask, carry, f.ids,
            mid=[t for t, _, _ in f.mid])
        refs.append(compare.RefCall(
            loss=loss, ent=ent, norms=compare.change_norms(carry[0], p0),
            delta_b=np.asarray(carry[1], np.float64), rng0=rng0,
            mid_delta_b=mid_db))
    return refs, p0, rj, weights


def expected_selections(rj, weights, records: List[compare.Record],
                        refs: List[compare.RefCall]) -> list:
    """The reference's selection of each checked round, as (call, round,
    ids): the first round of each followed call after the first, from
    the state the call before it left in ``records`` (Δb and the seen
    set), and each round t a call kept a state for (``mid``), from that
    state; each with that round's key."""
    out = []
    for i, (rec, ref) in enumerate(zip(records, refs)):
        if i:
            prev = records[i - 1]
            out.append((i, 0, reference.select_at(
                rj, prev.delta_b, prev.seen, weights, ref.rng0)))
        for t, db, seen in rec.mid:
            out.append((i, t, reference.select_at(
                rj, db, seen, weights, ref.rng0, t)))
    return out


class GcPauses:
    """The cyclic collector's pauses inside a ``with`` block, in seconds,
    as (generation, seconds) in the order they came."""

    def __init__(self):
        self.pauses: List[tuple] = []
        self._t0 = None

    def _on(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.pauses.append((info["generation"],
                                time.perf_counter() - self._t0))
            self._t0 = None

    def seconds(self) -> float:
        return sum(s for _, s in self.pauses)

    def __enter__(self):
        gc.callbacks.append(self._on)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._on)
        return False


class CompileCounter:
    """Counts XLA compilations inside a ``with`` block."""

    def __init__(self):
        self.count = 0

    def _on(self, event: str, _secs: float, **_kw) -> None:
        if event.endswith("backend_compile_duration"):
            self.count += 1

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._on)
        return False


def run(cell_name: str, seed: int, seconds: float, trace: bool,
        t_start: float, require_tpu: bool = True,
        root: Path = catalog.ROOT, cache: bool = True,
        patch: Optional[Callable] = None) -> dict:
    """One run; returns the result line's object.  ``require_tpu``,
    ``cache`` and ``patch`` (called with the built job before its first
    call) are for the benchmark's own tests on the CPU."""
    cell = catalog.cell(cell_name, root=root)
    chips = int(cell.entry["chips"])
    devs = check_devices(chips) if require_tpu else jax.devices()[:chips]
    if cache:
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    job = Job(cell, seed)
    if patch is not None:
        patch(job)
    job.call()                       # compiles, starts the sweep
    while not job.coverage_done():
        job.call()
    probe = None
    if trace:
        probe = _select_probe(job)
        probe()                      # compiles the probe
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_start

    with CompileCounter() as compiles, GcPauses() as pauses:
        if trace:
            layer = _traced(job, probe, cell)
        else:
            w0 = time.perf_counter()
            ends, gc_s = [], []
            while not ends or ends[-1] - w0 < seconds:
                job.call()
                ends.append(time.perf_counter())
                gc_s.append(pauses.seconds() - sum(gc_s))
            window = ends[-1] - w0
            calls = len(ends)
    device = device_entry(devs)
    gc.unfreeze()

    wl = cell.workload
    k, r = int(wl["num_select"]), int(wl["job_rounds"])
    followed, data, job_seed = job.followed, job.data, job.prog_seed
    del job, probe                   # the program's state goes first
    gc.collect()
    r0 = time.perf_counter()
    values = check(cell, job_seed, data, followed)
    reference_s = time.perf_counter() - r0
    limits = {n: float(v) for n, v in wl["limits"].items()}
    ok = compare.verdict(values, limits)
    rounds = sum(len(f.ids) for f in followed)

    if trace:
        metrics = {m["name"]: {"value": layer["metrics"][m["name"]],
                               "unit": m["unit"]}
                   for m in cell.per_layer
                   if layer["metrics"].get(m["name"]) is not None}
        device.update(busy_s=layer["busy_s"], window_s=layer["window_s"])
    else:
        e2e = {
            "updates_per_s": {"value": calls * r * k / window,
                              "unit": "updates/s"},
            "peak_hbm_mb": {"value": device["memory_peak_bytes"] / 1e6,
                            "unit": "MB"},
            "setup_s": {"value": setup_s, "unit": "s"},
        }
        metrics = {m["name"]: e2e[m["name"]] for m in cell.end_to_end}
    out = {"correct": bool(ok), "attempted": rounds,
           "failed": 0 if ok else rounds, "metrics": metrics,
           "device": device}
    if trace:
        out["breakdown"] = layer["breakdown"]
    info = {"setup_s": setup_s, "compiles_in_window": compiles.count,
            "rounds_followed": rounds, "reference_s": reference_s}
    if trace:
        info.update(layer["info"])
    else:
        info.update(window_s=window, calls=calls, call_s=list(
            np.diff([w0] + ends)), call_gc_s=gc_s,
            gc_max_s=max((p for _, p in pauses.pauses), default=0.0),
            gc_full=sum(g == 2 for g, _ in pauses.pauses))
    print(json.dumps({"info": info}), file=sys.stderr)
    out["checks"] = compare.checks_entry(values, limits)
    return out


def check(cell: catalog.Cell, job_seed: int, data: traffic.Traffic,
          followed: List[Followed]) -> Dict[str, float]:
    """The comparison's numbers: the reference over the followed calls
    against what the program left in them."""
    refs, p0, rj, weights = follow(cell, job_seed, data, followed)
    records = [f.record(p0) for f in followed]
    return compare.numbers(
        records, refs, reference.reference_distance,
        expected_selections(rj, weights, records, refs))


def control_check(cell: catalog.Cell, job_seed: int,
                  data: traffic.Traffic,
                  followed: List[Followed]) -> Dict[str, float]:
    """The same numbers for the control: the reference computed in
    bfloat16 (its Eq. 9 Gram too) put in the program's place, on the
    program's cohorts, against the float32 reference."""
    refs, p0, rj, weights = follow(cell, job_seed, data, followed)
    low, _, lj, _ = follow(cell, job_seed, data, followed,
                           dtype=jnp.bfloat16, p0=p0)
    n = int(cell.workload["num_clients"])
    stand_in = []
    for i, (c, f) in enumerate(zip(low, followed)):
        ids = f.ids.copy()     # the control's own choices, from its state
        if i:
            ids[0] = reference.select_at(lj, low[i - 1].delta_b,
                                         followed[i - 1].seen, weights,
                                         c.rng0)
        mid = []
        for t, _, seen in f.mid:
            mid.append((t, c.mid_delta_b[t], seen))
            ids[t] = reference.select_at(lj, c.mid_delta_b[t], seen,
                                         weights, c.rng0, t)
        stand_in.append(compare.Record(
            ids=ids, loss=c.loss, ent=c.ent, norms=c.norms,
            delta_b=c.delta_b, dist=reference.control_distance(c.delta_b),
            seen=f.seen, fresh=np.ones(n, bool), mid=mid))
    return compare.numbers(
        stand_in, refs, reference.reference_distance,
        expected_selections(rj, weights, stand_in, refs))


def _select_probe(job: Job) -> Callable:
    """``SELECT_CALLS`` calls of the live selector's jitted ``select`` on
    the state the last update left (its stale ring pending), so each
    refreshes the strip, clusters and samples as a round's select does."""
    fn = jax.jit(job.server.selector.fn.select)
    state = job.server.selector.state
    key = jax.random.PRNGKey(0)
    t = jnp.int32(0)

    def probe(n: int = 1):
        for _ in range(n):
            ids, _ = fn(state, t, key)
            ids.block_until_ready()
    return probe


def _traced(job: Job, probe: Callable, cell: catalog.Cell) -> dict:
    """The traced stretch: one call of the timed path, then the select
    probe; reduces the trace to the cell's per-layer metrics."""
    h0 = job.rounds_done
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0      # spans only; no frame per call
    with tempfile.TemporaryDirectory() as tmp:
        with jax.profiler.trace(tmp, profiler_options=opts):
            with jax.profiler.TraceAnnotation(tracefile.WINDOW):
                job.call()
            with jax.profiler.TraceAnnotation(tracefile.SELECT):
                probe(SELECT_CALLS)
            t_stop = time.perf_counter()
        t_load = time.perf_counter()
        tr = tracefile.load(tmp)
        t_done = time.perf_counter()
    ids = np.asarray(job.server.history["selected"][h0:], np.int64)
    rec = {
        "trace": tr, "cell": cell, "ids": ids,
        "sizes": job.data.sizes, "cap": int(job.data.x.shape[1]),
        "select_calls": SELECT_CALLS,
        "device_kind": jax.devices()[0].device_kind,
    }
    metrics = {m["name"]: catalog.reader(m["name"])(rec)
               for m in cell.per_layer}
    return {"metrics": metrics, "busy_s": tr.busy_s(tr.window),
            "window_s": tr.window_s, "breakdown": tr.breakdown(tr.window),
            "info": {"traced_rounds": int(len(ids)),
                     "gram_strip_bound": _strip_bound(cell, rec),
                     "trace_stop_s": t_load - t_stop,
                     "trace_load_s": t_done - t_load,
                     "trace_ops": sum(len(d) for d in tr.ops),
                     "readers_s": time.perf_counter() - t_done}}


def _strip_bound(cell: catalog.Cell, rec: dict) -> str:
    """Which bound sets the strip kernel's least time."""
    wl = cell.workload
    counts = peaks.gram_strip_counts(int(wl["num_clients"]),
                                     int(wl["num_select"]),
                                     int(cell.config["num_classes"]))
    return peaks.roofline_seconds(counts,
                                  peaks.peak(rec["device_kind"]))["bound"]
