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
segments left (a reference to the arrays,
read from the server when it evaluates after the segment; no device
read) before the rounds ``mid_rounds``, so selections inside the call,
at t > 0, are checked too.

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
    """Device references to what one followed call left behind."""
    h0: int
    h1: int
    params: object
    state: object
    mid: list = dataclasses.field(default_factory=list)  # [(t, (Δb, seen))]


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
            s = self.server
            self.followed.append(Followed(h0, self.rounds_done, s.params,
                                          s.selector.state, kept))
            self.following = not starts_clustered

    def _keep_states_before(self, rounds, kept: list) -> None:
        """For the next call only: appends to ``kept`` (t, (Δb, seen))
        as the segment that ends before each round t in ``rounds``
        leaves them.  The server evaluates after every segment; the
        wrapper adds one Python call to each and holds references,
        nothing more."""
        server = self.server
        evaluate = server._eval_round

        def evaluate_and_keep(t, progress):
            evaluate(t, progress)
            if t + 1 in rounds:
                st = server.selector.state
                kept.append((t + 1, (st.delta_b, st.seen)))
        server._eval_round = evaluate_and_keep

    def coverage_done(self) -> bool:
        return int(self.server.selector.state.unseen_count) == 0

    def records(self) -> List[compare.Record]:
        """Host copies of the program's side of each followed call."""
        h = self.server.history
        out = []
        for f in self.followed:
            st = f.state
            pending = np.zeros(self.fed_cfg.num_clients, bool)
            if int(st.stale_fill):
                pending[np.asarray(st.stale_ids)] = True
            mid = [(t, np.asarray(db, np.float64), np.asarray(seen))
                   for t, (db, seen) in f.mid]
            out.append(compare.Record(
                ids=np.asarray(h["selected"][f.h0:f.h1], np.int64),
                loss=np.asarray(h["train_loss"][f.h0:f.h1], np.float64),
                ent=np.asarray(h["bias_entropy"][f.h0:f.h1], np.float64),
                params=reference.to_host(f.params),
                delta_b=np.asarray(st.delta_b, np.float64),
                dist=np.asarray(st.dist_cache, np.float64),
                seen=np.asarray(st.seen),
                fresh=np.asarray(st.seen) & ~pending, mid=mid))
        return out


def follow(cell: catalog.Cell, job_seed: int, data: traffic.Traffic,
           records: List[compare.Record], dtype=None):
    """The reference over the followed calls; returns (refs, θ₀, the
    reference's job, the clients' weights)."""
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
    p0 = reference.to_host(carry[0])
    w = data.sizes.astype(np.float64)
    weights = jnp.asarray(w / w.sum(), jnp.float32)
    refs = []
    for rec in records:
        rng0 = carry[2]
        carry, loss, ent, mid_db = reference.follow_call(
            rj, data.x, data.y, data.mask, carry, rec.ids,
            mid=[t for t, _, _ in rec.mid])
        refs.append(compare.RefCall(
            loss=loss, ent=ent, params=reference.to_host(carry[0]),
            delta_b=np.asarray(carry[1], np.float64), rng0=rng0,
            rng=carry[2], mid_delta_b=mid_db))
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

    records = job.records()
    wl = cell.workload
    k, r = int(wl["num_select"]), int(wl["job_rounds"])
    data, job_seed = job.data, job.prog_seed
    del job, probe                   # the program's state goes first
    gc.collect()
    r0 = time.perf_counter()
    values = check(cell, job_seed, data, records)
    reference_s = time.perf_counter() - r0
    limits = {n: float(v) for n, v in wl["limits"].items()}
    ok = compare.verdict(values, limits)
    followed = sum(len(x.ids) for x in records)

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
    out = {"correct": bool(ok), "attempted": followed,
           "failed": 0 if ok else followed, "metrics": metrics,
           "device": device}
    if trace:
        out["breakdown"] = layer["breakdown"]
    info = {"setup_s": setup_s, "compiles_in_window": compiles.count,
            "rounds_followed": followed, "reference_s": reference_s}
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
          records: List[compare.Record]) -> Dict[str, float]:
    """The comparison's numbers: the reference over the followed calls
    against what the program recorded."""
    refs, p0, rj, weights = follow(cell, job_seed, data, records)
    return compare.numbers(
        records, refs, p0, reference.reference_distance,
        expected_selections(rj, weights, records, refs))


def control_check(cell: catalog.Cell, job_seed: int,
                  data: traffic.Traffic,
                  records: List[compare.Record]) -> Dict[str, float]:
    """The same numbers for the control: the reference computed in
    bfloat16 (its Eq. 9 Gram too) put in the program's place, on the
    program's cohorts, against the float32 reference."""
    refs, p0, rj, weights = follow(cell, job_seed, data, records)
    low, _, lj, _ = follow(cell, job_seed, data, records,
                           dtype=jnp.bfloat16)
    n = int(cell.workload["num_clients"])
    stand_in = []
    for i, (c, rec) in enumerate(zip(low, records)):
        ids = rec.ids.copy()   # the control's own choices, from its state
        if i:
            ids[0] = reference.select_at(lj, low[i - 1].delta_b,
                                         records[i - 1].seen, weights,
                                         c.rng0)
        mid = []
        for t, _, seen in rec.mid:
            mid.append((t, c.mid_delta_b[t], seen))
            ids[t] = reference.select_at(lj, c.mid_delta_b[t], seen,
                                         weights, c.rng0, t)
        stand_in.append(compare.Record(
            ids=ids, loss=c.loss, ent=c.ent, params=c.params,
            delta_b=c.delta_b, dist=reference.control_distance(c.delta_b),
            seen=rec.seen, fresh=np.ones(n, bool), mid=mid))
    return compare.numbers(
        stand_in, refs, p0, reference.reference_distance,
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
