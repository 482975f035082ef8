"""The scanned driver labels its work for a profiler trace: host phase
spans (``fed/...``) with ``REPRO_TRACE=1``, and named scopes in the
compiled round, whose optimized HLO text it hands over on request."""
import dataclasses
import gc
import glob
import os
import re

import jax
import pytest

from repro.fed import ExperimentSpec, LocalSpec, build
from repro.telemetry import trace

PHASES = ("select", "local", "delta_b", "aggregate", "selector_update")
BODY = "jit(scan_segment)/while/body/"


def _spec(rounds=6, eval_every=3):
    return ExperimentSpec(
        arch="paper-mlp", num_clients=12, num_select=3, rounds=rounds,
        alphas=(0.05, 5.0), selector="hics",
        local=LocalSpec(algo="fedavg", optimizer="sgd", lr=0.1, epochs=1,
                        batch_size=32),
        samples_train=400, samples_test=120, eval_every=eval_every,
        seed=0, jit_rounds=True)


@pytest.fixture
def tracing(monkeypatch):
    monkeypatch.setattr(trace, "_ENABLED", True)
    monkeypatch.setattr(trace, "_PROGRAMS", {})


@pytest.fixture
def not_tracing(monkeypatch):
    monkeypatch.setattr(trace, "_ENABLED", False)
    monkeypatch.setattr(trace, "_PROGRAMS", {})


def _fed_spans(server, tmp_path):
    """The ``fed/`` host spans of one ``server.run()`` in start order, as
    (name, start, end)."""
    from jax.profiler import ProfileData
    with jax.profiler.trace(str(tmp_path)):
        server.run()
    path = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                     recursive=True)[0]
    spans = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            spans += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                      for e in line.events if e.name.startswith("fed/")]
    return sorted(spans, key=lambda s: (s[1], -s[2]))


def test_phase_spans_inside_the_call_in_loop_order(tracing, tmp_path):
    server, _ = build(_spec())
    spans = _fed_spans(server, tmp_path)
    assert spans[0][0] == "fed/run"
    _, lo, hi = spans[0]
    assert all(lo <= a and b <= hi for _, a, b in spans[1:])
    segment = ["fed/keys", "fed/scan_segment[3]", "fed/history",
               "fed/eval"]
    assert [name for name, _, _ in spans[1:]] == segment * 2
    ends = [b for _, _, b in spans[1:]]
    starts = [a for _, a, _ in spans[1:]]
    assert all(e <= s for e, s in zip(ends, starts[1:]))   # no overlap


def test_no_spans_and_no_program_with_tracing_off(not_tracing, tmp_path):
    server, _ = build(_spec())
    assert _fed_spans(server, tmp_path) == []
    assert trace.program_text("scan_segment") is None


def _scan_body(text: str):
    """The instructions of the scan loop's body computation, as
    (opcode, op_name) pairs."""
    comps, entry, current = {}, None, None
    for line in text.splitlines():
        head = re.match(r"^(ENTRY )?%(\S+) .*\{$", line)
        if head:
            current = head.group(2)
            comps[current] = []
            entry = current if head.group(1) else entry
            continue
        ins = re.match(r"^\s+(?:ROOT )?%\S+ = (.*)$", line)
        if ins and current:
            comps[current].append(ins.group(1))
    loop = next(i for i in comps[entry] if " while(" in i
                and 'op_name="jit(scan_segment)/while"' in i)
    body = re.search(r"body=%([^\s,]+)", loop).group(1)
    out = []
    for ins in comps[body]:
        op = re.search(r"(?<=\s)([a-z][a-z0-9-]*)\(", " " + ins).group(1)
        meta = re.search(r'op_name="([^"]*)"', ins)
        out.append((op, meta.group(1) if meta else ""))
    return out


def test_program_text_carries_the_round_scopes(tracing):
    server, _ = build(_spec())
    server.run()
    text = trace.program_text("scan_segment")
    assert text.startswith("HloModule jit_scan_segment")
    for phase in PHASES:
        assert re.search(rf'op_name="{re.escape(BODY)}(closed_call/)?'
                         rf'{phase}/', text), phase
    for inner in ("strip", "cluster", "sample"):     # the select's own
        assert re.search(rf"/select/(cond/branch_\d+_fun/)?{inner}/",
                         text), inner
    free = {"parameter", "constant", "get-tuple-element", "tuple",
            "bitcast"}
    # XLA's own copies carry no metadata: only what the program traced
    work = [name for op, name in _scan_body(text)
            if op not in free and name]
    scoped = [n for n in work if n.startswith(BODY) and re.match(
        r"(closed_call/)?(%s)/" % "|".join(PHASES + ("observe",
                                                      "telemetry")),
        n[len(BODY):])]
    assert len(scoped) / len(work) >= 0.9, sorted(set(work) - set(scoped))


def test_program_text_reuses_the_compiled_program(tracing):
    """Asking for the text after the run finds the run's executable in
    JAX's caches: no second compile."""
    server, _ = build(_spec())
    server.run()
    compiles = []

    def on(event, _secs, **_kw):
        if event.endswith("backend_compile_duration"):
            compiles.append(event)
    jax.monitoring.register_event_duration_secs_listener(on)
    try:
        assert trace.program_text("scan_segment")
    finally:
        jax.monitoring.unregister_event_duration_listener(on)
    assert compiles == []


def test_program_text_is_none_once_the_server_is_gone(tracing):
    server, _ = build(_spec())
    server.run()
    del server
    gc.collect()
    assert trace.program_text("scan_segment") is None


def test_scopes_do_not_change_the_trajectory(tracing):
    """Named scopes are metadata: a traced run selects and trains as
    the host loop does, round for round."""
    scanned, _ = build(_spec())
    host, _ = build(dataclasses.replace(_spec(), jit_rounds=False))
    assert scanned.run()["selected"] == host.run()["selected"]
