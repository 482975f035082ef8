"""Device time by program scope (``benchlib/scopes.py``) and the four
readers of the round's phases: the scope table parsed from optimized
HLO text, time counted only inside the scanned program's intervals,
and host phase spans clipped to the traced call."""
import pytest

from benchlib import catalog, scopes, tracefile
from benchlib.tracefile import Event, Trace
from repro.telemetry import trace as program_trace

BODY = "jit(scan_segment)/while/body/closed_call"

#: a scanned round as XLA prints it: a scan loop whose body holds a
#: select branch (a nested merge loop with a copy XLA added), a fusion
#: labelled only by its root, local training, and the scan's own slice
HLO = f"""HloModule jit_scan_segment, is_scheduled=true

%sum (a: f32[], b: f32[]) -> f32[] {{
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.9 = f32[] add(%a, %b), metadata={{op_name="select/cluster/reduce_sum"}}
}}

%fused_computation.1 (param_0: f32[4]) -> f32[4] {{
  %param_0 = f32[4]{{0}} parameter(0)
  ROOT %multiply.1 = f32[4]{{0}} multiply(%param_0, %param_0), metadata={{op_name="{BODY}/local/vmap()/while/body/mul"}}
}}

%merge_body (p.1: (s32[], f32[4,4])) -> (s32[], f32[4,4]) {{
  %p.1 = (s32[], f32[4,4]{{1,0}}) parameter(0)
  %get-tuple-element.1 = f32[4,4]{{1,0}} get-tuple-element(%p.1), index=1
  %copy.3 = f32[4,4]{{1,0:T(8,128)}} copy(%get-tuple-element.1)
  %constant.2 = f32[] constant(0)
  %reduce.7 = f32[] reduce(%copy.3, %constant.2), dimensions={{0,1}}, to_apply=%sum, metadata={{op_name="{BODY}/select/cond/branch_1_fun/cluster/while/body/closed_call/reduce_sum"}}
  %get-tuple-element.2 = s32[] get-tuple-element(%p.1), index=0
  ROOT %tuple.2 = (s32[], f32[4,4]{{1,0}}) tuple(%get-tuple-element.2, %copy.3)
}}

%merge_cond (p.2: (s32[], f32[4,4])) -> pred[] {{
  %p.2 = (s32[], f32[4,4]{{1,0}}) parameter(0)
  %get-tuple-element.3 = s32[] get-tuple-element(%p.2), index=0
  %constant.3 = s32[] constant(8)
  ROOT %compare.1 = pred[] compare(%get-tuple-element.3, %constant.3), direction=LT, metadata={{op_name="{BODY}/select/cond/branch_1_fun/cluster/while/cond/lt"}}
}}

%sweep (q.1: f32[4,4]) -> s32[] {{
  %q.1 = f32[4,4]{{1,0}} parameter(0)
  ROOT %iota.2 = s32[] iota(), iota_dimension=0, metadata={{op_name="{BODY}/select/cond/branch_0_fun/sample/iota"}}
}}

%clustered (q.2: f32[4,4]) -> s32[] {{
  %q.2 = f32[4,4]{{1,0}} parameter(0)
  %constant.4 = s32[] constant(0)
  %tuple.3 = (s32[], f32[4,4]{{1,0}}) tuple(%constant.4, %q.2)
  %while.4 = (s32[], f32[4,4]{{1,0}}) while(%tuple.3), condition=%merge_cond, body=%merge_body, metadata={{op_name="{BODY}/select/cond/branch_1_fun/cluster/while"}}
  ROOT %get-tuple-element.4 = s32[] get-tuple-element(%while.4), index=0
}}

%round (r.1: (s32[], f32[4,4], f32[4])) -> (s32[], f32[4,4], f32[4]) {{
  %r.1 = (s32[], f32[4,4]{{1,0}}, f32[4]{{0}}) parameter(0)
  %get-tuple-element.5 = f32[4,4]{{1,0}} get-tuple-element(%r.1), index=1
  %get-tuple-element.6 = f32[4]{{0}} get-tuple-element(%r.1), index=2
  %get-tuple-element.7 = s32[] get-tuple-element(%r.1), index=0
  %conditional.5 = s32[] conditional(%get-tuple-element.7, %get-tuple-element.5, %get-tuple-element.5), branch_computations={{%sweep, %clustered}}, metadata={{op_name="{BODY}/select/cond"}}
  %fusion.1 = f32[4]{{0}} fusion(%get-tuple-element.6), kind=kLoop, calls=%fused_computation.1
  %dynamic-update-slice.6 = f32[4]{{0}} dynamic-update-slice(%fusion.1, %get-tuple-element.6, %conditional.5), metadata={{op_name="jit(scan_segment)/while/body/dynamic_update_slice"}}
  %add.2 = s32[] add(%get-tuple-element.7, %conditional.5), metadata={{op_name="jit(scan_segment)/while/body/add"}}
  ROOT %tuple.4 = (s32[], f32[4,4]{{1,0}}, f32[4]{{0}}) tuple(%add.2, %get-tuple-element.5, %dynamic-update-slice.6)
}}

%round_cond (r.2: (s32[], f32[4,4], f32[4])) -> pred[] {{
  %r.2 = (s32[], f32[4,4]{{1,0}}, f32[4]{{0}}) parameter(0)
  %get-tuple-element.8 = s32[] get-tuple-element(%r.2), index=0
  %constant.5 = s32[] constant(2)
  ROOT %compare.2 = pred[] compare(%get-tuple-element.8, %constant.5), direction=LT, metadata={{op_name="jit(scan_segment)/while/cond/lt"}}
}}

ENTRY %main.1 (x.1: f32[4,4], y.1: f32[4]) -> (s32[], f32[4,4], f32[4]) {{
  %x.1 = f32[4,4]{{1,0}} parameter(0), metadata={{op_name="x"}}
  %y.1 = f32[4]{{0}} parameter(1), metadata={{op_name="y"}}
  %copy.8 = f32[4,4]{{1,0}} copy(%x.1)
  %constant.6 = s32[] constant(0)
  %tuple.5 = (s32[], f32[4,4]{{1,0}}, f32[4]{{0}}) tuple(%constant.6, %copy.8, %y.1)
  ROOT %while.9 = (s32[], f32[4,4]{{1,0}}, f32[4]{{0}}) while(%tuple.5), condition=%round_cond, body=%round, metadata={{op_name="jit(scan_segment)/while"}}
}}
"""


@pytest.mark.parametrize("op_name, path", [
    (f"{BODY}/select/cond/branch_0_fun/cluster/while/body/closed_call/"
     "jit(searchsorted)/dynamic_slice", "select/cluster"),
    (f"{BODY}/select/strip/cond/branch_1_fun/hics_selection_step_cached/"
     "gram_strip/pallas_call", "select/strip/hics_selection_step_cached/"
                               "gram_strip"),
    (f"{BODY}/select/jit(_threefry_split)/"
     "FederatedServer._make_round_step.<locals>.round_step/while/body/add",
     "select"),
    (f"{BODY}/local/vmap()/while/body/closed_call/mul", "local"),
    (BODY, ""),
    ("jit(scan_segment)/while/body/dynamic_update_slice", ""),
    ("jit(evaluate)/dot_general", ""),
])
def test_scope_path_keeps_the_named_scopes(op_name, path):
    assert scopes.scope_path(op_name) == path


def test_op_scopes_of_a_scanned_round():
    table = scopes.op_scopes(HLO)
    assert table == {
        "copy.8": "",                         # before the loop
        "while.9": "",                        # containers, by their own
        "conditional.5": "select", "while.4": "select/cluster",
        "iota.2": "select/sample",
        "compare.1": "select/cluster",
        "copy.3": "select/cluster",           # XLA's copy: its loop's
        "reduce.7": "select/cluster",
        "fusion.1": "local",                  # its fused root's
        "dynamic-update-slice.6": "",         # the scan's own
        "add.2": "", "compare.2": "",
    }


def _trace():
    """Window 0..200; the scanned program runs twice, the last time over
    the window's end; the eval program between them reuses an op name."""
    ops = [Event("while.9", 10, 50),          # container: 11 of its own
           Event("copy.8", 10, 2),            # unscoped
           Event("iota.2", 12, 3),            # select/sample
           Event("while.4", 15, 10),          # select/cluster: 3 its own
           Event("copy.3", 15, 7),            # select/cluster
           Event("fusion.1", 30, 20),         # local
           Event("dynamic-update-slice.6", 50, 4),
           Event("fusion.1", 100, 30),        # jit_evaluate's own
           Event("copy.3", 190, 20)]          # select/cluster, clipped
    modules = [Event("jit_scan_segment(7)", 10, 50),
               Event("jit_evaluate(8)", 100, 30),
               Event("jit_scan_segment(7)", 185, 40)]
    spans = [Event(tracefile.WINDOW, 0, 200),
             Event("fed/history", -5, 15),    # over the window's start
             Event("fed/history", 70, 10),
             Event("fed/eval", 95, 40),
             Event("np.asarray(jax.Array)", 72, 4),
             Event("fed/history", 195, 30)]   # over its end
    return Trace(ops=[ops], modules=[modules], spans=spans)


def test_scope_seconds_inside_the_scanned_program_only():
    got = scopes.scope_seconds(_trace(), scopes.op_scopes(HLO),
                               (0, 200))
    assert got == pytest.approx({"": 17e-9, "select/sample": 3e-9,
                                 "select/cluster": 20e-9,
                                 "local": 20e-9})


def test_self_times_of_nested_events():
    """A loop's own time is its length less the events directly inside
    it; the own times add up to the union of the intervals."""
    loop, a, inner, b, after = (Event("while.1", 0, 100),
                                Event("fusion.1", 10, 20),
                                Event("while.2", 40, 30),
                                Event("fusion.2", 45, 10),
                                Event("copy.1", 120, 5))
    got = {e.name: t for e, t in scopes.self_times(
        [b, after, inner, a, loop])}
    assert got == {"while.1": 50, "fusion.1": 20, "while.2": 20,
                   "fusion.2": 10, "copy.1": 5}
    assert sum(got.values()) == 105


@pytest.fixture
def program(monkeypatch):
    monkeypatch.setattr(program_trace, "_PROGRAMS",
                        {scopes.PROGRAM: lambda: HLO})


def _rec(tr=None):
    return {"trace": tr or _trace(), "ids": [[0, 1], [2, 3]]}


@pytest.mark.parametrize("metric, ms", [
    ("select.round_device_ms", 23e-9 / 2 * 1e3),
    ("local.round_device_ms", 20e-9 / 2 * 1e3),
    ("driver.history_ms_per_round", (10 + 10 + 5) * 1e-9 / 2 * 1e3),
    ("driver.eval_ms_per_round", 40e-9 / 2 * 1e3),
    ("round.unscoped_share", 100 * 17 / (17 + 3 + 20 + 20)),
])
def test_reader(program, metric, ms):
    assert catalog.reader(metric)(_rec()) == pytest.approx(ms)


@pytest.mark.parametrize("metric", ["select.round_device_ms",
                                    "local.round_device_ms",
                                    "round.unscoped_share"])
def test_device_readers_read_nothing_without_the_program_text(
        monkeypatch, metric):
    monkeypatch.setattr(program_trace, "_PROGRAMS", {})
    assert catalog.reader(metric)(_rec()) is None


@pytest.mark.parametrize("metric", ["select.round_device_ms",
                                    "local.round_device_ms",
                                    "round.unscoped_share"])
def test_device_readers_read_nothing_without_the_scanned_program(
        program, metric):
    tr = _trace()
    tr.modules = [[Event("jit__lambda(7)", e.start, e.dur)
                   for e in tr.modules[0]]]
    assert catalog.reader(metric)(_rec(tr)) is None


@pytest.mark.parametrize("metric", ["driver.history_ms_per_round",
                                    "driver.eval_ms_per_round"])
def test_span_readers_read_nothing_without_the_spans(metric):
    tr = _trace()
    tr.spans = [e for e in tr.spans if not e.name.startswith("fed/")]
    assert catalog.reader(metric)(_rec(tr)) is None


def test_idle_gap_in_a_phase_names_it():
    tr = _trace()
    assert tr.label(74) == "np.asarray(jax.Array) in fed/history"
