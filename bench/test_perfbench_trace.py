"""The trace reduction: busy and idle time, per-operation and
per-program time, and the attribution of idle gaps to host spans."""
import gzip
import importlib.util
from pathlib import Path

import pytest

from benchlib import catalog, tracefile
from benchlib.tracefile import Event, Trace


def _trace():
    # window 0..100; device ops with an overlap, a nested op and a gap
    ops = [Event("fusion.1", 10, 20),      # 10-30
           Event("fusion.2", 25, 15),      # 25-40 overlaps .1
           Event("_gram_row_kernel", 50, 5),   # 50-55
           Event("_gram_row_kernel", 70, 7),   # 70-77
           Event("fusion.1", 95, 10)]      # 95-105, clipped at 100
    modules = [Event("jit_select(1)", 48, 10), Event("jit_select(2)", 68, 12),
               Event("jit_scan(3)", 5, 40)]
    spans = [Event(tracefile.WINDOW, 0, 100),
             Event("fed/scan_segment[5]", 5, 40),
             Event("host work", 40, 30),
             Event(tracefile.SELECT, 45, 51)]
    return Trace(ops=[ops], modules=[modules], spans=spans)


def test_busy_is_the_union_of_intervals_inside_the_window():
    tr = _trace()
    # 10-40 (30) + 50-55 (5) + 70-77 (7) + 95-100 (5) = 47 ns
    assert tr.busy_intervals(0, 0, 100) == [(10, 40), (50, 55), (70, 77),
                                            (95, 100)]
    assert tr.busy_s(tr.window) == pytest.approx(47e-9)
    assert tr.window_s == pytest.approx(100e-9)


def test_per_kernel_and_per_program_time():
    tr = _trace()
    t, n = tr.op_time(lambda s: "gram_row" in s)
    assert (n, t) == (2, pytest.approx(12e-9))
    t, n = tr.module_time(lambda s: "select" in s, tr.span(tracefile.SELECT))
    assert (n, t) == (2, pytest.approx(22e-9))


def test_span_time_is_a_union_clipped_to_the_window():
    tr = _trace()
    assert tr.span_time("fed/scan_segment", tr.window) == pytest.approx(40e-9)
    assert tr.span_time("fed/scan_segment", (20, 30)) == pytest.approx(10e-9)


def test_idle_gaps_carry_the_host_span_they_fell_in():
    bd = _trace().breakdown(_trace().window)
    names = [name for name, _ in bd["device_ops"]]
    assert names[0] == "fusion.1"          # 20 + 10 ns, beyond .2
    gaps = bd["idle_gaps"]
    # gaps: 0-10 (10), 40-50 (10), 55-70 (15), 77-95 (18)
    assert [round(g * 1e9) for _, g in gaps] == [18, 15, 10, 10]
    assert gaps[0][0] == tracefile.SELECT      # 77-95: innermost is select
    # 55-70, midpoint 62.5: innermost span, then the named one around it
    assert gaps[1][0] == "host work in bench/select"
    assert {g[0] for g in gaps[2:]} == {"fed/scan_segment[5]",
                                        "host work in fed/scan_segment[5]"}


def test_missing_window_span_is_an_error():
    tr = Trace(ops=[[]], modules=[[]], spans=[])
    with pytest.raises(KeyError):
        tr.window


# --- a recorded trace: 2 then 1 calls of cnn-paper-hics's jitted
# --- select on a TPU v5e, a 2 ms host sleep between them -----------------

RECORDED = Path(__file__).resolve().parent / "testdata" / \
    "select_probe.xplane.pb.gz"


@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData
    pd = ProfileData.from_serialized_xspace(gzip.open(RECORDED).read())
    return pd, tracefile.from_profile(pd)


def _raw_ops(pd):
    plane = pd.find_plane_with_name("/device:TPU:0")
    line = [ln for ln in plane.lines if ln.name == tracefile.OPS_LINE][0]
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for e in line.events]


def test_recorded_busy_time_matches_a_sweep_over_raw_events(recorded):
    pd, tr = recorded
    lo, hi = tr.window
    edges = []
    for _, a, b in _raw_ops(pd):
        a, b = max(a, lo), min(b, hi)
        if b > a:
            edges += [(a, 1), (b, -1)]
    edges.sort()
    busy, depth, last = 0.0, 0, None
    for t, step in edges:
        if depth > 0:
            busy += t - last
        depth += step
        last = t
    assert tr.busy_s(tr.window) == pytest.approx(busy * 1e-9, rel=1e-9)
    assert 0.0 < tr.busy_s(tr.window) < tr.window_s


def test_recorded_strip_kernel_time(recorded):
    pd, tr = recorded
    spec = importlib.util.spec_from_file_location(
        "strip_reader", Path(__file__).resolve().parent / "metrics"
        / "kernels.gram_strip_roofline.py")
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    # N=50 clients, K=5: the strip is f32[8,56] (rows and columns padded)
    raw = [(b - a) for name, a, b in _raw_ops(pd)
           if 'custom_call_target="tpu_custom_call"' in name
           and " = f32[8,56]" in name]
    strips = {n for n, hlo in tr.custom_calls.items() if reader.is_strip(hlo)}
    t, n = tr.op_time(lambda name: name in strips)
    assert n == len(raw) == 3                  # one per select call
    assert t == pytest.approx(sum(raw) * 1e-9)
    cell = catalog.cell("cnn-paper-hics")
    share = reader.read({"trace": tr, "cell": cell,
                         "device_kind": "TPU v5 lite"})
    assert 0.0 < share <= 100.0


def test_recorded_gap_is_attributed_to_the_host_span(recorded):
    _, tr = recorded
    bd = tr.breakdown(tr.window)
    name, seconds = bd["idle_gaps"][0]
    assert name == "bench/host_gap" and seconds >= 0.002
    assert len(bd["device_ops"]) == 10
    t, n = tr.module_time(lambda s: "select" in s, tr.span(tracefile.SELECT))
    assert n == 2 and t > 0.0
