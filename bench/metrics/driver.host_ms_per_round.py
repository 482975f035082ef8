"""driver.host_ms_per_round: host time per round outside the program's
scan segments.

The traced call's length on the trace's clock, less the union of the
program's ``fed/scan_segment[...]`` host spans inside it (each span ends
when its segment's results are ready), divided by the call's rounds:
the host work between segments (key splits, history, the eval sync).
Needs the program's spans (``REPRO_TRACE=1``); without them, nothing.
"""


def read(rec):
    tr = rec["trace"]
    inside = tr.span_time("fed/scan_segment", tr.window)
    rounds = len(rec["ids"])
    if inside <= 0.0 or rounds == 0:
        return None
    return (tr.window_s - inside) / rounds * 1e3
