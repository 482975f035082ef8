"""driver.history_ms_per_round: host time per round in which the
scanned driver reads a segment's results back and appends them to the
history.

The union of the program's ``fed/history`` host spans inside the traced
call, over its rounds.  Needs the program's spans (``REPRO_TRACE=1``);
without them, nothing.
"""


def read(rec):
    tr = rec["trace"]
    t, rounds = tr.span_time("fed/history", tr.window), len(rec["ids"])
    if t <= 0.0 or rounds == 0:
        return None
    return t / rounds * 1e3
