"""driver.eval_ms_per_round: host time per round in which the scanned
driver evaluates the global model on the test set between segments.

The union of the program's ``fed/eval`` host spans inside the traced
call, over its rounds.  Needs the program's spans (``REPRO_TRACE=1``);
without them, nothing.
"""


def read(rec):
    tr = rec["trace"]
    t, rounds = tr.span_time("fed/eval", tr.window), len(rec["ids"])
    if t <= 0.0 or rounds == 0:
        return None
    return t / rounds * 1e3
