"""select.device_ms: device time of one call of the live selector's
jitted ``select``.

After the traced call the benchmark calls the server's
``selector.fn.select`` (jitted) repeatedly on the state the last round's
update left, its stale rows pending, inside the ``bench/select`` span:
each call refreshes the distance strip, clusters and samples as a
round's selection does.  The device time of those programs, per call.
"""
from benchlib import tracefile


def read(rec):
    tr = rec["trace"]
    t, n = tr.module_time(lambda name: "select" in name,
                          tr.span(tracefile.SELECT))
    if n == 0:
        return None
    return t / n * 1e3
