"""local.round_device_ms: device time of the cohort's local training
inside the traced call's rounds, per round.

The operations of the program's scanned round under its ``local``
scope (the cohort's gathers, the vmapped local epochs, the extras
scatter), and the own time of its loops, counted inside the
``jit_scan_segment`` program's intervals (``benchlib/scopes.py``).  Needs the program's scope table
(``REPRO_TRACE=1``); without it, nothing.
"""
from benchlib import scopes


def read(rec):
    return scopes.round_device_ms(rec, "local")
