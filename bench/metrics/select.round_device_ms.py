"""select.round_device_ms: device time of the selection inside the
traced call's rounds, per round.

The operations of the program's scanned round under its ``select``
scope (strip refresh, clustering, sampling), and the own time of its
loops and branches (Ward's merge loop), counted inside the
``jit_scan_segment`` program's intervals (``benchlib/scopes.py``).
Needs the program's scope table (``REPRO_TRACE=1``); without it,
nothing.
"""
from benchlib import scopes


def read(rec):
    return scopes.round_device_ms(rec, "select")
