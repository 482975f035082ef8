"""round.unscoped_share: the share of the scanned round's device time
that no named scope of the program holds.

Of the operations counted inside the ``jit_scan_segment`` program's
intervals in the traced call (``benchlib/scopes.py``), the percent whose
scope path is empty: the scan's own bookkeeping, copies and loop, and
any operation whose metadata the scope table cannot map.  A rise says that
the per-scope readers (``select.round_device_ms``,
``local.round_device_ms``) miss time.  Needs the program's scope table
(``REPRO_TRACE=1``); without it, nothing.
"""
from benchlib import scopes


def read(rec):
    return scopes.unscoped_share(rec)
