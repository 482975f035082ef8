"""Profiler trace hooks behind the ``REPRO_TRACE=1`` env switch.

A ``jax.profiler.trace`` dump shows the host's work and the device's
operations.  Two labels tie them to the federated round:

  * :func:`trace_span` (``jax.profiler.TraceAnnotation``) names a host
    region.  The sync scanned loop wraps each call in ``fed/run`` and
    each segment's phases in ``fed/keys``, ``fed/scan_segment[n]``,
    ``fed/history`` and ``fed/eval``; the async tick scan and the sweep
    engine wrap their dispatches in ``fed/...`` / ``sweep/...`` spans.
  * The device operations carry ``jax.named_scope`` paths in their HLO
    metadata (``select/cluster``, ``local``, ``aggregate``, ...; the
    kernels in ``repro.kernels.ops`` by their own names).  A device
    trace holds operation names, not metadata, so the scanned loop
    hands over its compiled program: :func:`register_program` takes a
    thunk that returns the optimized HLO text, and
    :func:`program_text` runs it when a reader asks, after the trace.

Spans are exact no-ops unless ``REPRO_TRACE=1`` is set in the
environment at import time, and only then does the scanned loop
register its program.  Named scopes are always on: they are metadata,
so the compiled program is the same either way.

Usage::

    REPRO_TRACE=1 python - <<'PY'
    import jax
    with jax.profiler.trace("/tmp/trace"):
        ...   # spans now carry fed/... labels
    PY

This module deliberately imports nothing from the rest of the repo, so
the package ``__init__`` chain stays cycle-free.
"""
from __future__ import annotations

import contextlib
import os
from typing import Callable, Dict, Optional

_ENABLED = os.environ.get("REPRO_TRACE", "") == "1"

#: name -> thunk returning a compiled program's optimized HLO text
_PROGRAMS: Dict[str, Callable[[], Optional[str]]] = {}


def trace_enabled() -> bool:
    """Whether ``REPRO_TRACE=1`` was set when the process started."""
    return _ENABLED


def trace_span(name: str):
    """Context manager: label a code region as a profiler span (no-op
    unless ``REPRO_TRACE=1``)."""
    if not _ENABLED:
        return contextlib.nullcontext()
    import jax.profiler
    return jax.profiler.TraceAnnotation(name)


def register_program(name: str,
                     thunk: Callable[[], Optional[str]]) -> None:
    """Make a program's optimized HLO text available as ``name``; the
    thunk runs only when :func:`program_text` asks.  A later
    registration under the same name replaces the earlier one."""
    _PROGRAMS[name] = thunk


def program_text(name: str) -> Optional[str]:
    """The optimized HLO text registered as ``name``, or None."""
    thunk = _PROGRAMS.get(name)
    return None if thunk is None else thunk()
