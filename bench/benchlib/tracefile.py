"""Reduction of a profiler trace to the numbers the per-layer metrics
read: device busy time, per-kernel and per-program device time, host
spans, and the idle gaps between device work labelled with what the
host was doing.

A trace (``jax.profiler.trace``) is read with JAX's own
``ProfileData``.  Device planes are those named ``/device:TPU:<i>``;
their ``XLA Ops`` line holds one event per operation executed, their
``XLA Modules`` line one per program executed.  Host planes
(``/host:...``) hold the spans the benchmark and the program annotate
(``jax.profiler.TraceAnnotation``).  All times are nanoseconds on the
trace's common clock.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from typing import Dict, List, Optional, Tuple

#: the benchmark's own host spans around the traced stretch
WINDOW = "bench/window"
SELECT = "bench/select"

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: operations that hold others on the same line (a loop's body, a
#: branch): kept for busy time, left out of the per-operation list
CONTAINERS = ("while", "conditional", "call")


def short_name(name: str) -> str:
    """``%fusion.417 = f32[...] fusion(...)`` -> ``fusion.417``."""
    return name.split(" = ", 1)[0].lstrip("%")


def opcode(name: str) -> str:
    """``fusion.417`` (or its long form) -> ``fusion``."""
    return short_name(name).rsplit(".", 1)[0]


@dataclasses.dataclass
class Event:
    name: str            # for device operations, the short name
    start: float
    dur: float

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass
class Trace:
    ops: List[List[Event]]        # per device, operations
    modules: List[List[Event]]    # per device, programs
    spans: List[Event]            # host spans (every host line)
    #: whole HLO text of each custom call (Pallas kernels), by short name
    custom_calls: Dict[str, str] = dataclasses.field(default_factory=dict)
    _busy: Dict[tuple, list] = dataclasses.field(default_factory=dict,
                                                 repr=False)

    # --- the benchmark's own spans --------------------------------------
    def span(self, name: str) -> Tuple[float, float]:
        hits = [e for e in self.spans if e.name == name]
        if not hits:
            raise KeyError(f"no host span {name!r} in the trace")
        return hits[0].start, hits[0].end

    @property
    def window(self) -> Tuple[float, float]:
        return self.span(WINDOW)

    @property
    def window_s(self) -> float:
        a, b = self.window
        return (b - a) * 1e-9

    # --- device time ----------------------------------------------------
    def busy_intervals(self, dev: int, lo: float, hi: float
                       ) -> List[Tuple[float, float]]:
        """Union of device ``dev``'s operation intervals inside
        [lo, hi], merged and sorted."""
        if (dev, lo, hi) in self._busy:
            return self._busy[dev, lo, hi]
        iv = sorted((max(e.start, lo), min(e.end, hi))
                    for e in self.ops[dev] if e.end > lo and e.start < hi)
        merged: List[List[float]] = []
        for a, b in iv:
            if merged and a <= merged[-1][1]:
                merged[-1][1] = max(merged[-1][1], b)
            else:
                merged.append([a, b])
        out = self._busy[dev, lo, hi] = [(a, b) for a, b in merged]
        return out

    def busy_s(self, window: Tuple[float, float]) -> float:
        """Seconds in which some operation ran, averaged over devices."""
        lo, hi = window
        per = [sum(b - a for a, b in self.busy_intervals(d, lo, hi))
               for d in range(len(self.ops))]
        return 1e-9 * sum(per) / max(1, len(per))

    def op_time(self, match, window: Optional[Tuple[float, float]] = None
                ) -> Tuple[float, int]:
        """(seconds, count) of device operations whose name satisfies
        ``match``, summed over devices, optionally inside ``window``."""
        total, count = 0.0, 0
        for dev in self.ops:
            for e in dev:
                if match(e.name) and (window is None or (
                        e.start >= window[0] and e.end <= window[1])):
                    total += e.dur
                    count += 1
        return total * 1e-9, count

    def module_time(self, match, window: Optional[Tuple[float, float]]
                    = None) -> Tuple[float, int]:
        """(seconds, count) of device programs whose name satisfies
        ``match``."""
        total, count = 0.0, 0
        for dev in self.modules:
            for e in dev:
                if match(e.name) and (window is None or (
                        e.start >= window[0] and e.end <= window[1])):
                    total += e.dur
                    count += 1
        return total * 1e-9, count

    def span_time(self, prefix: str, window: Tuple[float, float]) -> float:
        """Seconds of the union of host spans named ``prefix...`` that
        fall inside ``window``."""
        lo, hi = window
        iv = sorted((max(e.start, lo), min(e.end, hi)) for e in self.spans
                    if e.name.startswith(prefix) and e.end > lo
                    and e.start < hi)
        total, end = 0.0, -1.0
        for a, b in iv:
            if b <= end:
                continue
            total += b - max(a, end)
            end = b
        return total * 1e-9

    # --- breakdown ------------------------------------------------------
    def label(self, t: float) -> str:
        """What the host was doing at time ``t``: the innermost host span
        that holds it, other than the benchmark's window, and the
        innermost of the program's and the benchmark's own spans (named
        ``<layer>/<what>``) around it where that is another."""
        holding = sorted((e for e in self.spans
                          if e.start <= t <= e.end and e.name != WINDOW),
                         key=lambda e: e.dur)
        if not holding:
            return "no host span"
        named = [e for e in holding if "/" in e.name
                 and not e.name.startswith("$")]
        if named and named[0] is not holding[0]:
            return f"{holding[0].name} in {named[0].name}"
        return holding[0].name

    def breakdown(self, window: Tuple[float, float], top: int = 10
                  ) -> Dict[str, list]:
        lo, hi = window
        by_name: Dict[str, float] = {}
        for dev in self.ops:
            for e in dev:
                if (e.end > lo and e.start < hi
                        and opcode(e.name) not in CONTAINERS):
                    by_name[e.name] = by_name.get(e.name, 0.0) + e.dur
        n_dev = max(1, len(self.ops))
        ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
        gaps: List[Tuple[float, float]] = []
        busy = self.busy_intervals(0, lo, hi) if self.ops else []
        prev = lo
        for a, b in busy + [(hi, hi)]:
            if a > prev:
                gaps.append((prev, a))
            prev = max(prev, b)
        gaps.sort(key=lambda g: -(g[1] - g[0]))
        return {
            "device_ops": [[name, t * 1e-9 / n_dev] for name, t in ops],
            "idle_gaps": [[self.label(0.5 * (a + b)), (b - a) * 1e-9]
                          for a, b in gaps[:top]],
        }


def _events(line, names: Optional[Dict[str, str]] = None
            ) -> List[Event]:
    """A line's events.  A device operation's name is the whole HLO
    instruction; given ``names`` (filled here, long -> short) only the
    short name is kept, one copy per distinct name, since a loop repeats
    its operations many times."""
    out = []
    for e in line.events:
        name = e.name
        if names is not None:
            short = names.get(name)
            if short is None:
                short = names[name] = short_name(name)
            name = short
        out.append(Event(name, float(e.start_ns), float(e.duration_ns)))
    return out


def from_profile(pd) -> Trace:
    ops: Dict[str, List[Event]] = {}
    modules: Dict[str, List[Event]] = {}
    spans: List[Event] = []
    names: Dict[str, str] = {}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[plane.name] = _events(line, names)
                elif line.name == MODULES_LINE:
                    modules[plane.name] = _events(line)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(_events(line))
    keys = sorted(ops)
    return Trace(ops=[ops[k] for k in keys],
                 modules=[modules.get(k, []) for k in keys], spans=spans,
                 custom_calls={short: long for long, short in names.items()
                               if " custom-call(" in long})


def load(directory: str) -> Trace:
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return from_profile(ProfileData.from_file(sorted(paths)[-1]))
