"""Reduction of a profiler trace to the benchmark's device numbers.

A trace is read once into a plain form, ``{"planes": [{"name", "lines":
[{"name", "events": [[name, start_ns, duration_ns], ...]}]}]}``, the same
for a trace the profiler just wrote (:func:`load`) and for a recorded one
checked in beside the tests. Every number below is computed from that form
inside a window ``(lo, hi)`` in the trace's nanoseconds:

* the device's busy time: the union of the intervals in which an operation
  runs on it, and its idle share, 1 minus busy over the window;
* the time of the operations whose name matches a kernel's;
* the operations that took most time, and the device's idle time, named
  by what the host was doing during it.
"""
from __future__ import annotations

import bisect
import collections
import itertools
import re
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Tuple

#: The host annotation that brackets the measured window.
WINDOW = "bench.window"
#: A device plane of the profiler's trace, one per chip.
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
#: The line of a device plane on which each executed operation is an event.
OP_LINE = "XLA Ops"

Event = Tuple[str, float, float]


def load(path: Path) -> dict:
    """The plain form of the ``.xplane.pb`` at ``path`` (or the newest one
    under it)."""
    import jax
    path = Path(path)
    if path.is_dir():
        path = max(path.rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    prof = jax.profiler.ProfileData.from_file(str(path))
    return {"planes": [
        {"name": plane.name, "lines": [
            {"name": line.name,
             "events": [[e.name, float(e.start_ns), float(e.duration_ns)]
                        for e in line.events]}
            for line in plane.lines]}
        for plane in prof.planes]}


def device_planes(trace: dict) -> List[dict]:
    return [p for p in trace["planes"] if DEVICE_PLANE.match(p["name"])]


def op_events(plane: dict) -> List[Event]:
    return [tuple(e) for line in plane["lines"] if line["name"] == OP_LINE
            for e in line["events"]]


def host_lines(trace: dict) -> List[dict]:
    return [line for p in trace["planes"] if p["name"].startswith("/host:")
            for line in p["lines"]]


def window(trace: dict) -> Optional[Tuple[float, float]]:
    """(start, end) of the ``WINDOW`` annotation, in trace nanoseconds."""
    for line in host_lines(trace):
        for name, start, dur in line["events"]:
            if name == WINDOW:
                return start, start + dur
    return None


def _clip(events: Iterable[Event], lo: float, hi: float):
    for name, start, dur in events:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            yield name, a, b


def busy_intervals(events: Iterable[Event], lo: float,
                   hi: float) -> List[Tuple[float, float]]:
    """The union of the events' intervals inside ``[lo, hi]``, merged and
    sorted."""
    out: List[List[float]] = []
    for _, a, b in sorted(_clip(events, lo, hi), key=lambda e: e[1]):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def busy_ns(trace: dict, lo: float, hi: float) -> Dict[str, float]:
    """Busy nanoseconds of each device inside the window."""
    return {p["name"]: sum(b - a for a, b in busy_intervals(
        op_events(p), lo, hi)) for p in device_planes(trace)}


def idle_share(trace: dict, lo: float, hi: float) -> Optional[float]:
    """Idle share of the devices, averaged over them, in [0, 1]."""
    busy = busy_ns(trace, lo, hi)
    if not busy or hi <= lo:
        return None
    return 1.0 - sum(busy.values()) / (len(busy) * (hi - lo))


def op_ns(trace: dict, lo: float, hi: float,
          match: Optional[re.Pattern] = None) -> Dict[str, float]:
    """Nanoseconds inside the window by operation name, summed over the
    devices; only names that ``match`` finds, where it is given."""
    out: Dict[str, float] = collections.defaultdict(float)
    for p in device_planes(trace):
        for name, a, b in _clip(op_events(p), lo, hi):
            if match is None or match.search(name):
                out[name] += b - a
    return dict(out)


def short_name(op: str) -> str:
    """An operation's name without its HLO text: ``%tpu_custom_call.1``
    for ``%tpu_custom_call.1 = (s32[...]) custom-call(...)``."""
    return op.split(" = ", 1)[0]


def top_ops(trace: dict, lo: float, hi: float,
            k: int = 10) -> List[Tuple[str, float]]:
    """The ``k`` operations that took most device time, in seconds, by
    short name."""
    ops: Dict[str, float] = collections.defaultdict(float)
    for name, ns in op_ns(trace, lo, hi).items():
        ops[short_name(name)] += ns
    return [(n, ns / 1e9) for n, ns in
            sorted(ops.items(), key=lambda kv: -kv[1])[:k]]


class _HostLine:
    """One host thread's events of at least ``min_ns``, sorted by start,
    for "what was open at t" (events of one thread nest, so the
    latest-starting open one is the innermost)."""

    def __init__(self, events: List[list], skip: Tuple[str, ...],
                 min_ns: float):
        evs = sorted((e for e in events
                      if e[2] >= min_ns and e[0] not in skip),
                     key=lambda e: e[1])
        self.names = [e[0] for e in evs]
        self.starts = [e[1] for e in evs]
        self.ends = [e[1] + e[2] for e in evs]
        self.max_end = list(itertools.accumulate(self.ends, max))

    def open_at(self, t: float) -> Optional[Tuple[str, float]]:
        i = bisect.bisect_right(self.starts, t) - 1
        while i >= 0 and self.max_end[i] > t:
            if self.ends[i] > t:
                return self.names[i], self.ends[i] - self.starts[i]
            i -= 1
        return None


def idle_gaps(trace: dict, lo: float, hi: float, k: int = 10,
              skip: Tuple[str, ...] = (WINDOW,), min_ns: float = 1e6,
              samples: int = 16) -> List[Tuple[str, float]]:
    """Device idle time inside the window, in seconds, summed over the
    devices by what the host was doing: each gap is cut into ``samples``
    equal parts, each named by the innermost host event of at least
    ``min_ns`` open in its middle (``"(untraced host)"`` where none is).
    The ``k`` activities with the most."""
    lines = [_HostLine(line["events"], skip, min_ns)
             for line in host_lines(trace)]
    out: Dict[str, float] = collections.defaultdict(float)
    for p in device_planes(trace):
        edge = lo
        for a, b in busy_intervals(op_events(p), lo, hi) + [(hi, hi)]:
            if a > edge:
                part = (a - edge) / samples
                for j in range(samples):
                    t = edge + (j + 0.5) * part
                    found = [f for f in (ln.open_at(t) for ln in lines) if f]
                    name = min(found, key=lambda f: f[1])[0] if found \
                        else "(untraced host)"
                    out[name] += part / 1e9
            edge = max(edge, b)
    return sorted(out.items(), key=lambda kv: -kv[1])[:k]
