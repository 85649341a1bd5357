"""Tracing half of the observability layer (DESIGN.md §9): spans on the
profiler's clock.

A span is a named wall-clock interval with structured attributes, opened at
a layer boundary of the service stack: ``service.query → broker.flush →
broker.round → broker.dispatch → backend.run_rows → ws_sim.chunk /
engine.segment → backend.fetch``, beside ``estimator.update``,
``store.get / store.put`` and ``broker.lock_wait``. :func:`span` opens a
``jax.profiler.TraceAnnotation`` (a TraceMe) whenever a profiler session is
collecting, so the program's spans land in the profiler's own trace, on the
clock of JAX's compile-path events and of the device's operations, whoever
opened the session: :func:`trace_to`, ``REPRO_WS_TRACE``, an operator's
``jax.profiler.trace``, or a profiler server capture of the daemon. With no
session collecting, a span is one ``is_enabled()`` check and a shared no-op.

Enabling, for a block::

    with obs.trace_to("trace_dir") as tr:
        svc.query(...)
    print(tr.summary())

or for a whole process, ``REPRO_WS_TRACE=trace_dir``. Either writes the
profiler's ``trace_dir/plugins/profile/<time>/*.xplane.pb`` and
``perfetto_trace.json.gz`` (open it in ``ui.perfetto.dev``): host spans and
device operations in one file. One session collects at a time.

``chrome_trace_doc`` / ``write_chrome_trace`` merge Chrome-trace event
lists, for ``core/gantt``'s simulated-time tracks beside a session's own.

Instrumentation never changes what is computed: stored artifacts are
byte-identical with tracing on or off (tested).
"""
from __future__ import annotations

import atexit
import gzip
import json
import os
import sys
import warnings
from contextlib import contextmanager
from pathlib import Path
from typing import Dict, List, Optional, Union

#: Set to a directory to trace the whole process into it.
TRACE_ENV = "REPRO_WS_TRACE"

PathLike = Union[str, os.PathLike]


class _NullSpan:
    """Shared do-nothing span: the entire cost of a disabled trace point."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs):
        return self


_NULL_SPAN = _NullSpan()

#: The span class, made once JAX is loaded (see :func:`_span_class`).
_SPAN_CLS = None
#: Names :func:`span` has recorded in this process: what tells the
#: program's spans from JAX's own events in a trace read back.
_NAMES: set = set()


def _span_class():
    """A ``TraceAnnotation`` with ``set()`` for late attributes; None while
    the process has not imported JAX, when no profiler can be collecting."""
    global _SPAN_CLS
    if _SPAN_CLS is None and "jax" in sys.modules:
        from jax.profiler import TraceAnnotation

        class _Span(TraceAnnotation):
            def set(self, **attrs):
                self.set_metadata(**attrs)
                return self

        _SPAN_CLS = _Span
    return _SPAN_CLS


def enabled() -> bool:
    """Whether a profiler session is collecting, so spans are recorded."""
    cls = _SPAN_CLS or _span_class()
    return cls is not None and cls.is_enabled()


def span(name: str, **attrs):
    """Open a span (the one call instrumented code makes). ``sp.set(**late)``
    adds attributes known only at the end (cache tier, wasted lanes)."""
    cls = _SPAN_CLS or _span_class()
    if cls is not None and cls.is_enabled():
        _NAMES.add(name)
        return cls(name, **attrs)
    return _NULL_SPAN


class Trace:
    """A profiler session that writes ``path``, and the host events it
    recorded, read back once it has closed."""

    def __init__(self, path: PathLike):
        self.path = Path(path)
        self._events: Optional[List[dict]] = None

    def start(self) -> "Trace":
        import jax
        if enabled():
            raise RuntimeError(
                "a profiler session is already collecting in this process "
                "(obs.trace_to, REPRO_WS_TRACE or jax.profiler); close it "
                "before opening another")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(self.path), create_perfetto_trace=True,
                                 profiler_options=opts)
        return self

    def stop(self):
        import jax
        jax.profiler.stop_trace()

    def _newest(self, pattern: str) -> Path:
        found = list(self.path.rglob(pattern))
        if not found:
            raise FileNotFoundError(f"no {pattern} under {self.path}")
        return max(found, key=lambda p: p.stat().st_mtime)

    def events(self) -> List[dict]:
        """Every host event of the session, the program's spans and JAX's
        own, by start: ``{name, start_ns, dur_ns, line, args}``, where
        ``line`` numbers the host's threads."""
        if self._events is None:
            import jax
            prof = jax.profiler.ProfileData.from_file(
                str(self._newest("*.xplane.pb")))
            lines = [line for plane in prof.planes
                     if plane.name.startswith("/host:")
                     for line in plane.lines]
            out = []
            with warnings.catch_warnings():
                warnings.simplefilter("ignore", DeprecationWarning)
                for k, line in enumerate(lines):
                    for e in line.events:
                        out.append(dict(
                            name=e.name, start_ns=e.start_ns,
                            dur_ns=e.duration_ns, line=k,
                            args=dict(e.stats)))
            self._events = sorted(out, key=lambda e: e["start_ns"])
        return self._events

    def spans(self) -> List[dict]:
        """The program's spans among :meth:`events`."""
        return [e for e in self.events() if e["name"] in _NAMES]

    def durations_ms(self) -> Dict[str, List[float]]:
        """Span durations in milliseconds, by name."""
        out: Dict[str, List[float]] = {}
        for e in self.spans():
            out.setdefault(e["name"], []).append(e["dur_ns"] / 1e6)
        return out

    def summary(self) -> str:
        """Per-span-name table: count, total/mean/max milliseconds."""
        rows = sorted(((sum(v), name, v)
                       for name, v in self.durations_ms().items()),
                      reverse=True)
        lines = [f"{'span':<24s} {'count':>6s} {'total_ms':>10s} "
                 f"{'mean_ms':>9s} {'max_ms':>9s}"]
        for total, name, v in rows:
            lines.append(f"{name:<24s} {len(v):>6d} {total:>10.2f} "
                         f"{total / len(v):>9.3f} {max(v):>9.3f}")
        return "\n".join(lines)

    def chrome_events(self) -> List[dict]:
        """The session's Chrome-trace events as the profiler wrote them
        (``perfetto_trace.json.gz``), to merge with other track groups."""
        with gzip.open(self._newest("perfetto_trace.json.gz"), "rt") as f:
            return [e for e in json.load(f)["traceEvents"] if e]


@contextmanager
def trace_to(path: PathLike):
    """Trace the block into ``path`` (a directory); yields the
    :class:`Trace`, whose spans read back after the block."""
    tr = Trace(path).start()
    try:
        yield tr
    finally:
        tr.stop()


# ---------------------------------------------------------------------------
# Chrome-trace document helpers (core/gantt's simulated-time tracks).
# ---------------------------------------------------------------------------

def chrome_trace_doc(*event_lists) -> dict:
    """Merge event lists into one Chrome-trace JSON document. Metadata
    events lead; timed events are stable-sorted by (pid, tid, ts), which
    preserves B-before-E order at equal timestamps within a thread."""
    meta, timed = [], []
    for events in event_lists:
        for ev in events:
            (meta if ev.get("ph") == "M" else timed).append(ev)
    timed.sort(key=lambda e: (e.get("pid", 0), e.get("tid", 0),
                              e.get("ts", 0.0)))
    return {"traceEvents": meta + timed, "displayTimeUnit": "ms"}


def write_chrome_trace(path: PathLike, *event_lists) -> Path:
    """Write merged event lists as a Chrome-trace JSON file."""
    out = Path(path)
    out.parent.mkdir(parents=True, exist_ok=True)
    out.write_text(json.dumps(chrome_trace_doc(*event_lists), indent=1))
    return out


def _install_from_env():
    path = os.environ.get(TRACE_ENV, "").strip()
    if path:
        atexit.register(Trace(path).start().stop)


_install_from_env()
