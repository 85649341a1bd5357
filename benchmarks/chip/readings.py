"""What one run hands its metric readers (``metrics/<name>.py``), and the
arithmetic they share."""
from __future__ import annotations

import dataclasses
import re
from typing import List, Optional, Tuple

import devtrace as tr

#: JAX's own compile-path events (``jax.monitoring`` time spans): tracing a
#: function to a jaxpr, lowering it to a module, and compiling it or
#: loading it from the persistent cache (the last includes the cache read).
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


@dataclasses.dataclass
class Run:
    """A run's measured window and what was recorded in it."""
    answers: list                  # check.Answer, in order of answer
    t_start: float                 # window start, host perf_counter s
    t_end: float                   # the last answer
    setup_s: float
    counters_before: dict
    counters_after: dict
    spans: List[Tuple[str, float, float]]   # (event, start, end), epoch s
    span_window: Tuple[float, float]        # the window in epoch s
    trace: Optional[dict] = None   # trace.load form, traced runs only
    trace_window: Optional[Tuple[float, float]] = None   # trace ns

    @property
    def window_s(self) -> float:
        return self.t_end - self.t_start

    @property
    def n_events(self) -> int:
        return sum(int(a.result.grid.extras["n_events"].sum())
                   for a in self.answers)

    def counter_delta(self, prefix: str) -> float:
        """Change over the window of the counter ``prefix``, summed over
        its label sets."""
        return sum(v - self.counters_before.get(k, 0)
                   for k, v in self.counters_after.items()
                   if k == prefix or k.startswith(prefix + "{"))


def union_s(spans: List[Tuple[float, float]]) -> float:
    total, edge = 0.0, float("-inf")
    for a, b in sorted(spans):
        if b > edge:
            total += b - max(a, edge)
            edge = b
    return total


def compile_ms_per_dispatch(run: Run) -> Optional[float]:
    """Host milliseconds inside JAX's compile path during the window (the
    union of its nested spans), per dispatch to a backend."""
    n = run.counter_delta("backend.run_rows")
    if not n:
        return None
    lo, hi = run.span_window
    spans = [(max(a, lo), min(b, hi)) for e, a, b in run.spans
             if e in COMPILE_EVENTS and b > lo and a < hi]
    return 1e3 * union_s(spans) / n


def idle_pct(run: Run) -> Optional[float]:
    if run.trace is None:
        return None
    share = tr.idle_share(run.trace, *run.trace_window)
    return None if share is None else 100.0 * share


def kernel_ns_per_event(run: Run, kernel: re.Pattern) -> Optional[float]:
    """Device nanoseconds of the kernel's operations per simulated event;
    None where the trace holds no such operation."""
    if run.trace is None or not run.n_events:
        return None
    ops = tr.op_ns(run.trace, *run.trace_window, match=kernel)
    if not ops:
        return None
    return sum(ops.values()) / run.n_events
