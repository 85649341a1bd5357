"""The per-layer metrics read from the program's own spans and counters.

    python -m pytest -q benchmarks/chip/test_program_metrics.py

A hand-made trace in the plain form of ``devtrace.load`` whose every number
is worked out below, and the same trace without the program's spans, as a
program that does not put them on the profiler's clock writes it.
"""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import cell  # noqa: E402
import devtrace as dt  # noqa: E402
from readings import Run  # noqa: E402

MS = 1e6  # nanoseconds


def _trace(program_spans: bool = True):
    """Window [10, 110] ms, two answers. The host thread answers queries
    over [5, 50] (clipped to [10, 50]) and [55, 120] (clipped to [55, 110]):
    40 + 55 = 95 ms. Their backend dispatches run over [20, 45] and
    [60, 100], with a second thread's dispatch over [90, 105] overlapping
    the second: 25 + 45 = 70 ms. So the service spends 25 ms of host time
    outside the backend, 12.5 ms an answer. A store write over [46, 49]
    inside the first query changes nothing."""
    query = [["service.query", 5 * MS, 45 * MS],
             ["backend.run_rows", 20 * MS, 25 * MS],
             ["store.put", 46 * MS, 3 * MS],
             ["service.query", 55 * MS, 65 * MS],
             ["backend.run_rows", 60 * MS, 40 * MS]]
    other = [["backend.run_rows", 90 * MS, 15 * MS]]
    if not program_spans:
        query, other = [], []
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [
                ["%tpu_custom_call.1 = (s32[8]) custom-call(s32[8] %a)",
                 25 * MS, 70 * MS]]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python3", "events": [
                [dt.WINDOW, 10 * MS, 100 * MS],
                ["bench.query", 10 * MS, 40 * MS],
                ["bench.query", 55 * MS, 55 * MS]] + query},
            {"name": "python3", "events": other}]},
    ]}


def _run(trace=None, before=None, after=None):
    run = Run(answers=[object(), object()], t_start=0.0, t_end=1.0,
              setup_s=0.0, counters_before=before or {},
              counters_after=after or {}, spans=[], span_window=(0.0, 1.0),
              trace=trace)
    if trace is not None:
        run.trace_window = dt.window(trace)
    return run


@pytest.mark.parametrize("name", ["service.host_ms_per_answer",
                                  "service.host_ms_per_answer.ci"])
def test_host_ms_per_answer(name):
    read = cell._reader(name)
    assert read(_run(_trace())) == pytest.approx(12.5)
    # nothing to read: a program without its spans on the trace, an
    # untraced run
    assert read(_run(_trace(program_spans=False))) is None
    assert read(_run()) is None


@pytest.mark.parametrize("name", ["backend.lowerings_per_dispatch",
                                  "backend.lowerings_per_dispatch.ci"])
def test_lowerings_per_dispatch(name):
    read = cell._reader(name)
    before = {"backend.run_rows{backend=pallas}": 3, "compile.lowerings": 7}
    after = {"backend.run_rows{backend=pallas}": 13, "compile.lowerings": 47}
    assert read(_run(before=before, after=after)) == 4.0
    # a cached kernel lowers nothing
    assert read(_run(before=before, after=dict(after, **{
        "compile.lowerings": 7}))) == 0.0
    # nothing to read: a program without the counter, no dispatch
    assert read(_run(before={"backend.run_rows{backend=pallas}": 3},
                     after={"backend.run_rows{backend=pallas}": 13})) is None
    assert read(_run(before=before, after=dict(after, **{
        "backend.run_rows{backend=pallas}": 3}))) is None
