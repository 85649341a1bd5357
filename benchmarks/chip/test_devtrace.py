"""The reduction from a profiler trace to the benchmark's device numbers.

    python -m pytest -q benchmarks/chip/test_devtrace.py

One hand-made trace whose every number is worked out below, and one
recorded on the chip (``testdata/trace_paper_batch.json``: the first 0.8 s
of a traced ``paper_batch`` window on a TPU v5 lite) whose numbers were read
once and are held here.
"""
from __future__ import annotations

import json
import re
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import devtrace as dt  # noqa: E402
import readings  # noqa: E402

MS = 1e6  # nanoseconds


def _trace():
    """Window [10, 110] ms. Device 0 runs a kernel over [0, 30] (clipped
    to [10, 30]) and [25, 50] (overlapping it), and a copy over [100, 120]
    (clipped to [100, 110]): busy 40 + 10 = 50 ms. Device 1 runs the kernel
    over [60, 70]: busy 10 ms. The host thread traces over [30, 60] inside a
    query over [10, 110], and lowers over [70, 100], with a 0.5 ms event
    inside the lowering that is too short to name a gap."""
    k = "%tpu_custom_call.1 = (s32[8]) custom-call(s32[8] %a)"
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [
                [k, 0 * MS, 30 * MS], [k, 25 * MS, 25 * MS],
                ["%copy.1 = s32[8] copy(s32[8] %b)", 100 * MS, 20 * MS]]},
            {"name": "XLA Modules", "events": [["jit_x", 0, 200 * MS]]}]},
        {"name": "/device:TPU:1", "lines": [
            {"name": "XLA Ops", "events": [[k, 60 * MS, 10 * MS]]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python3", "events": [
                [dt.WINDOW, 10 * MS, 100 * MS],
                ["bench.query", 10 * MS, 100 * MS],
                ["trace_to_jaxpr_dynamic", 30 * MS, 30 * MS],
                ["lower_sharding_computation", 70 * MS, 30 * MS],
                ["PjitFunction(_where)", 80 * MS, 0.5 * MS]]}]},
    ]}


def test_window_busy_and_idle_share():
    tr = _trace()
    lo, hi = dt.window(tr)
    assert (lo, hi) == (10 * MS, 110 * MS)
    assert dt.busy_ns(tr, lo, hi) == {"/device:TPU:0": 50 * MS,
                                      "/device:TPU:1": 10 * MS}
    # (100 - 50) + (100 - 10) idle of 2 x 100 ms
    assert dt.idle_share(tr, lo, hi) == pytest.approx(0.7)


def test_kernel_time_and_top_ops():
    tr = _trace()
    lo, hi = dt.window(tr)
    kernel = dt.op_ns(tr, lo, hi, match=re.compile(r"^%tpu_custom_call"))
    # summed per event, not unioned: 20 + 25 on device 0, 10 on device 1
    assert sum(kernel.values()) == 55 * MS
    assert dt.top_ops(tr, lo, hi) == [("%tpu_custom_call.1", 0.055),
                                      ("%copy.1", 0.01)]


def test_idle_gaps_named_by_host_activity():
    tr = _trace()
    lo, hi = dt.window(tr)
    gaps = dict(dt.idle_gaps(tr, lo, hi, samples=1000))
    # device 0 is idle over [50, 100]: trace [50, 60], the query [60, 70],
    # lowering [70, 100]; device 1 over [10, 60] and [70, 110]: query
    # [10, 30] and [100, 110], trace [30, 60], lowering [70, 100]
    assert gaps["trace_to_jaxpr_dynamic"] == pytest.approx(0.040)
    assert gaps["lower_sharding_computation"] == pytest.approx(0.060)
    assert gaps["bench.query"] == pytest.approx(0.040)
    assert "PjitFunction(_where)" not in gaps
    assert sum(gaps.values()) == pytest.approx(0.140)


def test_compile_spans_are_unioned():
    spans = [("/jax/core/compile/jaxpr_trace_duration", 1.0, 3.0),
             ("/jax/core/compile/jaxpr_trace_duration", 1.5, 2.0),
             ("/jax/core/compile/backend_compile_duration", 2.5, 4.0),
             ("/jax/core/compile/backend_compile_duration", 9.0, 12.0),
             ("/jax/other", 0.0, 20.0)]
    run = readings.Run(answers=[], t_start=0.0, t_end=10.0, setup_s=0.0,
                       counters_before={"backend.run_rows{backend=x}": 1},
                       counters_after={"backend.run_rows{backend=x}": 5},
                       spans=spans, span_window=(0.0, 10.0))
    # [1, 4] and [9, 10] inside the window: 4 s over 4 dispatches
    assert readings.compile_ms_per_dispatch(run) == pytest.approx(1000.0)


def test_recorded_trace():
    tr = json.loads((HERE / "testdata" / "trace_paper_batch.json")
                    .read_text())
    lo, _ = dt.window(tr)
    hi = lo + 0.8e9
    assert dt.busy_ns(tr, lo, hi) == {"/device:TPU:0": 340908235.0}
    kernel = dt.op_ns(tr, lo, hi, match=re.compile(r"^%tpu_custom_call"))
    assert sum(kernel.values()) == 340907910.0
    assert dt.idle_share(tr, lo, hi) == pytest.approx(0.57386470625)
    gaps = dict(dt.idle_gaps(tr, lo, hi))
    assert gaps["lower_sharding_computation"] == pytest.approx(0.2582391)
    assert gaps["trace_to_jaxpr_dynamic"] == pytest.approx(0.1147729)
