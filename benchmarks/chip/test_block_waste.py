"""The kernel's block waste, read from the program's counters.

    python -m pytest -q benchmarks/chip/test_block_waste.py
"""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import cell  # noqa: E402
from readings import Run  # noqa: E402


def _run(before, after):
    return Run(answers=[object()], t_start=0.0, t_end=1.0, setup_s=0.0,
               counters_before=before, counters_after=after, spans=[],
               span_window=(0.0, 1.0))


@pytest.mark.parametrize("name", ["ws_sim.block_waste",
                                  "ws_sim.block_waste.ci"])
def test_block_waste(name):
    read = cell._reader(name)
    rows = "ws_sim.block_row_events{task_model=divisible}"
    slots = "ws_sim.block_slot_events{task_model=divisible}"
    # 900 row events in the window in blocks that could have run 1,000
    run = _run({rows: 100, slots: 200}, {rows: 1000, slots: 1200})
    assert read(run) == pytest.approx(10.0)
    # a program that keeps no such counters, or ran no block in the window
    assert read(_run({}, {})) is None
    assert read(_run({rows: 5, slots: 8}, {rows: 5, slots: 8})) is None
