"""The reader of the DAG kernel's state in vreg tiles,
``ws_sim.state_tiles_per_scenario``.

    python -m pytest -q benchmarks/chip/test_state_tiles.py
"""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import cell  # noqa: E402
from readings import Run  # noqa: E402


def _run(answers=1):
    return Run(answers=[object()] * answers, t_start=0.0, t_end=1.0,
               setup_s=0.0, counters_before={}, counters_after={}, spans=[],
               span_window=(0.0, 1.0))


def test_state_tiles_reader(monkeypatch):
    from repro import obs
    registry = obs.MetricsRegistry()
    monkeypatch.setattr(obs, "REGISTRY", registry)
    read = cell._reader("ws_sim.state_tiles_per_scenario")
    # nothing to read: a program that keeps no such gauge, or one of
    # another task model only, or a window with no answer
    assert read(_run()) is None
    registry.gauge("ws_sim.state_tiles", {"task_model": "divisible"}).set(23)
    assert read(_run()) is None
    registry.gauge("ws_sim.state_tiles", {"task_model": "dag"}).set(71)
    assert read(_run()) == 71
    assert read(_run(answers=0)) is None


def test_kernel_build_sets_the_tiles_gauge(monkeypatch):
    """The cell's kernel at 2^21 carries its predecessor counts as a
    (216, 128) tile, 27 vregs, where one int32[27646] vector took 216."""
    import jax
    from repro import obs
    from repro.core import sweep as sw
    from repro.core.dag_gen import bots_sort
    from repro.core.topology import one_cluster
    from repro.kernels.ws_sim import kernel_call
    registry = obs.MetricsRegistry()
    monkeypatch.setattr(obs, "REGISTRY", registry)
    kernel_call.cache_clear()     # a cached build sets no gauge
    scn = sw.scenario_from_rows(sw.grid_rows([0], [2], 1))
    leaves, scn_def = jax.tree.flatten(scn)
    model = sw.make_model("dag", topology=one_cluster(32, 2),
                          dag=bots_sort(1 << 21))
    kernel_call(model, 128, True, scn_def, tuple(l.dtype for l in leaves))
    tiles = cell._reader("ws_sim.state_tiles_per_scenario")(_run())
    # the core: 12 scalars, 10 int32[32] vectors, the (1, 4) trace; the
    # model: 4 int32[32] vectors, a scalar, the (32, 421) deques in 4 × 4
    # tiles and the (216, 128) predecessor counts in 27
    assert tiles == (12 + 10 + 1) + (4 + 1 + 16 + 27) == 71
