"""The Pallas wrapper builds each ``pallas_call`` once per model and shape.

A dispatch that finds its kernel built adds no lowering (``compile.lowerings``,
registered by ``repro.core.backend``) and one ``ws_sim.kernel_cache{result=hit}``;
its result is bit-identical to the dispatch that built it. Runs in interpret
mode on the CPU, through the chunked path the TPU backend takes.
"""
import numpy as np
import pytest

import repro.core.backend  # noqa: F401  (registers the compile counters)
from repro import obs
from repro.core import adaptive as ad
from repro.core import dag as dg
from repro.core import dag_gen as gen
from repro.core import divisible as dv
from repro.core import engine as eng
from repro.core import topology as T
from repro.kernels.ws_sim import kernel_call, ws_sim_pallas

GRID_CHUNK = 128
MODELS = ("divisible", "dag", "adaptive")


def _model(name, max_events=1 << 12):
    """A fresh model object each call: equal models are equal by content."""
    topo = T.one_cluster(4, 2)
    if name == "divisible":
        return dv.DivisibleModel(dv.EngineConfig(topology=topo,
                                                 max_events=max_events))
    if name == "dag":
        return dg.DagModel(dg.DagEngineConfig(
            topology=topo, dag=gen.merge_sort(64, 16), max_events=max_events))
    return ad.AdaptiveModel(ad.AdaptiveEngineConfig(
        topology=topo, pool_cap=256, max_events=max_events))


def _scenarios(name, n=40):
    work = 0 if name == "dag" else 400
    return eng.batch_scenarios(work, np.arange(n, dtype=np.uint32) + 1, lam=2)


def _counts():
    c = obs.REGISTRY.snapshot()["counters"]
    return (c.get("compile.lowerings", 0),
            c.get("ws_sim.kernel_cache{result=hit}", 0),
            c.get("ws_sim.kernel_cache{result=miss}", 0))


def _run(model, scn, grid_chunk=GRID_CHUNK):
    out = ws_sim_pallas(model, scn, interpret=True, grid_chunk=grid_chunk)
    return {f: np.asarray(getattr(out, f)) for f in out._fields}


@pytest.mark.parametrize("name", MODELS)
def test_equal_model_reuses_built_kernel(name):
    scn = _scenarios(name)
    first = _run(_model(name), scn)
    before = _counts()
    second = _run(_model(name), scn)
    after = _counts()
    assert (after[0] - before[0], after[1] - before[1],
            after[2] - before[2]) == (0, 1, 0)
    assert first.keys() == second.keys()
    for field in first:
        np.testing.assert_array_equal(first[field], second[field],
                                      err_msg=field)
    assert not first["overflow"].any()


@pytest.mark.parametrize("change", ["max_events", "task_model"])
def test_other_model_misses(change):
    """Event budgets no other test builds, so the miss is this test's own."""
    scn = _scenarios("divisible")
    _run(_model("divisible", max_events=3000), scn)
    other = (_model("divisible", max_events=3001)
             if change == "max_events" else _model("adaptive", 3000))
    before = _counts()
    _run(other, scn)
    after = _counts()
    assert (after[1] - before[1], after[2] - before[2]) == (0, 1)


def test_unchunked_grid_keys_on_its_size():
    """``grid_chunk=None`` (the interpret backend): each batch size is its
    own grid, built once. An event budget no other test builds."""
    model = _model("divisible", max_events=3002)
    _run(model, _scenarios("divisible", 8), grid_chunk=None)
    before = _counts()
    _run(model, _scenarios("divisible", 8), grid_chunk=None)
    _run(model, _scenarios("divisible", 12), grid_chunk=None)
    after = _counts()
    assert (after[1] - before[1], after[2] - before[2]) == (1, 1)


def test_kernel_cache_is_bounded():
    assert kernel_call.cache_info().maxsize is not None


def test_kernel_cache_reaches_service_stats(tmp_path):
    """A service on a private registry still shows the wrapper's series."""
    from repro.service.api import SimulationService
    _run(_model("divisible"), _scenarios("divisible", 8), grid_chunk=None)
    svc = SimulationService(root=tmp_path / "store", lock_wait_s=None,
                            metrics=obs.MetricsRegistry())
    counters = svc.stats()["metrics"]["counters"]
    assert counters.get("ws_sim.kernel_cache{result=hit}", 0) \
        + counters.get("ws_sim.kernel_cache{result=miss}", 0) > 0
