"""The ``dag_batch`` cell: BOTS sort's DAG as the reference restates it,
against the program's generator, and the reader of the kernel's state.

    python -m pytest -q benchmarks/chip/test_bots_sort.py

The last tests run the cell whole on the CPU, cut to a few processors
(``conftest.py``): a sound run is correct, and the control and an answer
altered under the timed path are not.
"""
from __future__ import annotations

import contextlib
import io
import json
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parents[1] / "src"))

import cell  # noqa: E402
import run  # noqa: E402
from readings import Run  # noqa: E402


@pytest.fixture(scope="module")
def dag_batch():
    return cell.load("dag_batch")


def _reference_and_program(config):
    from repro.core.dag_gen import bots_sort
    want = cell.task_model(config).dag_of(config)
    have = bots_sort(config["n_elems"], merge_cutoff=config["merge_cutoff"],
                     quick_cutoff=config["quick_cutoff"],
                     split_dur=config["split_dur"])
    return want, have


def test_cell_loads_as_stated(dag_batch):
    assert dag_batch.chips == 1
    assert dag_batch.config["task_model"] == "bots_sort"
    assert dag_batch.traffic == {"W_list": [0], "lam_list": [2, 62, 262, 482],
                                 "reps": 16}
    assert "ws_sim.state_kib_per_scenario" in {
        m.name for m in dag_batch.per_layer}
    assert "events_per_s" in {m.name for m in dag_batch.end_to_end}
    kw = dag_batch.model.query_kwargs(dag_batch.config)
    assert "deque_cap" not in kw
    assert kw["dag"].n == dag_batch.config["n_tasks"] == 27646


@pytest.mark.parametrize("cut", [
    dict(n_elems=1 << 12, merge_cutoff=64, quick_cutoff=64),
    dict(n_elems=5000, merge_cutoff=100, quick_cutoff=300, split_dur=2),
    dict(n_elems=1 << 16, merge_cutoff=2048, quick_cutoff=256),
    dict(),                                   # the cell's own size
], ids=["4Ki", "uneven", "64Ki", "cell"])
def test_program_dag_equals_reference(dag_batch, cut):
    config = {**dag_batch.config, **cut}
    want, have = _reference_and_program(config)
    for name in ("dur", "child_ptr", "child_idx", "pred_count"):
        np.testing.assert_array_equal(getattr(have, name),
                                      getattr(want, name), err_msg=name)


@pytest.mark.parametrize("change", [
    {"n_tasks": 27645}, {"n_elems": 1 << 20}, {"dag_generator": "merge_sort"},
], ids=["n_tasks", "n_elems", "unknown_key"])
def test_configuration_that_disagrees_is_refused(dag_batch, change):
    config = {**dag_batch.config, **change}
    with pytest.raises(ValueError):
        cell.validate(config, dag_batch.traffic, dag_batch.model)


def test_work_list_other_than_zero_is_refused(dag_batch):
    with pytest.raises(ValueError):
        cell.validate(dag_batch.config, {**dag_batch.traffic,
                                         "W_list": [1000]}, dag_batch.model)


def _run():
    return Run(answers=[object()], t_start=0.0, t_end=1.0, setup_s=0.0,
               counters_before={}, counters_after={}, spans=[],
               span_window=(0.0, 1.0))


def test_state_kib_reader(monkeypatch):
    from repro import obs
    registry = obs.MetricsRegistry()
    monkeypatch.setattr(obs, "REGISTRY", registry)
    read = cell._reader("ws_sim.state_kib_per_scenario")
    # nothing to read: a program that keeps no such gauge, or one of
    # another task model only
    assert read(_run()) is None
    registry.gauge("ws_sim.state_bytes", {"task_model": "divisible"}).set(9)
    assert read(_run()) is None
    registry.gauge("ws_sim.state_bytes", {"task_model": "dag"}).set(166326)
    assert read(_run()) == pytest.approx(166326 / 1024)
    empty = _run()
    empty.answers = []
    assert read(empty) is None


def test_kernel_build_sets_the_state_gauge(monkeypatch):
    """Building the cell's kernel records its per-scenario state: the
    bounded deques and the predecessor counts, not one slot per task."""
    import jax
    from repro import obs
    from repro.core import sweep as sw
    from repro.core.dag_gen import bots_sort
    from repro.core.topology import one_cluster
    from repro.kernels.ws_sim import kernel_call
    registry = obs.MetricsRegistry()
    monkeypatch.setattr(obs, "REGISTRY", registry)
    dag = bots_sort(1 << 21)
    scn = sw.scenario_from_rows(sw.grid_rows([0], [2], 1))
    leaves, scn_def = jax.tree.flatten(scn)
    dtypes = tuple(l.dtype for l in leaves)
    kib = {}
    for cap in (None, dag.n):
        model = sw.make_model("dag", topology=one_cluster(32, 2), dag=dag,
                              deque_cap=cap)
        kernel_call(model, 128, True, scn_def, dtypes)
        kib[cap] = cell._reader("ws_sim.state_kib_per_scenario")(_run())
    pred_kib = dag.n * 4 / 1024
    assert pred_kib < kib[None] < pred_kib + 32 * 421 * 4 / 1024 + 8
    assert kib[dag.n] > kib[None] + 32 * (dag.n - 421) * 4 / 1024 - 1


def test_benchmark_entries_name_the_cell():
    bench = json.loads((HERE.parents[1] / "BENCHMARK.json").read_text())
    listed = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]
              if "dag_batch" in m.get("workloads", ())}
    assert listed == {"events_per_s", "ws_sim.device_ns_per_event",
                      "device.idle_share", "backend.lowerings_per_dispatch",
                      "service.host_ms_per_answer",
                      "ws_sim.state_kib_per_scenario"}


# -- the cell run whole, cut to a few processors ---------------------------

@pytest.fixture(scope="module")
def small_root(tmp_path_factory, small_sizes):
    """A checkout root with the real cells, traffic and metrics, whose
    configurations of the later task models are cut to ``small_sizes``."""
    root = tmp_path_factory.mktemp("root")
    bench = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    (root / "configs").mkdir()
    for c in bench["configs"]:
        config = json.loads((run.ROOT / c["file"]).read_text())
        config.update(small_sizes.get(config["task_model"], {}))
        c["file"] = f"configs/{c['name']}.json"
        (root / c["file"]).write_text(json.dumps(config))
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def _result(root, control=False, seed=2**31 + 54321) -> dict:
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = run.main(["--workload", "dag_batch", "--seed", str(seed),
                       "--seconds", "2"], require_chip=False,
                      control=control, root=root)
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


def test_small_cell_is_the_bots_dag(small_root):
    small = cell.load("dag_batch", small_root)
    assert small.config["p"] == 4
    assert small.model.query_kwargs(small.config)["dag"].n == 430


def test_sound_run_is_correct(small_root):
    res = _result(small_root)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) >= {"setup_s", "events_per_s"}
    for name in ("seeds_off", "rows_off", "overflow", "fallbacks"):
        assert res["checks"][name]["value"] == 0, name


def test_control_is_not_correct(small_root):
    res = _result(small_root, control=True)
    assert not res["correct"]
    checks = res["checks"]
    assert checks["rows_off"]["value"] > 0
    assert checks["cells_gap"]["value"] > checks["cells_gap"]["limit"]


def test_altered_answer_is_not_correct(small_root, monkeypatch):
    """Every answer's makespan off by one where the broker produces it."""
    import dataclasses
    from repro.service import broker
    real = broker.run_rows

    def altered(*a, **k):
        grid = real(*a, **k)
        return dataclasses.replace(grid, makespan=grid.makespan + 1)

    monkeypatch.setattr(broker, "run_rows", altered)
    res = _result(small_root)
    assert not res["correct"]
    assert res["checks"]["rows_off"]["value"] > 0
