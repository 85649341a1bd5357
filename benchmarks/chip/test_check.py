"""The correctness check fails what it must, at a size a test run holds.

    JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/test_check.py

Each test drives a whole run of ``run.py`` (set-up, warm-up, window,
comparison) on the CPU, past the harness's look for a chip, over copies of
the cells' configurations cut to a few processors. A sound run must come
out correct; the control, and each fault planted in the program under the
timed path, must not.
"""
from __future__ import annotations

import contextlib
import io
import itertools
import json
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import run  # noqa: E402

#: Each configuration cut to a size the CPU runs in seconds.
SMALL = {"divisible": dict(p=8)}
#: A cell of the DAG task model, which no cell of BENCHMARK.json runs yet.
DAG_CONFIG = dict(
    name="mergesort_dag_small", task_model="dag", topology="one_cluster",
    strategy="uniform", p=4, mwt=False, owner_lifo=True, theta=[[0, 0]],
    dag_generator="merge_sort", n_elems=600, cutoff=16, split_dur=1,
    n_tasks=190, lam_list=[10], max_events=1 << 20)


def write_root(root: Path, bench: dict, configs: dict) -> Path:
    """A checkout root whose BENCHMARK.json names ``configs`` (name to
    configuration) in place of the files its entries name."""
    (root / "configs").mkdir(exist_ok=True)
    for c in bench["configs"]:
        c["file"] = f"configs/{c['name']}.json"
        (root / c["file"]).write_text(json.dumps(configs[c["name"]]))
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The real cells, traffic and metrics over small configurations, and a
    DAG cell beside them."""
    bench = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    configs = {}
    for c in bench["configs"]:
        cfg = json.loads((run.ROOT / c["file"]).read_text())
        cfg.update(SMALL[cfg["task_model"]])
        configs[c["name"]] = cfg
    configs[DAG_CONFIG["name"]] = DAG_CONFIG
    bench["configs"].append({"name": DAG_CONFIG["name"]})
    bench["workloads"].append({"name": "dag_batch", "chips": 1,
                               "config": DAG_CONFIG["name"],
                               "traffic": "dag_r16"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "paper_batch" in m.get("workloads", ()):
            m["workloads"].append("dag_batch")
    return write_root(tmp_path_factory.mktemp("root"), bench, configs)


def result(root, workload, control=False, seed=2**31 + 12345) -> dict:
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = run.main(["--workload", workload, "--seed", str(seed),
                       "--seconds", "2"], require_chip=False,
                      control=control, root=root)
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


def test_certified_blocks_ask_the_same_work_in_another_order():
    import cell
    traffic = json.loads((HERE / "traffic" / "paper_ci_mix.json")
                         .read_text())
    pool_of = {s: r for r, seeds in traffic["pools"].items() for s in seeds}
    runs = [list(itertools.islice(cell.blocks(traffic, seed), 6))
            for seed in (2**31 + 1, 2**31 + 2)]
    for blocks in runs:
        assert len(blocks) == 6
        for b in blocks:
            assert {r: sum(pool_of[s] == r for s in b)
                    for r in traffic["block"]} == traffic["block"]
        asked = [s for b in blocks for s in b]
        assert len(set(asked)) == len(asked)
    assert runs[0] != runs[1]
    assert cell.warm_seed(traffic, 7) not in pool_of


def test_split_metric_reads_with_its_quantity(root):
    import cell
    ci = {m.name: m.read for m in cell.load("paper_ci", root).per_layer}
    base = {m.name: m.read for m in cell.load("paper_batch", root).per_layer}
    for name in ("device.idle_share", "backend.host_compile_ms_per_dispatch"):
        assert ci[name + ".ci"] is base[name]


@pytest.mark.parametrize("cfg,traffic", [
    ({"topology": "two_clusters"}, {}),
    ({"strategy": "latency_weighted"}, {}),
    ({"task_model": "adaptive"}, {}),
    ({"steal_threshold": 4}, {}),
    ({"lam_list": [2, 62]}, {}),
    ({}, {"arrival_rate": 2.0}),
    ({}, {"lam_list": [122]}),
    ({}, {"W_list": [1000000, 10000000]}),
], ids=["topology", "strategy", "task_model", "config_key", "config_lam",
        "traffic_key", "traffic_lam", "certified_two_cells"])
def test_what_the_harness_cannot_run_is_refused(cfg, traffic):
    """A configuration or traffic that the harness and its reference do not
    restate is refused, never run as something else."""
    import cell
    c = cell.load("paper_ci")
    config, mix = {**c.config, **cfg}, {**c.traffic, **traffic}
    with pytest.raises(ValueError):
        cell.validate(config, mix, cell.task_model(config))


def test_per_layer_metric_without_cells_is_refused(tmp_path):
    import cell
    bench = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    del bench["per_layer"][0]["workloads"]
    configs = {c["name"]: json.loads((run.ROOT / c["file"]).read_text())
               for c in bench["configs"]}
    with pytest.raises(ValueError):
        cell.load("paper_batch", write_root(tmp_path, bench, configs))


@pytest.mark.parametrize("workload", ["paper_batch", "dag_batch", "paper_ci"])
def test_sound_run_is_correct(root, workload):
    res = result(root, workload)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) >= {"setup_s"}


@pytest.mark.parametrize("workload", ["paper_batch", "dag_batch", "paper_ci"])
def test_control_is_not_correct(root, workload):
    res = result(root, workload, control=True)
    assert not res["correct"]
    checks = res["checks"]
    assert checks["rows_off"]["value"] > 0
    assert checks["cells_gap"]["value"] > checks["cells_gap"]["limit"]


# -- faults planted under the timed path -----------------------------------

def _replace(grid, **cols):
    import dataclasses
    extras = dict(grid.extras)
    for k in [k for k in cols if k in extras]:
        extras[k] = cols.pop(k)
    return dataclasses.replace(grid, extras=extras, **cols)


def state_unchanged(grid):
    """The simulation returns its initial state: no event ran."""
    zero = np.zeros_like(grid.makespan)
    executed = np.zeros_like(grid.extras["executed"])
    executed[:, 0] = grid.W
    return _replace(grid, makespan=zero, n_requests=zero, n_success=zero,
                    n_fail=zero, total_idle=zero, n_events=zero,
                    executed=executed)


def answer_altered(grid):
    """Every answer's makespan is off by one where it is produced."""
    return _replace(grid, makespan=grid.makespan + 1)


def exchange_left_out(grid):
    """The rows of every chip but the first come back as the first chip's
    (the results of the other chips never arrive)."""
    n = len(grid)
    half = n // 2
    cols = {c: np.concatenate([getattr(grid, c)[:half]] * 2)[:n]
            for c in ("makespan", "n_requests", "n_success", "n_fail",
                      "total_idle", "startup_end")}
    cols["n_events"] = np.concatenate(
        [grid.extras["n_events"][:half]] * 2)[:n]
    return _replace(grid, **cols)


@pytest.mark.parametrize("fault,workload", [
    (state_unchanged, "paper_batch"),
    (answer_altered, "dag_batch"),
    (exchange_left_out, "paper_batch_x4"),
])
def test_fault_in_rows_is_not_correct(root, monkeypatch, fault, workload):
    from repro.service import broker
    real = broker.run_rows
    monkeypatch.setattr(broker, "run_rows",
                        lambda *a, **k: fault(real(*a, **k)))
    res = result(root, workload)
    assert not res["correct"]
    assert res["checks"]["rows_off"]["value"] > 0


@pytest.mark.parametrize("workload", ["paper_batch", "paper_ci"])
def test_half_batch_mean_is_not_correct(root, monkeypatch, workload):
    """Half of each answer's rows left out, the mean taken over the rest."""
    from repro.service import broker
    real = broker.summarize_cells

    def half(grid, *a, **k):
        keep = np.arange(len(grid)) < max(len(grid) // 2, 2)
        return real(broker._take_grid(grid, np.nonzero(keep)[0]), *a, **k)

    monkeypatch.setattr(broker, "summarize_cells", half)
    res = result(root, workload)
    assert not res["correct"]
    assert res["checks"]["cells_gap"]["value"] > \
        res["checks"]["cells_gap"]["limit"]
