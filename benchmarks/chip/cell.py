"""A cell of the benchmark, found by name: its configuration, its traffic,
its task model and its metric readers.

``BENCHMARK.json`` at the root of the checkout names every cell
(``workloads``), the configuration file of each, and the metrics. The files
of one cell are found from those names alone, so a new cell, traffic mix,
task model or metric is new files and one entry in ``BENCHMARK.json``:

* a configuration: the JSON file that ``configs[].file`` names;
* a task model: ``models/<task_model>.py``, with ``KEYS`` (the
  configuration keys it reads beside ``SHARED``), ``check(config,
  traffic)``, ``query_kwargs(config)`` (what it adds to every query the
  program is asked) and ``simulate(config, row)`` (the reference's
  simulation of one row);
* a traffic mix: ``traffic/<traffic>.json``, parameters that
  :func:`blocks` and :func:`query_kwargs` read (``BATCH`` or
  ``CERTIFIED``);
* a metric: ``metrics/<name>.py``, a module with ``read(run)`` that
  returns the value, or None where the run holds nothing to read. A metric
  split by the end-to-end metric it moves, ``<name>.<part>``, reads with
  ``<name>``'s reader where it has no file of its own.

A configuration or a traffic mix that holds a key, a topology, a strategy
or a task model that the harness and its reference do not restate is
refused before the run starts, never ignored.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import random
from pathlib import Path
from types import ModuleType
from typing import Iterator, List

import reference as ref

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
M32 = 0xFFFFFFFF
#: Query seeds lie this far apart, more than any query's largest
#: replication budget (the service's ``max_reps``), so no two queries of a
#: run share a store key.
SEED0_SPACING = 4096

#: Configuration keys that say where it comes from and what it guarantees.
DOCUMENTARY = ("name", "source", "reduced", "cuts", "assumed", "guarantees",
               "runs_through")
#: Configuration keys of every task model.
SHARED = ("task_model", "topology", "strategy", "p", "mwt", "theta")
#: Keys of a traffic mix of fixed-size queries.
BATCH = ("W_list", "lam_list", "reps")
#: Keys of a certified traffic mix: single-cell questions that replicate
#: until their interval meets ``ci``, asked in blocks of ``block[r]``
#: questions from ``pools[r]``, those that take ``r`` rounds
#: (``catalogue.py``); ``candidates_by_rounds`` records the natural mix.
CERTIFIED = ("W_list", "lam_list", "ci", "ci_relative", "batch_reps",
             "min_reps", "max_reps", "warm_seed0", "block", "pools",
             "candidates_by_rounds")


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    read: object           # Run -> Optional[float]


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    model: ModuleType
    end_to_end: List[Metric]
    per_layer: List[Metric]


def _module(path: Path, name: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def task_model(config: dict) -> ModuleType:
    path = HERE / "models" / f"{config['task_model']}.py"
    if not path.is_file():
        raise ValueError(f"no reference for task model "
                         f"{config['task_model']!r} ({path.name})")
    return _module(path, f"model_{config['task_model']}")


def _reader(name: str):
    parts = name.split(".")
    for k in range(len(parts), 0, -1):
        path = HERE / "metrics" / (".".join(parts[:k]) + ".py")
        if path.is_file():
            return _module(path, f"metric_{name}").read
    raise ValueError(f"no reader for metric {name!r}")


def _metrics(entries: list, cell: str, need_list: bool) -> list:
    """Metrics of ``entries`` that ``cell`` reports: those that list it, and
    an end-to-end metric without a list (``setup_s``) in every cell."""
    out = []
    for m in entries:
        listed = m.get("workloads")
        if listed is None and need_list:
            raise ValueError(f"per-layer metric {m['name']!r} lists no "
                             f"workloads")
        if listed is None or cell in listed:
            out.append(Metric(m["name"], m["unit"], _reader(m["name"])))
    return out


def _refuse_unknown(what: str, keys, allowed) -> None:
    extra = sorted(set(keys) - set(allowed))
    if extra:
        raise ValueError(f"{what}: keys {extra} are not read by the harness")


def validate(config: dict, traffic: dict, model: ModuleType) -> None:
    """Refuse what the harness or its reference would not run as stated."""
    _refuse_unknown("configuration", config,
                    DOCUMENTARY + SHARED + tuple(model.KEYS))
    if config["topology"] not in ref.TOPOLOGIES:
        raise ValueError(f"no reference topology {config['topology']!r}")
    if config["strategy"] not in ref.STRATEGIES:
        raise ValueError(f"no reference strategy {config['strategy']!r}")
    kind = BATCH if "reps" in traffic else CERTIFIED
    _refuse_unknown("traffic", traffic, kind)
    missing = sorted(set(kind) - set(traffic))
    if missing:
        raise ValueError(f"traffic: missing {missing}")
    if kind is CERTIFIED and (len(traffic["W_list"]) != 1 or len(
            traffic["lam_list"]) != 1 or len(config["theta"]) != 1):
        raise ValueError("a certified question asks about one cell")
    for key in ("W_list", "lam_list"):
        if key in config and not set(traffic[key]) <= set(config[key]):
            raise ValueError(f"traffic {key} {traffic[key]} is not in the "
                             f"configuration's {config[key]}")
    model.check(config, traffic)


def load(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json``."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    cfg = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = json.loads((root / cfg["file"]).read_text())
    traffic = json.loads((HERE / "traffic" / f"{w['traffic']}.json")
                         .read_text())
    model = task_model(config)
    validate(config, traffic, model)
    return Cell(name, int(w["chips"]), config, traffic, model,
                _metrics(bench["end_to_end"], name, need_list=False),
                _metrics(bench["per_layer"], name, need_list=True))


# ---------------------------------------------------------------------------
# Traffic: one client that waits for each answer (a planner's script)
# ---------------------------------------------------------------------------

def _mix(seed: int) -> int:
    """splitmix64 of the run's seed, folded to 32 bits."""
    z = (int(seed) + 0x9E3779B97F4A7C15) & 0xFFFFFFFFFFFFFFFF
    z = ((z ^ (z >> 30)) * 0xBF58476D1CE4E5B9) & 0xFFFFFFFFFFFFFFFF
    z = ((z ^ (z >> 27)) * 0x94D049BB133111EB) & 0xFFFFFFFFFFFFFFFF
    return (z ^ (z >> 31)) & M32


def query_kwargs(cell: Cell) -> dict:
    """The keyword arguments of ``SimulationService.make_query`` that every
    query of the cell shares; the seed is set per query."""
    config, traffic = cell.config, cell.traffic
    kw = dict(W_list=list(traffic["W_list"]),
              lam_list=list(traffic["lam_list"]),
              theta=[tuple(t) for t in config["theta"]],
              mwt=bool(config["mwt"]))
    if "reps" in traffic:
        kw["reps"] = int(traffic["reps"])
    else:
        kw.update(ci=float(traffic["ci"]),
                  ci_relative=bool(traffic["ci_relative"]),
                  batch_reps=int(traffic["batch_reps"]),
                  max_reps=int(traffic["max_reps"]))
    kw.update(cell.model.query_kwargs(config))
    return kw


def blocks(traffic: dict, seed: int) -> Iterator[List[int]]:
    """The ``seed0`` of each query in turn, after the warm-up's, in blocks
    of equal work; the window ends at the end of a block.

    A batch mix spaces its queries' seeds ``SEED0_SPACING`` apart from a
    base drawn from ``seed``, one query to a block. A certified mix asks, in
    every block, ``block[r]`` questions from the pool of those that take
    ``r`` replication rounds, in an order drawn from ``seed``, and no
    question twice: every seed asks for the same work in another order.
    The blocks end where a pool does."""
    base = _mix(seed)
    mix = traffic.get("block")
    if mix is None:
        for i in range(1, M32 // SEED0_SPACING):
            yield [(base + i * SEED0_SPACING) & M32]
        return
    rng = random.Random(base)
    pools = {r: rng.sample(traffic["pools"][r], len(traffic["pools"][r]))
             for r in mix}
    for b in range(min(len(pools[r]) // k for r, k in mix.items())):
        block = [s for r, k in mix.items()
                 for s in pools[r][b * k:(b + 1) * k]]
        rng.shuffle(block)
        yield block


def warm_seed(traffic: dict, seed: int) -> int:
    """The ``seed0`` of the warm-up query, which no measured query uses: a
    certified mix names its own (one round, so that set-up does the same
    work whatever the seed); a batch mix takes the base of its seeds."""
    return int(traffic.get("warm_seed0", _mix(seed)))
