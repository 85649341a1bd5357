"""Whether the answers that the measured window returned are correct.

Once the window has closed, every answer is held to the plain reference
(``reference.py``):

* ``seeds_off``  rows whose (W, λ, θ, seed) differ from the rows the query
  asked for, plus rows missing or extra: the traffic, the broker's padding,
  sorting and fan-back. Exact.
* ``rows_off``   rows of a sample drawn from the seed, at least one from
  every answer, whose simulated columns differ from the reference's
  simulation of the same row: the kernel and everything between it and the
  answer. Exact.
* ``overflow``   rows that hit an event cap. Exact.
* ``cells_gap``  the widest relative gap between an answer's per-cell
  count, mean and 95% half-width and the reference's statistics of the
  rows the answer returned: the estimator.
* ``stop_off``   certified answers that stopped at another round than the
  reference's stopping rule, applied to their own rows. Exact.
* ``fallbacks``  dispatches that ran anywhere but the compiled kernel.

The control (``control=True``) puts in the program's place what would
tempt a later change: the program with its multiple-work-transfer path on
(a victim's channel is never busy), where the configuration states single
transfers, and the answer's statistics taken in float32 where the service
states float64.
"""
from __future__ import annotations

import dataclasses
import math
import random
from typing import Dict, List, Sequence, Tuple

import numpy as np

import reference as ref

#: Each number's limit, set from the readings in PERF.md ("Correctness").
LIMITS = {"seeds_off": 0, "rows_off": 0, "overflow": 0,
          "cells_gap": 1e-8, "stop_off": 0, "fallbacks": 0}
#: Rows simulated by the reference after the window (more where a run has
#: more answers: one row of each).
SAMPLE_ROWS = 48


@dataclasses.dataclass
class Answer:
    """One query answered in the window: its seed and what it returned."""
    seed0: int
    result: object         # repro.service.broker.QueryResult
    t_done: float = 0.0


def expected_rows(traffic: dict, config: dict, seed0: int,
                  n_rounds: int) -> List[ref.Row]:
    theta = [tuple(t) for t in config["theta"]]
    if "reps" not in traffic:
        return [r for s in range(n_rounds)
                for r in ref.query_rows(traffic["W_list"], traffic["lam_list"],
                                        traffic["batch_reps"], seed0, s,
                                        theta)]
    return ref.query_rows(traffic["W_list"], traffic["lam_list"],
                          traffic["reps"], seed0, 0, theta)


def _grid_rows(grid) -> List[Tuple[int, int, int, int, int]]:
    return list(zip(*(np.asarray(c).tolist() for c in (
        grid.W, grid.lam, grid.theta_static, grid.theta_comm, grid.seed))))


def _cells(grid) -> Dict[tuple, List[int]]:
    """Valid makespans by (W, λ, θ) cell, in order of first appearance."""
    out: Dict[tuple, List[int]] = {}
    ok = ~np.asarray(grid.overflow, bool)
    keys = zip(*(np.asarray(c).tolist() for c in (
        grid.W, grid.lam, grid.theta_static, grid.theta_comm)))
    for k, m, good in zip(keys, np.asarray(grid.makespan).tolist(), ok):
        out.setdefault(k, [])
        if good:
            out[k].append(m)
    return out


def _stats32(makespans: Sequence[int], confidence: float = 0.95):
    x = np.asarray(makespans, np.float32)
    n = x.size
    mean = x.mean(dtype=np.float32)
    var = ((x - mean) ** 2).sum(dtype=np.float32) / np.float32(n - 1)
    hw = np.float32(ref.z_value(confidence)) * np.sqrt(var / np.float32(n))
    return n, float(mean), float(hw)


def _gap(a: float, b: float) -> float:
    if a == b:
        return 0.0
    if not (math.isfinite(a) and math.isfinite(b)):
        return math.inf
    return abs(a - b) / max(abs(b), 1e-300)


def cells_gap(res, control: bool = False) -> float:
    """Widest relative gap of an answer's (n, mean, half-width) per cell."""
    cells = res.cells
    got = {(int(W), int(lr), int(ts), int(tc)): (int(n), float(m), float(h))
           for W, lr, ts, tc, n, m, h in zip(
               cells.W, cells.lam_remote, cells.theta_static,
               cells.theta_comm, cells.n, cells.mean, cells.half_width)}
    worst = 0.0
    for key, ms in _cells(res.grid).items():
        want = ref.cell_stats(ms, cells.confidence)
        have = _stats32(ms, cells.confidence) if control else got.get(key)
        if have is None or have[0] != want[0]:
            return math.inf
        worst = max(worst, _gap(have[1], want[1]), _gap(have[2], want[2]))
    return worst


def _row_of(grid, k: int) -> dict:
    out = {c: np.asarray(getattr(grid, c))[k] for c in (
        "makespan", "n_requests", "n_success", "n_fail", "total_idle",
        "startup_end", "overflow")}
    out["n_events"] = np.asarray(grid.extras["n_events"])[k]
    out["executed"] = np.asarray(grid.extras["executed"])[k]
    return out


def sample(answers: Sequence[Answer], seed: int,
           n_rows: int = SAMPLE_ROWS) -> List[Tuple[int, int]]:
    """(answer, row) pairs to simulate, drawn from ``seed``: one row of
    every answer, and more drawn from all rows until there are ``n_rows``,
    so that a short run compares as many rows as a long one."""
    rng = random.Random(seed)
    picks = {(a, rng.randrange(len(ans.result.grid)))
             for a, ans in enumerate(answers) if len(ans.result.grid)}
    rest = sorted({(a, k) for a, ans in enumerate(answers)
                   for k in range(len(ans.result.grid))} - picks)
    picks |= set(rng.sample(rest, max(0, min(n_rows - len(picks),
                                              len(rest)))))
    return sorted(picks)


def compare(cell, answers: Sequence[Answer], seed: int, fallbacks: int = 0,
            control: bool = False) -> Dict[str, float]:
    """Each number of the module docstring for the answers of ``cell``
    (``cell.Cell``)."""
    config, traffic = cell.config, cell.traffic
    certified = "reps" not in traffic
    seeds_off = overflow = stop_off = 0
    gap = 0.0
    for ans in answers:
        res = ans.result
        want = expected_rows(traffic, config, ans.seed0, res.n_rounds)
        have = _grid_rows(res.grid)
        seeds_off += abs(len(want) - len(have)) + sum(
            1 for w, h in zip(want, have) if tuple(w) != h)
        overflow += int(np.asarray(res.grid.overflow, bool).sum())
        gap = max(gap, cells_gap(res, control))
        if certified:
            ms = np.asarray(res.grid.makespan).tolist()
            r = ref.stop_round(ms, traffic["batch_reps"], traffic["ci"],
                               traffic["ci_relative"], traffic["min_reps"],
                               traffic["max_reps"])
            stop_off += int(r != res.n_rounds)
    rows_off = 0
    for a, k in sample(answers, seed):
        grid = answers[a].result.grid
        row = ref.Row(*_grid_rows(grid)[k])
        want = cell.model.simulate(config, row)
        have = _row_of(grid, k)
        rows_off += int(any(not np.array_equal(np.asarray(have[c]),
                                               np.asarray(want[c]))
                            for c in ref.ROW_COLUMNS))
    out = dict(seeds_off=seeds_off, rows_off=rows_off, overflow=overflow,
               cells_gap=gap, fallbacks=fallbacks)
    if certified:
        out["stop_off"] = stop_off
    return out


def verdict(numbers: Dict[str, float]) -> bool:
    return all(v <= LIMITS[k] for k, v in numbers.items())
