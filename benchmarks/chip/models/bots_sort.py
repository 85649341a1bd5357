"""BOTS *sort* (Duran et al., ICPP 2009) as a DAG of tasks: cilksort, which
sorts four quarters as tasks and merges them with a parallel
divide-and-conquer merge (``cilksort_par``, ``cilkmerge_par``), run by the
DAG task model (arXiv:1910.02803, §2.1.2, §3.2).

``cilksort_dag`` restates the task graph in plain numpy and imports nothing
of the program; ``simulate`` runs it through the DAG model's plain
reference, ``models/dag.py``'s ``simulate_dag``. The program is asked for
its own generator's DAG (``repro.core.dag_gen.bots_sort``) and no deque
capacity, so a program DAG that differs from this one, or a deque bound too
small for it, shows as rows that differ from the reference's."""
from __future__ import annotations

import functools
import importlib.util
import math
from pathlib import Path
from typing import List

import numpy as np

import reference as ref

_dag = importlib.util.spec_from_file_location(
    "model_dag_reference", Path(__file__).resolve().parent / "dag.py")
dag_model = importlib.util.module_from_spec(_dag)
_dag.loader.exec_module(dag_model)

#: Configuration keys of this model beside the shared ones (``cell.py``).
KEYS = ("lam_list", "n_elems", "merge_cutoff", "quick_cutoff", "split_dur",
        "n_tasks", "owner_lifo", "max_events")


def check(config: dict, traffic: dict) -> None:
    if list(traffic["W_list"]) != [0]:
        raise ValueError("a DAG query's work is its DAG: W_list must be [0]")
    n = len(dag_of(config).dur)
    if config["n_tasks"] != n:
        raise ValueError(f"n_tasks {config['n_tasks']} but the DAG has {n}")


def query_kwargs(config: dict) -> dict:
    """The program's own generator of the same DAG, and no deque capacity:
    the program derives its bound from the DAG."""
    from repro.core.dag_gen import bots_sort
    dag = bots_sort(config["n_elems"], merge_cutoff=config["merge_cutoff"],
                    quick_cutoff=config["quick_cutoff"],
                    split_dur=config["split_dur"])
    return dict(task_model="dag", dag=dag,
                max_events=int(config["max_events"]),
                owner_lifo=bool(config["owner_lifo"]))


def simulate(config: dict, row: ref.Row) -> dict:
    return dag_model.simulate_dag(
        config["p"], dag_of(config), row.lam, row.seed,
        theta_static=row.theta_static, mwt=config["mwt"],
        owner_lifo=config["owner_lifo"])


def dag_of(config: dict):
    return cilksort_dag(config["n_elems"], config["merge_cutoff"],
                        config["quick_cutoff"], config["split_dur"])


@functools.lru_cache(maxsize=4)
def cilksort_dag(n_elems: int, merge_cutoff: int, quick_cutoff: int,
                 split_dur: int = 1):
    """Cilksort's tasks, numbered as they are created, depth first.

    A sort of ``m >= quick_cutoff`` elements is a spawn task whose children
    sort the quarters ``q, q, q, m - 3q`` (``q = m // 4``), a join of the
    four that spawns the merges ``(q, q)`` and ``(q, m - 3q)``, a join of
    those two, and then the merge of the halves ``(2q, m - 2q)``, which
    follows that join inline. Below the cutoff the sort is one leaf task
    of cost ``max(m log2 m / 4, 1)``. A merge of runs ``a >= b`` is one
    task of cost ``max(a // 2, 1)`` when ``b`` is empty and
    ``max((a + b) // 2, 1)`` when ``b < merge_cutoff``; else a binary
    search of cost ``max(floor(log2 b), 1)`` whose children merge
    ``(a // 2, b // 2)`` and ``(a - a // 2 - 1, b - b // 2)``, then a join
    of the two. Spawns and joins cost ``split_dur``."""
    dur: List[int] = []
    children: List[List[int]] = []

    def new(cost: int, after=()) -> int:
        dur.append(cost)
        children.append([])
        for u in after:
            children[u].append(len(dur) - 1)
        return len(dur) - 1

    def merge(x: int, y: int, after) -> int:
        a, b = (x, y) if x >= y else (y, x)
        if b == 0:
            return new(max(a // 2, 1), after)
        if b < merge_cutoff:
            return new(max((a + b) // 2, 1), after)
        search = new(max(int(math.log2(b)), 1), after)
        first = merge(a // 2, b // 2, [search])
        second = merge(a - a // 2 - 1, b - b // 2, [search])
        return new(split_dur, [first, second])

    def sort(m: int, after) -> int:
        if m < quick_cutoff:
            return new(max(int(m * max(np.log2(max(m, 2)), 1.0) / 4), 1),
                       after)
        q = m // 4
        spawn = new(split_dur, after)
        ends = [sort(k, [spawn]) for k in (q, q, q, m - 3 * q)]
        join = new(split_dur, ends)
        halves = [merge(q, q, [join]), merge(q, m - 3 * q, [join])]
        return merge(2 * q, m - 2 * q, [new(split_dur, halves)])

    sort(n_elems, [])
    pred = np.zeros(len(dur), np.int64)
    for kids in children:
        pred[kids] += 1
    ptr = np.concatenate([[0], np.cumsum([len(k) for k in children])])
    idx = np.array([v for k in children for v in k], np.int64)
    return dag_model.Dag(np.asarray(dur, np.int64), ptr.astype(np.int64),
                         idx, pred)
