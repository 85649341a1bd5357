"""The DAG task model (arXiv:1910.02803, §3.2): tasks with dependencies,
from a single source on processor 0. A finished task pushes its ready
children on its processor's deque; the owner pops the newest (or the
oldest), a thief takes the oldest. Its one generator is the paper's merge
sort (Fig 9). ``simulate`` and ``merge_sort_dag`` are the plain reference
and import nothing of the program."""
from __future__ import annotations

import functools
from typing import List, NamedTuple, Optional, Tuple

import numpy as np

import reference as ref
from reference import ACTIVE, ANS_FLIGHT, INF, REQ_FLIGHT

#: Configuration keys of this model beside the shared ones (``cell.py``).
KEYS = ("lam_list", "dag_generator", "n_elems", "cutoff", "split_dur",
        "n_tasks", "owner_lifo", "max_events")


class Dag(NamedTuple):
    dur: np.ndarray          # int64[n]
    child_ptr: np.ndarray    # int64[n + 1]
    child_idx: np.ndarray    # int64[E]
    pred_count: np.ndarray   # int64[n]


def check(config: dict, traffic: dict) -> None:
    if config["dag_generator"] != "merge_sort":
        raise ValueError(f"no reference DAG generator "
                         f"{config['dag_generator']!r}; have 'merge_sort'")
    n = len(dag_of(config).dur)
    if config.get("n_tasks", n) != n:
        raise ValueError(f"n_tasks {config['n_tasks']} but the DAG has {n}")
    if list(traffic["W_list"]) != [0]:
        raise ValueError("a DAG query's work is its DAG: W_list must be [0]")


def query_kwargs(config: dict) -> dict:
    """The program's own container for the reference's DAG."""
    from repro.core.dag_gen import TaskDag
    d = dag_of(config)
    dag = TaskDag(d.dur.astype(np.int32), d.child_ptr.astype(np.int32),
                  d.child_idx.astype(np.int32),
                  d.pred_count.astype(np.int32),
                  name=f"merge_sort(n={config['n_elems']},"
                       f"cutoff={config['cutoff']})")
    return dict(task_model="dag", dag=dag,
                max_events=int(config["max_events"]),
                owner_lifo=bool(config["owner_lifo"]))


def simulate(config: dict, row: ref.Row) -> dict:
    return simulate_dag(config["p"], dag_of(config), row.lam, row.seed,
                        theta_static=row.theta_static, mwt=config["mwt"],
                        owner_lifo=config["owner_lifo"])


def dag_of(config: dict) -> Dag:
    return merge_sort_dag(config["n_elems"], config["cutoff"],
                          config["split_dur"])


@functools.lru_cache(maxsize=4)
def merge_sort_dag(n_elems: int, cutoff: int, split_dur: int = 1) -> Dag:
    """The paper's merge-sort application (§3.2, Fig 9): split tasks fan
    out to sorted leaves of at most ``cutoff`` elements, which cost
    ``max(m log2 m / 4, 1)``; merges of ``m`` elements cost ``m // 2``.
    Tasks are numbered in depth-first order: split, left, right, merge."""
    dur: List[int] = []
    edges: List[Tuple[int, int]] = []

    def rec(m: int, parent: Optional[int]) -> int:
        if m <= cutoff:
            tid = len(dur)
            dur.append(max(int(m * max(np.log2(max(m, 2)), 1.0) / 4), 1))
            if parent is not None:
                edges.append((parent, tid))
            return tid
        split = len(dur)
        dur.append(split_dur)
        if parent is not None:
            edges.append((parent, split))
        left = rec(m // 2, split)
        right = rec(m - m // 2, split)
        merge = len(dur)
        dur.append(max(m // 2, 1))
        edges.extend([(left, merge), (right, merge)])
        return merge

    rec(n_elems, None)
    n = len(dur)
    children: List[List[int]] = [[] for _ in range(n)]
    pred = np.zeros(n, np.int64)
    for u, v in edges:
        children[u].append(v)
        pred[v] += 1
    ptr = np.concatenate([[0], np.cumsum([len(c) for c in children])])
    idx = np.array([v for c in children for v in c], np.int64)
    return Dag(np.asarray(dur, np.int64), ptr.astype(np.int64), idx, pred)


def simulate_dag(p: int, dag: Dag, lam: int, seed: int,
                 theta_static: int = 0, mwt: bool = False,
                 owner_lifo: bool = True) -> dict:
    """DAG of tasks from its single source on processor 0. A finished task
    pushes its ready children on its processor's deque; the owner pops the
    newest, a thief takes the oldest when the deque holds more than the
    threshold and, under SWT, the victim's channel is free."""
    n = len(dag.dur)
    dur = dag.dur
    pred = dag.pred_count.copy()
    state = np.full(p, ACTIVE, np.int64)
    ev_time = np.zeros(p, np.int64)
    cur = np.full(p, -1, np.int64)
    src = int(np.nonzero(dag.pred_count == 0)[0][0])
    cur[0] = src
    ev_time[0] = dur[src]
    victim = np.zeros(p, np.int64)
    stolen = np.full(p, -1, np.int64)
    busy_until = np.zeros(p, np.int64)
    rng = [ref.proc_seed(seed, i) for i in range(p)]
    idle_since = np.zeros(p, np.int64)
    executed = np.zeros(p, np.int64)
    deques: List[List[int]] = [[] for _ in range(p)]
    active = p
    n_completed = n_events = n_requests = n_success = n_fail = 0
    total_idle = 0
    startup_end = makespan = -1
    done = False

    def steal(i, t):
        v, rng[i] = ref.victim(rng[i], i, p)
        victim[i] = v
        state[i] = REQ_FLIGHT
        ev_time[i] = t + lam

    while not done:
        i = int(np.argmin(ev_time))
        t = int(ev_time[i])
        if t >= INF:
            break
        n_events += 1
        st = state[i]
        if st == ACTIVE:                        # a task ends (or the kick)
            c = int(cur[i])
            if c >= 0:
                n_completed += 1
                executed[i] += int(dur[c])
                for k in range(dag.child_ptr[c], dag.child_ptr[c + 1]):
                    child = int(dag.child_idx[k])
                    pred[child] -= 1
                    if pred[child] == 0:
                        deques[i].append(child)
            cur[i] = -1
            if n_completed >= n:
                done = True
                makespan = t
                others = (cur < 0) & (np.arange(p) != i)
                total_idle += int((t - idle_since[others]).sum())
                break
            if deques[i]:
                task = deques[i].pop() if owner_lifo else deques[i].pop(0)
                cur[i] = task
                ev_time[i] = t + int(dur[task])
            else:
                active -= 1
                idle_since[i] = t
                steal(i, t)
        elif st == REQ_FLIGHT:
            v = int(victim[i])
            n_requests += 1
            if len(deques[v]) > theta_static and (mwt or t >= busy_until[v]):
                n_success += 1
                stolen[i] = deques[v].pop(0)
                busy_until[v] = t + lam
            else:
                n_fail += 1
                stolen[i] = -1
            state[i] = ANS_FLIGHT
            ev_time[i] = t + lam
        else:
            task = int(stolen[i])
            if task >= 0:
                state[i] = ACTIVE
                cur[i] = task
                ev_time[i] = t + int(dur[task])
                stolen[i] = -1
                active += 1
                total_idle += t - int(idle_since[i])
                if active == p and startup_end < 0:
                    startup_end = t
            else:
                steal(i, t)
    return dict(makespan=makespan, n_events=n_events, n_requests=n_requests,
                n_success=n_success, n_fail=n_fail, total_idle=total_idle,
                startup_end=startup_end, overflow=not done,
                executed=executed)
