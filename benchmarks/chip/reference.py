"""Plain serial reference of what the benchmark's cells ask the simulator.

Independent of the program under test: nothing here, nor in the task
models' simulations (``models/<task_model>.py``), imports ``repro``. They
restate the semantics of the paper's work-stealing simulator
(arXiv:1910.02803, §2-§3) for the deployments the cells run: one cluster of
``p`` processors with a constant latency (``TOPOLOGIES``), uniform victim
selection (``STRATEGIES``), single or multiple work transfers (SWT/MWT) and
steal thresholds. One pending event per processor, the earliest first, the
lowest index on a tie; all randomness is a per-processor xorshift32 stream.

This module also restates the service's rules around those rows: which
seeds a query's rows get (``row_seeds``), the per-cell mean and 95%
confidence interval of the makespan, and the adaptive stopping rule of a
certified answer. ``cell_stats`` and ``stop_round`` compare the program's
answer with it.
"""
from __future__ import annotations

import itertools
import math
import statistics
from typing import List, NamedTuple, Sequence, Tuple

#: The topologies and victim-selection strategies the simulations restate.
TOPOLOGIES = ("one_cluster",)
STRATEGIES = ("uniform",)

INF = 2**31 - 1
ACTIVE, REQ_FLIGHT, ANS_FLIGHT = 0, 1, 2
M32 = 0xFFFFFFFF

#: Columns of a row that the comparison holds to the reference, exactly.
ROW_COLUMNS = ("makespan", "n_events", "n_requests", "n_success", "n_fail",
               "total_idle", "startup_end", "overflow", "executed")


# ---------------------------------------------------------------------------
# Seeds and rows of a query
# ---------------------------------------------------------------------------

def xorshift32(s: int) -> int:
    s &= M32
    s ^= (s << 13) & M32
    s ^= s >> 17
    s ^= (s << 5) & M32
    return s


def proc_seed(seed: int, i: int) -> int:
    """Processor ``i``'s initial PRNG state in scenario ``seed``."""
    x = (int(seed) * 0x9E3779B9 + int(i) * 0x85EBCA6B + 1) & M32
    x ^= x >> 16
    x = (x * 0x7FEB352D) & M32
    x ^= x >> 15
    x = (x * 0x846CA68B) & M32
    x ^= x >> 16
    return x | 1


def row_seeds(n: int, seed0: int, stream: int = 0) -> List[int]:
    """Scenario seeds of a query's ``n`` rows in replication round
    ``stream``: ``(idx + stream * 2**22) * 2654435761 + seed0 mod 2**32``."""
    return [((k + (stream << 22)) * 2654435761 + seed0) & M32
            for k in range(n)]


class Row(NamedTuple):
    W: int
    lam: int
    theta_static: int
    theta_comm: int
    seed: int


def query_rows(W_list: Sequence[int], lam_list: Sequence[int], reps: int,
               seed0: int, stream: int = 0,
               theta: Sequence[Tuple[int, int]] = ((0, 0),)) -> List[Row]:
    """A query's rows in the service's order: W outer, then λ, θ, rep."""
    cells = list(itertools.product(W_list, lam_list, theta, range(reps)))
    seeds = row_seeds(len(cells), seed0, stream)
    return [Row(int(W), int(lam), int(th[0]), int(th[1]), s)
            for (W, lam, th, _), s in zip(cells, seeds)]


# ---------------------------------------------------------------------------
# What every task model's simulation shares (``models/<task_model>.py``)
# ---------------------------------------------------------------------------

def victim(rng: int, i: int, p: int) -> Tuple[int, int]:
    """A uniform victim other than ``i``, and the advanced PRNG state."""
    rng = xorshift32(rng)
    v = rng % (p - 1)
    return (v + 1 if v >= i else v), rng


# ---------------------------------------------------------------------------
# The answer: per-cell estimates and the stopping rule
# ---------------------------------------------------------------------------

def z_value(confidence: float) -> float:
    return statistics.NormalDist().inv_cdf(0.5 + 0.5 * confidence)


def cell_stats(makespans: Sequence[int], confidence: float = 0.95):
    """(n, mean, 95% half-width) of one cell's makespans: the sample mean
    and ``z * s / sqrt(n)`` with the unbiased sample deviation ``s``."""
    x = [float(v) for v in makespans]
    n = len(x)
    mean = math.fsum(x) / n
    if n < 2:
        return n, mean, math.inf
    var = math.fsum((v - mean) ** 2 for v in x) / (n - 1)
    return n, mean, z_value(confidence) * math.sqrt(var / n)


def stop_round(makespans: Sequence[int], batch: int, ci: float,
               relative: bool, min_reps: int, max_reps: int,
               confidence: float = 0.95) -> int:
    """Rounds of ``batch`` replications a single-cell certified question
    takes: the first round after which the cell has ``min_reps`` samples
    and a half-width within the target, or the round that reaches
    ``max_reps``. Returns -1 where ``makespans`` is too short to decide."""
    rounds = -(-max_reps // batch)
    for r in range(1, rounds + 1):
        if r * batch > len(makespans):
            return -1
        n, mean, hw = cell_stats(makespans[:r * batch], confidence)
        target = ci * (abs(mean) if relative else 1.0)
        if (n >= min_reps and hw <= target) or n >= max_reps:
            return r
    return rounds
