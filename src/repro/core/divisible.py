"""Divisible-load task model (paper §2.1.1, §3) over the unified event core.

This is the task model the paper uses for all of its §4 experiments: ``W``
unit tasks start on processor 0; an idle processor steals; a successful steal
transfers floor(w/2) of the victim's remaining work. All event machinery —
one pending event per processor, ``argmin(ev_time)`` selection, SWT/MWT
answer policies, steal thresholds, victim-selection dispatch, xorshift32 PRNG
lanes, trace logging — lives in ``repro.core.engine`` (DESIGN.md §2); this
module defines only the divisible :class:`TaskModel` and its public types.

Steal-answer policies (paper §2.4): ``mwt=True`` allows simultaneous answers
(requests arriving at the same instant are serialized by processor index,
each taking half of what remains — exactly Fig 2); ``mwt=False`` (SWT) makes a
victim refuse while a previous answer is still in flight. ``theta_static`` /
``theta_comm`` implement the steal threshold of §2.4.2: a steal fails unless
the victim's remaining work exceeds ``theta_static + theta_comm·d(v,i)``.

All quantities are int32 (unit tasks, integer latencies); the engine is
bit-exact reproducible and matches the numpy oracle in
``repro.core.oracle`` event-for-event.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax.numpy as jnp
import numpy as np

from repro.core import engine as eng
# Re-exported for backward compatibility (these historically lived here).
from repro.core.engine import (  # noqa: F401
    ACTIVE, ANS_FLIGHT, EV_ANS_FAIL, EV_ANS_OK, EV_IDLE, EV_REQ_FAIL,
    EV_REQ_OK, INF32, REQ_FLIGHT, EngineConfig, Scenario, batch_scenarios,
    make_scenario)


class SimResult(NamedTuple):
    makespan: jnp.ndarray       # int32; valid iff ~overflow
    n_events: jnp.ndarray       # int32 events processed
    n_requests: jnp.ndarray     # int32 steal requests answered (paper metric)
    n_success: jnp.ndarray      # int32 successful steals
    n_fail: jnp.ndarray         # int32 failed steals
    total_idle: jnp.ndarray     # int32 summed idle time over processors
    startup_end: jnp.ndarray    # int32 first time all p procs active (-1: never)
    executed: jnp.ndarray       # int32[p] work executed per processor
    overflow: jnp.ndarray       # bool: hit max_events before termination
    trace: jnp.ndarray          # int32[max_trace, 4] (t, proc, kind, aux)
    n_trace: jnp.ndarray        # int32 valid trace rows


@dataclasses.dataclass(frozen=True)
class DivisibleModel(eng.TaskModel):
    """Divisible-load task engine: work is a splittable int32 amount."""
    cfg: EngineConfig

    def init(self, arrays, scn: Scenario, core: eng.CoreState):
        idle_at = eng.write(core.idle_at, 0, scn.W)
        core = core._replace(
            idle_at=idle_at,
            ev_time=idle_at,      # everyone's first event is its idle event
            executed=eng.write(core.executed, 0, scn.W),
        )
        return core, ()

    def is_done(self, arrays, core: eng.CoreState, ms, i, t):
        """No remaining work anywhere: neither running nor in flight
        (processor i's exhaustion is already reflected via state2)."""
        state2 = eng.write(core.state, i, REQ_FLIGHT)
        rem_active = jnp.sum(jnp.where(state2 == ACTIVE, core.idle_at - t, 0))
        rem_flight = jnp.sum(jnp.where(state2 == ANS_FLIGHT, core.stolen, 0))
        return (rem_active + rem_flight) == 0

    def on_idle(self, arrays, cid, chops, scn, core, ms, i, t):
        """idle event: processor i's running work is exhausted (paper idle())."""
        state2 = eng.write(core.state, i, REQ_FLIGHT)  # tentatively not-active
        finished = self.is_done(arrays, core, ms, i, t)

        core = eng.enter_idle(core, i, t)
        core = eng.log(self, core, t, i, EV_IDLE, 0)

        def _finish(c: eng.CoreState) -> eng.CoreState:
            # Account terminal idle time of every non-active processor.
            idle_now = jnp.where(state2 == ACTIVE, 0, t - c.idle_since)
            return eng.finish(self, c, t, idle_now)

        def _steal(c: eng.CoreState) -> eng.CoreState:
            return eng.start_stealing(self, cid, chops, scn, c, i, t)

        return eng.cond(finished, _finish, _steal, core), ms

    def on_request(self, arrays, cid, chops, scn, core, ms, i, t):
        """steal-request event: thief i's request reaches victim v
        (paper answer_steal_request() + get_part_of_work_if_exist())."""
        v = eng.read(core.victim, i)
        w_v = jnp.where(eng.read(core.state, v) == ACTIVE,
                        eng.read(core.idle_at, v) - t, 0)
        d_vi = eng.dist(cid, chops, scn, v, i)
        thr = eng.steal_threshold(scn, d_vi)
        free = eng.chan_free(self, core, v, t)
        amt = w_v // 2
        ok = (amt >= 1) & (w_v > thr) & free
        amt = jnp.where(ok, amt, 0)

        new_idle_v = t + (w_v - amt)
        core = core._replace(
            idle_at=eng.write(core.idle_at, v,
                              jnp.where(ok, new_idle_v,
                                        eng.read(core.idle_at, v))),
            ev_time=eng.write(core.ev_time, v,
                              jnp.where(ok, new_idle_v,
                                        eng.read(core.ev_time, v))),
            executed=eng.add(core.executed, v, -amt),
        )
        core = eng.deliver_answer(core, i, v, t, d_vi, ok, amt)
        return eng.log(self, core, t, i,
                       jnp.where(ok, EV_REQ_OK, EV_REQ_FAIL), v), ms

    def on_answer(self, arrays, cid, chops, scn, core, ms, i, t):
        """steal-answer event: the (possibly empty) answer reaches thief i
        (paper steal_answer())."""
        amt = eng.read(core.stolen, i)
        ok = amt > 0

        def _got_work(c: eng.CoreState) -> eng.CoreState:
            c = eng.acquire_work(self, c, i, t, t + amt, amt, jnp.int32(0))
            return eng.log(self, c, t, i, EV_ANS_OK, amt)

        def _retry(c: eng.CoreState) -> eng.CoreState:
            c = eng.start_stealing(self, cid, chops, scn, c, i, t)
            return eng.log(self, c, t, i, EV_ANS_FAIL,
                           eng.read(c.victim, i))

        return eng.cond(ok, _got_work, _retry, core), ms

    def results(self, core: eng.CoreState, ms) -> SimResult:
        return SimResult(
            makespan=core.makespan,
            n_events=core.n_events,
            n_requests=core.n_requests,
            n_success=core.n_success,
            n_fail=core.n_fail,
            total_idle=core.total_idle,
            startup_end=core.startup_end,
            executed=core.executed,
            overflow=(~core.done) | core.halt,
            trace=core.trace,
            n_trace=core.n_trace,
        )


def simulate(cfg: EngineConfig, scn: Scenario) -> SimResult:
    """Run one simulation (jitted; cached per EngineConfig)."""
    return eng.simulate(DivisibleModel(cfg), scn)


def simulate_batch(cfg: EngineConfig, scn: Scenario) -> SimResult:
    """Run a batch: every leaf of ``scn`` has a leading batch axis."""
    return eng.simulate_batch(DivisibleModel(cfg), scn)


# ---------------------------------------------------------------------------
# Helpers for callers.
# ---------------------------------------------------------------------------

def default_max_events(W: int, p: int, lam: int) -> int:
    """Heuristic event-count cap.

    Event census: ≤ 2·p idle events for real work intervals plus steal cycles.
    Each steal cycle of an idle processor occupies ≥ 2·lam time, and the
    execution spans ≈ W/p + O(lam·log W) time, so cycles per processor are
    ≈ makespan / (2·lam). 3 events per cycle, ×p processors, ×4 safety.
    """
    lam = max(int(lam), 1)
    makespan_est = W / max(p, 1) + 16.0 * lam * max(np.log2(max(W, 2) / lam), 1.0)
    cycles = makespan_est / (2.0 * lam) + 8.0
    return int(min(12 * p * cycles + 64, 2**31 - 1))
