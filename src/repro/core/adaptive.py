"""Adaptive-task task model (paper §2.1.3) over the unified event core.

The whole workload starts as one big task on processor 0. A successful steal
*splits* the victim's running task: the thief receives half the remaining
work as a new task, and a **merge task** is created that becomes ready when
both halves complete (``pred = 2``); its processing time is
``merge_alpha + merge_beta · stolen`` (the paper: "depends on the size of the
tasks that proceeded it and the algorithm used"). Merge tasks are pushed to
the deque of the processor that completed their second predecessor, can be
stolen like DAG tasks, but cannot themselves be split. Each split chains the
victim's merge-parent pointer, so the merges form the binary "bring together"
tree of [Roch et al. 2006] prefix-style adaptive algorithms.

Event machinery, victim selection, SWT/MWT and steal-threshold semantics are
shared through ``repro.core.engine`` (DESIGN.md §2); this module defines only
the adaptive :class:`TaskModel` and its public types. Termination follows the
paper's task-engine rule exactly: the simulation ends when the number of
*created* tasks equals the number of *completed* tasks.

Work/time are int32; bit-exact vs ``oracle.simulate_adaptive_oracle``.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax.numpy as jnp

from repro.core import engine as eng
from repro.core.engine import (ACTIVE, EV_ANS_FAIL, EV_ANS_OK,
                               EV_IDLE, EV_REQ_FAIL, EV_REQ_OK, Scenario)
from repro.core.topology import Topology


class AdaptiveSimResult(NamedTuple):
    makespan: jnp.ndarray
    n_events: jnp.ndarray
    n_requests: jnp.ndarray
    n_success: jnp.ndarray
    n_fail: jnp.ndarray
    n_splits: jnp.ndarray       # successful splits (== merge tasks created)
    total_idle: jnp.ndarray
    startup_end: jnp.ndarray
    executed: jnp.ndarray       # int32[p]
    total_merge_work: jnp.ndarray
    n_created: jnp.ndarray
    n_completed: jnp.ndarray
    overflow: jnp.ndarray
    trace: jnp.ndarray        # int32[max_trace, 4] (t, proc, kind, aux)
    n_trace: jnp.ndarray


class AdaptiveState(NamedTuple):
    """Per-model state pytree: the growing task pool + ready-merge deques."""
    cur_task: jnp.ndarray     # int32[p] pool id; -1 none
    # task pool
    tdur: jnp.ndarray         # int32[cap] merge dur / thief-task size at creation
    mpar: jnp.ndarray         # int32[cap] merge parent (-1 root)
    tpred: jnp.ndarray        # int32[cap] remaining preds (merges start at 2)
    is_merge: jnp.ndarray     # bool[cap]
    next_free: jnp.ndarray
    # deques (ready merge tasks)
    buf: jnp.ndarray
    head: jnp.ndarray
    tail: jnp.ndarray
    # counters
    n_created: jnp.ndarray
    n_completed: jnp.ndarray
    n_splits: jnp.ndarray
    total_merge_work: jnp.ndarray


@dataclasses.dataclass(frozen=True)
class AdaptiveEngineConfig:
    topology: Topology
    mwt: bool = False
    merge_alpha: int = 1          # merge dur = alpha + beta * stolen_size
    merge_beta_num: int = 0       # beta as a rational num/den (int arithmetic)
    merge_beta_den: int = 16
    pool_cap: int = 4096          # >= 1 + 2 * max_splits
    deque_cap: int = 256
    max_events: int = 1 << 20
    log_trace: bool = False
    max_trace: int = 0

    @property
    def p(self) -> int:
        return self.topology.p

    def merge_dur(self, s):
        return (jnp.int32(self.merge_alpha)
                + (jnp.asarray(s, jnp.int32) * self.merge_beta_num) // self.merge_beta_den)


@dataclasses.dataclass(frozen=True)
class AdaptiveModel(eng.TaskModel):
    """Adaptive task engine: splittable work + a binary merge-task tree."""
    cfg: AdaptiveEngineConfig

    def init(self, arrays, scn: Scenario, core: eng.CoreState):
        p, cap = self.p, self.cfg.pool_cap
        idle_at = eng.write(core.idle_at, 0, scn.W)
        core = core._replace(
            idle_at=idle_at,
            ev_time=idle_at,
            stolen=jnp.full((p,), -1, jnp.int32),
            executed=eng.write(core.executed, 0, scn.W),
        )
        ms = AdaptiveState(
            cur_task=eng.write(jnp.full((p,), -1, jnp.int32), 0, 0),
            tdur=eng.write(jnp.zeros((cap,), jnp.int32), 0, scn.W),
            mpar=jnp.full((cap,), -1, jnp.int32),
            tpred=jnp.zeros((cap,), jnp.int32),
            is_merge=jnp.zeros((cap,), jnp.bool_),
            next_free=jnp.int32(1),
            buf=jnp.zeros((p, self.cfg.deque_cap), jnp.int32),
            head=jnp.zeros((p,), jnp.int32),
            tail=jnp.zeros((p,), jnp.int32),
            n_created=jnp.int32(1),
            n_completed=jnp.int32(0),
            n_splits=jnp.int32(0),
            total_merge_work=jnp.int32(0),
        )
        return core, ms

    def is_done(self, arrays, core, ms: AdaptiveState, i, t):
        return ms.n_completed >= ms.n_created

    def _push(self, core, ms: AdaptiveState, i, task):
        """Push a ready merge task to i's deque tail (overflow halts)."""
        cap = self.cfg.deque_cap
        tl = eng.read(ms.tail, i)
        ok = tl < cap
        pos = jnp.minimum(tl, cap - 1)
        ms = ms._replace(
            buf=eng.write(ms.buf, (i, pos),
                          jnp.where(ok, task, eng.read(ms.buf, i, pos))),
            tail=eng.add(ms.tail, i, jnp.where(ok, 1, 0)),
        )
        return core._replace(halt=core.halt | ~ok), ms

    def _complete_task(self, core, ms: AdaptiveState, i, c, t):
        """Task c completes on proc i: decrement its merge parent, maybe
        ready it."""
        ms = ms._replace(n_completed=ms.n_completed + 1)
        m = eng.read(ms.mpar, c)
        has_parent = m >= 0
        pc = jnp.where(has_parent,
                       eng.read(ms.tpred, jnp.maximum(m, 0)) - 1, 1)
        ms = ms._replace(tpred=eng.write(
            ms.tpred, jnp.maximum(m, 0),
            jnp.where(has_parent, pc,
                      eng.read(ms.tpred, jnp.maximum(m, 0)))))
        ready = has_parent & (pc == 0)
        return eng.cond(ready, lambda s: self._push(s[0], s[1], i, m),
                        lambda s: s, (core, ms))

    def on_idle(self, arrays, cid, chops, scn, core, ms: AdaptiveState, i, t):
        c = eng.read(ms.cur_task, i)
        core, ms = eng.cond(
            c >= 0, lambda s: self._complete_task(s[0], s[1], i, c, t),
            lambda s: s, (core, ms))
        ms = ms._replace(cur_task=eng.write(ms.cur_task, i, -1))

        finished = self.is_done(arrays, core, ms, i, t)

        def _finish(s):
            core, ms = s
            idle_now = jnp.where(
                (ms.cur_task >= 0) | (jnp.arange(self.p) == i),
                0, t - core.idle_since)
            return eng.finish(self, core, t, idle_now), ms

        def _continue(s):
            core, ms = s
            empty = eng.read(ms.head, i) >= eng.read(ms.tail, i)

            def pop_local(s):
                core, ms = s
                pos = eng.read(ms.tail, i) - 1     # merges: LIFO locally
                task = eng.read(ms.buf, i, pos)
                end = t + eng.read(ms.tdur, task)
                ms = ms._replace(
                    tail=eng.add(ms.tail, i, -1),
                    cur_task=eng.write(ms.cur_task, i, task),
                )
                core = core._replace(
                    idle_at=eng.write(core.idle_at, i, end),
                    ev_time=eng.write(core.ev_time, i, end),
                    executed=eng.add(core.executed, i,
                                     eng.read(ms.tdur, task)),
                )
                return core, ms

            def steal(s):
                core, ms = s
                core = eng.enter_idle(core, i, t)
                core = eng.log(self, core, t, i, EV_IDLE, 0)
                return eng.start_stealing(self, cid, chops, scn, core, i, t), ms

            return eng.cond(empty, steal, pop_local, s)

        return eng.cond(finished, _finish, _continue, (core, ms))

    def on_request(self, arrays, cid, chops, scn, core, ms: AdaptiveState, i, t):
        v = eng.read(core.victim, i)
        d_vi = eng.dist(cid, chops, scn, v, i)
        free = eng.chan_free(self, core, v, t)

        qlen = eng.read(ms.tail, v) - eng.read(ms.head, v)
        can_queue = (qlen > 0) & free

        # split only a *running work* task
        c_v = eng.read(ms.cur_task, v)
        running_work = ((eng.read(core.state, v) == ACTIVE) & (c_v >= 0)
                        & ~eng.read(ms.is_merge, jnp.maximum(c_v, 0)))
        w_v = jnp.where(running_work, eng.read(core.idle_at, v) - t, 0)
        thr = eng.steal_threshold(scn, d_vi)
        amt = w_v // 2
        room = ms.next_free + 2 <= self.cfg.pool_cap
        can_split = running_work & (amt >= 1) & (w_v > thr) & free & room

        def steal_queue(s):
            core, ms = s
            task = eng.read(ms.buf, v, eng.read(ms.head, v))
            ms = ms._replace(head=eng.add(ms.head, v, 1))
            return core, ms, task

        def steal_split(s):
            core, ms = s
            m_id = ms.next_free
            t_id = ms.next_free + 1
            mdur = self.cfg.merge_dur(amt)
            new_idle_v = t + (w_v - amt)
            ms = ms._replace(
                tdur=eng.write(eng.write(ms.tdur, m_id, mdur), t_id, amt),
                mpar=eng.write(eng.write(eng.write(
                    ms.mpar, m_id, eng.read(ms.mpar, c_v)), t_id, m_id),
                    c_v, m_id),
                tpred=eng.write(eng.write(ms.tpred, m_id, 2), t_id, 0),
                is_merge=eng.write(eng.write(ms.is_merge, m_id, True),
                                   t_id, False),
                next_free=ms.next_free + 2,
                n_created=ms.n_created + 2,
                n_splits=ms.n_splits + 1,
                total_merge_work=ms.total_merge_work + mdur,
            )
            core = core._replace(
                idle_at=eng.write(core.idle_at, v, new_idle_v),
                ev_time=eng.write(core.ev_time, v, new_idle_v),
                executed=eng.add(core.executed, v, -amt),
            )
            return core, ms, t_id

        def fail(s):
            core, ms = s
            return core, ms, jnp.int32(-1)

        branch = jnp.where(can_queue, 0, jnp.where(can_split, 1, 2))
        core, ms, payload = eng.switch(
            branch, [steal_queue, steal_split, fail], (core, ms))
        ok = can_queue | can_split
        core = eng.deliver_answer(core, i, v, t, d_vi, ok, payload)
        core = eng.log(self, core, t, i,
                       jnp.where(ok, EV_REQ_OK, EV_REQ_FAIL), v)
        return core, ms

    def on_answer(self, arrays, cid, chops, scn, core, ms: AdaptiveState, i, t):
        task = eng.read(core.stolen, i)
        ok = task >= 0

        def got(s):
            core, ms = s
            end = t + eng.read(ms.tdur, task)
            core = eng.acquire_work(self, core, i, t, end,
                                    eng.read(ms.tdur, task), jnp.int32(-1))
            ms = ms._replace(cur_task=eng.write(ms.cur_task, i, task))
            return eng.log(self, core, t, i, EV_ANS_OK, task), ms

        def retry(s):
            core, ms = s
            core = eng.start_stealing(self, cid, chops, scn, core, i, t)
            return eng.log(self, core, t, i, EV_ANS_FAIL,
                           eng.read(core.victim, i)), ms

        return eng.cond(ok, got, retry, (core, ms))

    def results(self, core: eng.CoreState, ms: AdaptiveState) -> AdaptiveSimResult:
        return AdaptiveSimResult(
            makespan=core.makespan, n_events=core.n_events,
            n_requests=core.n_requests, n_success=core.n_success,
            n_fail=core.n_fail, n_splits=ms.n_splits,
            total_idle=core.total_idle, startup_end=core.startup_end,
            executed=core.executed, total_merge_work=ms.total_merge_work,
            n_created=ms.n_created, n_completed=ms.n_completed,
            overflow=(~core.done) | core.halt,
            trace=core.trace, n_trace=core.n_trace,
        )


def simulate_adaptive(cfg: AdaptiveEngineConfig, scn: Scenario) -> AdaptiveSimResult:
    return eng.simulate(AdaptiveModel(cfg), scn)


def simulate_adaptive_batch(cfg: AdaptiveEngineConfig, scn: Scenario) -> AdaptiveSimResult:
    return eng.simulate_batch(AdaptiveModel(cfg), scn)
