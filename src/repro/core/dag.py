"""DAG-of-tasks task model (paper §2.1.2) over the unified event core.

Each processor keeps a deque of *activated* tasks, a ring of ``cap`` slots
(``TaskDag.deque_bound`` by default, which no run can exceed). An active
processor runs one task; completion decrements the children's predecessor
counts and pushes newly-ready tasks to its own deque end. Idle processors
pop locally (``owner_lifo=True`` = classic ABP: owner pops the newest end,
thieves steal the oldest end, which holds the activated task with the
**largest height** — exactly the steal rule of the paper) or FIFO
(``owner_lifo=False``, the literal reading of the paper's text); steals
always take the head.

Event machinery, victim selection, SWT/MWT and steal-threshold semantics are
shared with every other task model through ``repro.core.engine`` (one pending
event per processor, argmin event selection — DESIGN.md §2); this module
defines only the DAG :class:`TaskModel` and its public types. For DAGs the
steal threshold is a queue-length threshold: a steal fails unless
``len(queue) > theta_static`` (there is no divisible work to meter, matching
the paper's split()->None for DAG tasks).

All int32; bit-exact against ``repro.core.oracle.simulate_dag_oracle``.
"""
from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional

import jax.numpy as jnp
from jax import lax

from repro.core import engine as eng
from repro.core.dag_gen import TaskDag
from repro.core.engine import (EV_ANS_FAIL, EV_ANS_OK,
                               EV_IDLE, EV_REQ_FAIL, EV_REQ_OK, Scenario)
from repro.core.topology import Topology


class DagSimResult(NamedTuple):
    makespan: jnp.ndarray
    n_events: jnp.ndarray
    n_requests: jnp.ndarray
    n_success: jnp.ndarray
    n_fail: jnp.ndarray
    total_idle: jnp.ndarray
    startup_end: jnp.ndarray
    executed: jnp.ndarray      # int32[p] work time executed per processor
    tasks_run: jnp.ndarray     # int32[p] number of tasks run per processor
    n_completed: jnp.ndarray
    overflow: jnp.ndarray      # hit max_events or deque overflow
    trace: jnp.ndarray         # int32[max_trace, 4] (t, proc, kind, aux)
    n_trace: jnp.ndarray


class DagState(NamedTuple):
    """Per-model state pytree: the task engine's deques + activation front."""
    cur_task: jnp.ndarray      # int32[p]; -1 = no running task
    pred: jnp.ndarray          # int32[R, 128] predecessor counts, flat_tiles
    buf: jnp.ndarray           # int32[p, cap] deques, slot = index % cap
    head: jnp.ndarray          # int32[p] tasks ever taken from the head
    tail: jnp.ndarray          # int32[p] head + the deque's length
    tasks_run: jnp.ndarray     # int32[p]
    n_completed: jnp.ndarray


@dataclasses.dataclass(frozen=True)
class DagEngineConfig:
    topology: Topology
    dag: TaskDag
    mwt: bool = False
    owner_lifo: bool = True       # ABP discipline (steal-largest-height)
    # default: the DAG's bound for the discipline (TaskDag.deque_bound),
    # which no run can exceed; a smaller cap halts an overflowing row
    deque_cap: Optional[int] = None
    max_events: int = 1 << 20
    log_trace: bool = False
    max_trace: int = 0

    @property
    def p(self) -> int:
        return self.topology.p

    @property
    def cap(self) -> int:
        if self.deque_cap is None:
            return self.dag.deque_bound(self.owner_lifo)
        return self.deque_cap


@dataclasses.dataclass(frozen=True)
class DagModel(eng.TaskModel):
    """DAG task engine: work is a static precedence graph of unit tasks."""
    cfg: DagEngineConfig

    def static_arrays(self):
        """``dur``, ``child_ptr``, ``child_idx`` and ``pred_count`` as
        :func:`engine.flat_tiles`, read through ``engine.read_flat``."""
        dag = self.cfg.dag
        return tuple(jnp.asarray(eng.flat_tiles(a)) for a in (
            dag.dur, dag.child_ptr, dag.child_idx, dag.pred_count))

    def init(self, arrays, scn: Scenario, core: eng.CoreState):
        dur, _, _, pred0 = arrays
        p = self.p
        src = int(self.cfg.dag.sources[0])
        core = core._replace(
            ev_time=eng.write(core.ev_time, 0, eng.read_flat(dur, src)),
            stolen=jnp.full((p,), -1, jnp.int32),
        )
        ms = DagState(
            cur_task=eng.write(jnp.full((p,), -1, jnp.int32), 0, src),
            pred=pred0,
            buf=jnp.zeros((p, self.cfg.cap), jnp.int32),
            head=jnp.zeros((p,), jnp.int32),
            tail=jnp.zeros((p,), jnp.int32),
            tasks_run=jnp.zeros((p,), jnp.int32),
            n_completed=jnp.int32(0),
        )
        return core, ms

    def is_done(self, arrays, core, ms: DagState, i, t):
        return ms.n_completed >= self.cfg.dag.n

    def _activate_children(self, cptr, cidx, core, ms: DagState, i, c):
        """end_execute_task(): decrement preds of c's children; push ready
        ones to i's own deque tail (capacity overflow halts the engine)."""
        cap = self.cfg.cap

        def body(k, s):
            core, ms = s
            child = eng.read_flat(cidx, k)
            pc = eng.read_flat(ms.pred, child) - 1
            ready = pc == 0
            tl = eng.read(ms.tail, i)
            ok = tl - eng.read(ms.head, i) < cap
            pos = lax.rem(tl, cap)
            ms = ms._replace(
                pred=eng.write_flat(ms.pred, child, pc),
                buf=eng.write(ms.buf, (i, pos),
                              jnp.where(ready & ok, child,
                                        eng.read(ms.buf, i, pos))),
                tail=eng.add(ms.tail, i, jnp.where(ready & ok, 1, 0)),
            )
            core = core._replace(halt=core.halt | (ready & ~ok))
            return core, ms

        return eng.fori_loop(eng.read_flat(cptr, c),
                             eng.read_flat(cptr, c + 1), body, (core, ms),
                             self.cfg.dag.max_children)

    def on_idle(self, arrays, cid, chops, scn, core, ms: DagState, i, t):
        dur, cptr, cidx, _ = arrays
        c = eng.read(ms.cur_task, i)
        has_task = c >= 0

        def complete(s):
            core, ms = s
            ms = ms._replace(n_completed=ms.n_completed + 1,
                             tasks_run=eng.add(ms.tasks_run, i, 1))
            core = core._replace(
                executed=eng.add(core.executed, i, eng.read_flat(dur, c)))
            return self._activate_children(cptr, cidx, core, ms, i, c)

        core, ms = eng.cond(has_task, complete, lambda s: s, (core, ms))
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
                if self.cfg.owner_lifo:
                    pos = eng.read(ms.tail, i) - 1
                    ms = ms._replace(tail=eng.add(ms.tail, i, -1))
                else:
                    pos = eng.read(ms.head, i)
                    ms = ms._replace(head=eng.add(ms.head, i, 1))
                task = eng.read(ms.buf, i, lax.rem(pos, self.cfg.cap))
                ms = ms._replace(cur_task=eng.write(ms.cur_task, i, task))
                core = core._replace(
                    ev_time=eng.write(core.ev_time, i,
                                      t + eng.read_flat(dur, task)))
                return core, ms

            def steal(s):
                core, ms = s
                core = eng.enter_idle(core, i, t)
                core = eng.log(self, core, t, i, EV_IDLE, 0)
                return eng.start_stealing(self, cid, chops, scn, core, i, t), ms

            return eng.cond(empty, steal, pop_local, s)

        return eng.cond(finished, _finish, _continue, (core, ms))

    def on_request(self, arrays, cid, chops, scn, core, ms: DagState, i, t):
        v = eng.read(core.victim, i)
        qlen = eng.read(ms.tail, v) - eng.read(ms.head, v)
        d_vi = eng.dist(cid, chops, scn, v, i)
        free = eng.chan_free(self, core, v, t)
        ok = (qlen > scn.theta_static) & free
        slot = lax.rem(eng.read(ms.head, v), self.cfg.cap)
        task = jnp.where(ok, eng.read(ms.buf, v, slot), -1)
        ms = ms._replace(head=eng.add(ms.head, v, jnp.where(ok, 1, 0)))
        core = eng.deliver_answer(core, i, v, t, d_vi, ok, task)
        core = eng.log(self, core, t, i,
                       jnp.where(ok, EV_REQ_OK, EV_REQ_FAIL), v)
        return core, ms

    def on_answer(self, arrays, cid, chops, scn, core, ms: DagState, i, t):
        dur = arrays[0]
        task = eng.read(core.stolen, i)
        ok = task >= 0

        def got(s):
            core, ms = s
            core = eng.acquire_work(self, core, i, t,
                                    t + eng.read_flat(dur, task),
                                    jnp.int32(0), jnp.int32(-1))
            ms = ms._replace(cur_task=eng.write(ms.cur_task, i, task))
            return eng.log(self, core, t, i, EV_ANS_OK, task), ms

        def retry(s):
            core, ms = s
            core = eng.start_stealing(self, cid, chops, scn, core, i, t)
            return eng.log(self, core, t, i, EV_ANS_FAIL,
                           eng.read(core.victim, i)), ms

        return eng.cond(ok, got, retry, (core, ms))

    def results(self, core: eng.CoreState, ms: DagState) -> DagSimResult:
        return DagSimResult(
            makespan=core.makespan, n_events=core.n_events,
            n_requests=core.n_requests, n_success=core.n_success,
            n_fail=core.n_fail, total_idle=core.total_idle,
            startup_end=core.startup_end, executed=core.executed,
            tasks_run=ms.tasks_run, n_completed=ms.n_completed,
            overflow=(~core.done) | core.halt,
            trace=core.trace, n_trace=core.n_trace,
        )


def simulate_dag(cfg: DagEngineConfig, scn: Scenario) -> DagSimResult:
    return eng.simulate(DagModel(cfg), scn)


def simulate_dag_batch(cfg: DagEngineConfig, scn: Scenario) -> DagSimResult:
    return eng.simulate_batch(DagModel(cfg), scn)
