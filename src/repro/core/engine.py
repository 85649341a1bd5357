"""Unified Work-Stealing discrete-event core (DESIGN.md §2).

The paper's architecture is one event/processor engine parameterized by a
pluggable *task engine* (§2.1, §3). This module is that engine: every piece
of machinery that is independent of the task model lives here —

* the one-pending-event-per-processor state (:class:`CoreState`): the global
  event heap of the serial simulator collapses to ``argmin(ev_time)`` over a
  dense int32 vector, which vectorizes on the VPU and vmaps across scenarios;
* the three-state processor machine (``ACTIVE`` / ``REQ_FLIGHT`` /
  ``ANS_FLIGHT``) and the event dispatch ``lax.switch`` on it;
* SWT/MWT answer-channel policy (:func:`chan_free`, paper §2.4.1) and the
  bookkeeping shared by every steal answer (:func:`deliver_answer`);
* victim-selection dispatch over the topology strategies (§2.3/§3.3) and the
  per-processor xorshift32 PRNG lanes;
* trace logging (the log engine, §3.5) and result accumulation (event,
  request, success/fail, idle-time and startup counters).

A *task model* supplies what the paper calls the task engine: how work is
represented, surrendered to a thief, and detected as exhausted. It is a
hashable (frozen-dataclass) object implementing:

``static_arrays()``
    per-model constant arrays (e.g. DAG durations/edges) threaded explicitly
    so the Pallas kernel can feed them as refs instead of closure constants;
``init(arrays, scn, core) -> (core, ms)``
    patch the freshly built :class:`CoreState` and build the model-state
    pytree ``ms`` (deques, task pools, predecessor counts, ...);
``on_idle / on_request / on_answer (arrays, cid, chops, scn, core, ms, i, t)``
    the three event handlers, each returning ``(core, ms)``; ``chops`` is
    the topology's k×k cluster hop table flattened to int32[k*k];
``is_done(arrays, core, ms, i, t)``
    the termination predicate, used by the model's ``on_idle``;
``results(core, ms)``
    fold the final state into the model's public result NamedTuple.

The concrete models are ``divisible.DivisibleModel``, ``dag.DagModel`` and
``adaptive.AdaptiveModel``; each is bit-exact against its serial numpy twin
in ``repro.core.oracle``. Because handlers are plain traced JAX, the same
``_simulate_impl`` body runs as ordinary jit/vmap code (one row chunk a
device, ``backend.ExecutionBackend._device_chunks``), or inside the Pallas
kernel (``kernels.ws_sim``) with all state VMEM-resident, one row or a block
of rows (:func:`simulate_block`) a grid step.
"""
from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import functools
import math
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro import obs
from repro.core import topology as topo_mod
from repro.core.topology import Topology

INF32 = np.int32(2**31 - 1)

#: Version of the event-loop semantics. Bumped whenever a change alters any
#: result a simulation can produce (event ordering, PRNG, accounting); part
#: of the content-addressed key of the service result store
#: (``repro.service.store``), so stale cached sweeps can never be replayed
#: against a newer engine.
ENGINE_VERSION = 2

# Processor states (values are the lax.switch branch index).
ACTIVE = 0
REQ_FLIGHT = 1
ANS_FLIGHT = 2

# Trace event kinds (log engine).
EV_IDLE = 0          # aux = 0
EV_REQ_FAIL = 1      # aux = victim
EV_REQ_OK = 2        # aux = victim (stolen amount recoverable from ANS_OK)
EV_ANS_FAIL = 3      # aux = next victim chosen
EV_ANS_OK = 4        # aux = stolen amount


class Scenario(NamedTuple):
    """Dynamic (traced, vmappable) per-simulation parameters.

    Shared by every task model; ``W`` is the divisible/adaptive workload and
    is ignored by DAG scenarios (the DAG itself is static configuration).
    ``max_events`` is a *per-scenario* event budget: the loop stops at
    ``min(model.max_events, scn.max_events)`` events, so one compiled program
    whose static cap was relaxed upward can still reproduce each row's
    smaller-budget run bit-for-bit (the broker's cross-bucket coalescing —
    DESIGN.md §7). ``INF32`` (the default) defers entirely to the model cap.
    """
    W: jnp.ndarray            # int32 total unit tasks
    seed: jnp.ndarray         # uint32 scenario seed
    lam_local: jnp.ndarray    # int32 intra-cluster delay
    lam_remote: jnp.ndarray   # int32 per-hop inter-cluster delay
    theta_static: jnp.ndarray  # int32 steal-threshold constant
    theta_comm: jnp.ndarray    # int32 steal-threshold per unit of distance
    remote_prob: jnp.ndarray   # uint32 fixed-point P(remote) for LOCAL_FIRST
    max_events: jnp.ndarray    # int32 per-row event budget (INF32: model cap)


def make_scenario(W, seed, lam=1, lam_local=None, lam_remote=None,
                  theta_static=0, theta_comm=0, remote_prob=0.25,
                  max_events=None) -> Scenario:
    """Convenience constructor. ``lam`` sets both latencies (one-cluster use)."""
    ll = lam if lam_local is None else lam_local
    lr = lam if lam_remote is None else lam_remote
    budget = INF32 if max_events is None else max_events
    return Scenario(
        W=jnp.asarray(W, jnp.int32),
        seed=jnp.asarray(seed, jnp.uint32),
        lam_local=jnp.asarray(ll, jnp.int32),
        lam_remote=jnp.asarray(lr, jnp.int32),
        theta_static=jnp.asarray(theta_static, jnp.int32),
        theta_comm=jnp.asarray(theta_comm, jnp.int32),
        remote_prob=jnp.asarray(topo_mod.remote_prob_u32(remote_prob), jnp.uint32),
        max_events=jnp.asarray(budget, jnp.int32),
    )


def batch_scenarios(W, seeds, lam=1, **kw) -> Scenario:
    """Broadcast scalars against a seed vector into a batched Scenario."""
    seeds = jnp.asarray(seeds, jnp.uint32)
    n = seeds.shape[0]

    def bcast(x, dtype):
        x = jnp.asarray(x, dtype)
        return jnp.broadcast_to(x, (n,)) if x.ndim == 0 else x

    base = make_scenario(W, 0, lam=lam, **kw)
    return Scenario(
        W=bcast(base.W, jnp.int32),
        seed=seeds,
        lam_local=bcast(base.lam_local, jnp.int32),
        lam_remote=bcast(base.lam_remote, jnp.int32),
        theta_static=bcast(base.theta_static, jnp.int32),
        theta_comm=bcast(base.theta_comm, jnp.int32),
        remote_prob=bcast(base.remote_prob, jnp.uint32),
        max_events=bcast(base.max_events, jnp.int32),
    )


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Static compile-time configuration shared by every task model."""
    topology: Topology
    mwt: bool = False                 # multiple work transfers (paper §2.4.1)
    max_events: int = 1 << 20
    log_trace: bool = False
    max_trace: int = 0                # rows kept when log_trace

    @property
    def p(self) -> int:
        return self.topology.p


class CoreState(NamedTuple):
    """Model-independent engine state (one pending event per processor)."""
    t: jnp.ndarray
    state: jnp.ndarray        # int32[p] ACTIVE / REQ_FLIGHT / ANS_FLIGHT
    idle_at: jnp.ndarray      # int32[p] completion time of running work
    ev_time: jnp.ndarray      # int32[p] the pending event per processor
    victim: jnp.ndarray       # int32[p]
    stolen: jnp.ndarray       # int32[p] in-flight payload (model-defined)
    busy_until: jnp.ndarray   # int32[p] SWT answer-channel horizon
    rng: jnp.ndarray          # uint32[p] xorshift32 lanes
    rr_aux: jnp.ndarray       # int32[p] round-robin cursor
    idle_since: jnp.ndarray   # int32[p]
    executed: jnp.ndarray     # int32[p] work executed per processor
    active_count: jnp.ndarray
    n_events: jnp.ndarray
    n_requests: jnp.ndarray
    n_success: jnp.ndarray
    n_fail: jnp.ndarray
    total_idle: jnp.ndarray
    startup_end: jnp.ndarray  # first time all p procs active (-1: never)
    makespan: jnp.ndarray
    done: jnp.ndarray
    halt: jnp.ndarray         # model-signaled abnormal stop (capacity overflow)
    trace: jnp.ndarray        # int32[max_trace, 4] (t, proc, kind, aux)
    n_trace: jnp.ndarray


class TaskModel:
    """Base class for task models: forwards static config from ``self.cfg``.

    Subclasses are frozen dataclasses with a single ``cfg`` field (hashable,
    so compiled simulators cache per model) implementing the hook methods
    documented in the module docstring.
    """

    def __post_init__(self):
        # the event core reads distances from the cluster hop table: refuse
        # a topology that has none here, where the model is built
        self.topology.cluster_hops

    @property
    def topology(self) -> Topology:
        return self.cfg.topology

    @property
    def p(self) -> int:
        return self.cfg.topology.p

    @property
    def mwt(self) -> bool:
        return self.cfg.mwt

    @property
    def max_events(self) -> int:
        return self.cfg.max_events

    @property
    def log_trace(self) -> bool:
        return getattr(self.cfg, "log_trace", False)

    @property
    def max_trace(self) -> int:
        return getattr(self.cfg, "max_trace", 0)

    def static_arrays(self) -> Tuple[jnp.ndarray, ...]:
        return ()


# ---------------------------------------------------------------------------
# Shared machinery: distance, victim selection, stealing, answers, logging.
# ---------------------------------------------------------------------------

_SELECT_FORMS = contextvars.ContextVar("select_forms", default=False)
_EVERY_BRANCH = contextvars.ContextVar("every_branch", default=False)


@contextlib.contextmanager
def select_forms(every_branch: bool = False):
    """Trace the event core with one-hot select forms (the Pallas kernel
    body, ``kernels.ws_sim``). Mosaic has no lowering for ``scatter``,
    ``scatter-add``, ``dynamic_slice`` or an int32 ``argmin``; the helpers
    below emit those forms under XLA and masks, selects and reductions here.
    All operands are int32/uint32/bool, so both forms are exact.

    ``every_branch`` (a block of rows, :func:`simulate_block`): a
    :func:`switch` runs every branch and selects, as each row of the block
    takes its own branch."""
    tokens = (_SELECT_FORMS.set(True), _EVERY_BRANCH.set(every_branch))
    try:
        yield
    finally:
        _EVERY_BRANCH.reset(tokens[1])
        _SELECT_FORMS.reset(tokens[0])


def _hit(x, idx):
    """Bool mask over ``x``'s shape that is true at leading index ``idx``."""
    m = None
    for d, i in enumerate(idx):
        h = lax.broadcasted_iota(jnp.int32, x.shape, d) == i
        m = h if m is None else m & h
    return m


def _select(m, a, b):
    """``where(m, a, b)``; bool operands as logic ops, since Mosaic cannot
    select between bool vectors."""
    if jnp.result_type(b) == jnp.bool_:
        return (m & a) | (~m & b)
    return jnp.where(m, a, b)


def read(x, *idx):
    """``x[idx]``: an element, or a row when ``idx`` indexes fewer dims."""
    if not _SELECT_FORMS.get():
        return x[idx[0]] if len(idx) == 1 else x[idx]
    axes = tuple(range(len(idx)))
    m = _hit(x, idx)
    if x.dtype == jnp.bool_:
        return jnp.sum((m & x).astype(jnp.int32), axis=axes) > 0
    if jnp.issubdtype(x.dtype, jnp.unsignedinteger):
        xi = lax.bitcast_convert_type(x, jnp.int32)
        return jnp.sum(jnp.where(m, xi, 0), axis=axes).astype(x.dtype)
    return jnp.sum(jnp.where(m, x, 0), axis=axes)


def write(x, idx, v):
    """``x.at[idx].set(v)`` (``idx`` an index or a tuple of indices)."""
    if not _SELECT_FORMS.get():
        return x.at[idx].set(v)
    idx = idx if isinstance(idx, tuple) else (idx,)
    return _select(_hit(x, idx), jnp.asarray(v, x.dtype), x)


def add(x, idx, v):
    """``x.at[idx].add(v)``."""
    if not _SELECT_FORMS.get():
        return x.at[idx].add(v)
    idx = idx if isinstance(idx, tuple) else (idx,)
    return jnp.where(_hit(x, idx), x + jnp.asarray(v, x.dtype), x)


def argmin(x):
    """First index of the minimum of a 1-D vector, as int32."""
    if not _SELECT_FORMS.get():
        return jnp.argmin(x).astype(jnp.int32)
    iota = lax.broadcasted_iota(jnp.int32, x.shape, 0)
    return jnp.min(jnp.where(x == jnp.min(x), iota, x.shape[0]))


def cond(pred, true_fun, false_fun, operand):
    """``lax.cond``. In the kernel body both branches run and a select
    picks: Mosaic's canonicalizer turns an ``scf.if`` whose branches yield
    values computed before it into a select on a scalar predicate over
    vectors, which it then cannot lower."""
    if not _SELECT_FORMS.get():
        return lax.cond(pred, true_fun, false_fun, operand)
    return jax.tree.map(lambda a, b: _select(pred, a, b),
                        true_fun(operand), false_fun(operand))


def _is_flags(x) -> bool:
    return x.dtype == jnp.bool_ and x.ndim > 0


def _to_i32(tree):
    return jax.tree.map(lambda x: x.astype(jnp.int32) if _is_flags(x) else x,
                        tree)


def _via_i32(fn, like):
    """``fn`` over int32 stand-ins for the bool vectors of ``like``: it
    takes and returns int32 where ``fn`` takes and returns bool vectors."""
    leaves, tree = jax.tree.flatten(like)
    flags = [_is_flags(x) for x in leaves]

    def wrapped(*args):
        xs = [x != 0 if f else x
              for x, f in zip(jax.tree.leaves(args), flags)]
        return _to_i32(fn(*jax.tree.unflatten(tree, xs)))

    return wrapped


def _from_i32(tree, like):
    return jax.tree.map(lambda x, l: x != 0 if _is_flags(l) else x,
                        tree, like)


def switch(index, branches, *operands):
    """``lax.switch``. In the kernel body, bool vectors cross the branches
    as int32: Mosaic cannot legalize an ``scf.if`` that yields them."""
    if not _SELECT_FORMS.get():
        return lax.switch(index, branches, *operands)
    if _EVERY_BRANCH.get():
        outs = [f(*operands) for f in branches]
        out = outs[-1]
        for k in range(len(outs) - 2, -1, -1):
            out = jax.tree.map(functools.partial(_select, index == k),
                               outs[k], out)
        return out
    out_like = jax.eval_shape(branches[0], *operands)
    out = lax.switch(index, [_via_i32(f, operands) for f in branches],
                     *_to_i32(operands))
    return _from_i32(out, out_like)


def while_loop(cond_fun, body_fun, init_val):
    """``lax.while_loop``; in the kernel body bool vectors of the carry
    travel as int32, as in :func:`switch`."""
    if not _SELECT_FORMS.get():
        return lax.while_loop(cond_fun, body_fun, init_val)
    out = lax.while_loop(_via_i32(cond_fun, (init_val,)),
                         _via_i32(body_fun, (init_val,)), _to_i32(init_val))
    return _from_i32(out, init_val)


def fori_loop(lower, upper, body_fun, init_val, max_trips: int):
    """``lax.fori_loop`` with traced bounds, ``upper - lower <= max_trips``.
    In the kernel body it is unrolled ``max_trips`` times, each trip
    selected on ``k < upper``: Mosaic crashes on a loop with 1-D vector
    carries inside a branch of the event dispatch."""
    if not _SELECT_FORMS.get():
        return lax.fori_loop(lower, upper, body_fun, init_val)
    x = init_val
    for r in range(max_trips):
        k = lower + r
        x = cond(k < upper, lambda x, k=k: body_fun(k, x), lambda x: x, x)
    return x


def first_true(mask):
    """First true index of a 1-D bool vector (0 when none), as int32."""
    if not _SELECT_FORMS.get():
        return jnp.argmax(mask).astype(jnp.int32)
    n = mask.shape[0]
    k = jnp.min(jnp.where(mask, lax.broadcasted_iota(jnp.int32, (n,), 0), n))
    return jnp.where(k == n, 0, k)


def cluster_hop_table(topology: Topology) -> np.ndarray:
    """The event core's distance structure: ``topology.cluster_hops``
    flattened to int32[k*k]; a pass over k² lanes where ``hops`` is p×p."""
    return topology.cluster_hops.reshape(-1)


def _n_clusters(chops) -> int:
    return math.isqrt(chops.shape[0])


def dist(cid, chops, scn: Scenario, i, j):
    """Scalar distance d(i, j) under the scenario's latency scalars."""
    ci, cj = read(cid, i), read(cid, j)
    hops = read(chops, ci * _n_clusters(chops) + cj)
    d = jnp.where(ci == cj, scn.lam_local, scn.lam_remote * hops)
    return jnp.where(i == j, jnp.int32(0), d).astype(jnp.int32)


def hops_from(cid, chops, ci):
    """int32[p] hop counts from cluster ``ci`` to each processor's cluster,
    ``chops[ci * k + cid]``; in the kernel body one select over the p lanes
    per cluster."""
    k = _n_clusters(chops)
    if not _SELECT_FORMS.get():
        return chops[ci * k + cid]
    out = jnp.zeros(cid.shape, chops.dtype)
    for c in range(k):
        out = jnp.where(cid == c, read(chops, ci * k + c), out)
    return out


def select_victim(strategy: int, p: int, cid, chops, scn: Scenario,
                  rng_i, rr_i, i):
    """Victim selection (topology engine §3.3); returns (victim, rng', rr')."""
    if strategy == topo_mod.UNIFORM:
        rng_i = topo_mod.xorshift32(rng_i)
        v = (rng_i % jnp.uint32(p - 1)).astype(jnp.int32)
        v = v + (v >= i).astype(jnp.int32)
        return v, rng_i, rr_i
    if strategy == topo_mod.LOCAL_FIRST:
        rng_i = topo_mod.xorshift32(rng_i)
        go_remote = rng_i < scn.remote_prob
        rng_i = topo_mod.xorshift32(rng_i)
        my = read(cid, i)
        idx = jnp.arange(p, dtype=jnp.int32)
        local_mask = (cid == my) & (idx != i)
        remote_mask = cid != my
        mask = jnp.where(go_remote, remote_mask, local_mask)
        n = jnp.maximum(mask.sum().astype(jnp.uint32), jnp.uint32(1))
        k = (rng_i % n).astype(jnp.int32)
        csum = jnp.cumsum(mask.astype(jnp.int32))
        v = first_true(csum > k)
        v = jnp.where(v == i, (i + 1) % p, v)  # only if both masks empty
        return v, rng_i, rr_i
    if strategy == topo_mod.INV_DISTANCE:
        idx = jnp.arange(p, dtype=jnp.int32)
        ci = read(cid, i)
        d = jnp.where(cid == ci, scn.lam_local,
                      scn.lam_remote * hops_from(cid, chops, ci)
                      ).astype(jnp.float32)
        w = jnp.where(idx == i, 0.0, 1.0 / jnp.maximum(d, 1.0))
        c = jnp.cumsum(w)
        rng_i = topo_mod.xorshift32(rng_i)
        u = (rng_i.astype(jnp.float32) / jnp.float32(2**32)) * c[-1]
        v = first_true(c > u)
        v = jnp.where(v == i, (i + 1) % p, v)
        return v, rng_i, rr_i
    if strategy == topo_mod.ROUND_ROBIN:
        nxt = (rr_i + 1) % jnp.int32(p)
        nxt = jnp.where(nxt == i, (nxt + 1) % jnp.int32(p), nxt)
        return nxt, rng_i, nxt
    raise ValueError(f"unknown strategy {strategy}")


def start_stealing(model: TaskModel, cid, chops, scn: Scenario,
                   core: CoreState, i, t) -> CoreState:
    """processor engine start_stealing(): pick victim, emit request event."""
    v, rng_i, rr_i = select_victim(model.topology.strategy, model.p, cid,
                                   chops, scn, read(core.rng, i),
                                   read(core.rr_aux, i), i)
    d = dist(cid, chops, scn, i, v)
    return core._replace(
        state=write(core.state, i, REQ_FLIGHT),
        victim=write(core.victim, i, v),
        ev_time=write(core.ev_time, i, t + d),
        rng=write(core.rng, i, rng_i),
        rr_aux=write(core.rr_aux, i, rr_i),
    )


def enter_idle(core: CoreState, i, t) -> CoreState:
    """Bookkeeping when processor i runs out of work (before it steals)."""
    return core._replace(active_count=core.active_count - 1,
                         idle_since=write(core.idle_since, i, t))


def chan_free(model: TaskModel, core: CoreState, v, t):
    """SWT/MWT answer-channel policy (paper §2.4.1): under SWT a victim
    refuses while a previous answer is still in flight."""
    return jnp.bool_(model.mwt) | (t >= read(core.busy_until, v))


def steal_threshold(scn: Scenario, d_vi):
    """Steal threshold of §2.4.2: θ_static + θ_comm · d(v, i)."""
    return scn.theta_static + scn.theta_comm * d_vi


def deliver_answer(core: CoreState, i, v, t, d_vi, ok, payload) -> CoreState:
    """Answer bookkeeping shared by every model's on_request: occupy the
    victim's answer channel on success, put ``payload`` in flight toward the
    thief, and account the request."""
    return core._replace(
        busy_until=write(core.busy_until, v,
                         jnp.where(ok, t + d_vi, read(core.busy_until, v))),
        stolen=write(core.stolen, i, payload),
        state=write(core.state, i, ANS_FLIGHT),
        ev_time=write(core.ev_time, i, t + d_vi),
        n_requests=core.n_requests + 1,
        n_success=core.n_success + ok.astype(jnp.int32),
        n_fail=core.n_fail + (~ok).astype(jnp.int32),
    )


def acquire_work(model: TaskModel, core: CoreState, i, t, end, exec_add,
                 stolen_reset) -> CoreState:
    """Thief i becomes ACTIVE until ``end``: shared part of every model's
    successful on_answer (idle-time and startup accounting)."""
    new_active = core.active_count + 1
    first_full = (new_active == model.p) & (core.startup_end < 0)
    return core._replace(
        state=write(core.state, i, ACTIVE),
        idle_at=write(core.idle_at, i, end),
        ev_time=write(core.ev_time, i, end),
        stolen=write(core.stolen, i, stolen_reset),
        executed=add(core.executed, i, exec_add),
        active_count=new_active,
        total_idle=core.total_idle + (t - read(core.idle_since, i)),
        startup_end=jnp.where(first_full, t, core.startup_end),
    )


def finish(model: TaskModel, core: CoreState, t, idle_now) -> CoreState:
    """Terminate: freeze the event vector and account terminal idle time
    (``idle_now`` is the model's int32[p] per-processor idle contribution)."""
    return core._replace(
        done=jnp.bool_(True),
        makespan=t,
        ev_time=jnp.full((model.p,), INF32, jnp.int32),
        total_idle=core.total_idle + jnp.sum(idle_now),
    )


def log(model: TaskModel, core: CoreState, t, proc, kind, aux) -> CoreState:
    """Append one row to the trace ring (log engine); no-op when disabled."""
    if not model.log_trace:
        return core
    row = jnp.stack([t, proc, jnp.int32(kind), jnp.asarray(aux, jnp.int32)])
    idx = jnp.minimum(core.n_trace, model.max_trace - 1)
    keep = core.n_trace < model.max_trace
    row = jnp.where(keep, row, read(core.trace, idx))
    if _SELECT_FORMS.get():
        trace = write(core.trace, idx, row)
    else:
        trace = lax.dynamic_update_slice(core.trace, row[None, :],
                                         (idx, jnp.int32(0)))
    return core._replace(trace=trace,
                         n_trace=core.n_trace + keep.astype(jnp.int32))


# ---------------------------------------------------------------------------
# The event loop.
# ---------------------------------------------------------------------------

def init_core(model: TaskModel, scn: Scenario) -> CoreState:
    """Generic initial state; the model patches proc 0 (all work starts
    there) and its own payload conventions in ``init``."""
    p = model.p
    idx = jnp.arange(p, dtype=jnp.uint32)
    rng = jax.vmap(topo_mod.seed_state, in_axes=(None, 0))(scn.seed, idx)
    max_trace = max(model.max_trace, 1) if model.log_trace else 1
    return CoreState(
        t=jnp.int32(0),
        state=jnp.full((p,), ACTIVE, jnp.int32),
        idle_at=jnp.zeros((p,), jnp.int32),
        ev_time=jnp.zeros((p,), jnp.int32),
        victim=jnp.zeros((p,), jnp.int32),
        stolen=jnp.zeros((p,), jnp.int32),
        busy_until=jnp.zeros((p,), jnp.int32),
        rng=rng,
        rr_aux=jnp.arange(p, dtype=jnp.int32),
        idle_since=jnp.zeros((p,), jnp.int32),
        executed=jnp.zeros((p,), jnp.int32),
        active_count=jnp.int32(p),
        n_events=jnp.int32(0),
        n_requests=jnp.int32(0),
        n_success=jnp.int32(0),
        n_fail=jnp.int32(0),
        total_idle=jnp.int32(0),
        startup_end=jnp.int32(-1),
        makespan=jnp.int32(-1),
        done=jnp.bool_(False),
        halt=jnp.bool_(False),
        trace=jnp.zeros((max_trace, 4), jnp.int32),
        n_trace=jnp.int32(0),
    )


def _budget(model: TaskModel, scn: Scenario):
    """Per-row event budget: the static model cap bounds the compiled loop,
    the (traced) scenario budget truncates it per row — a row dispatched
    under a relaxed static cap is bit-identical to a run whose static cap
    equals its budget, because the loop freezes each row at its own cond."""
    return jnp.minimum(jnp.int32(model.max_events),
                       jnp.asarray(scn.max_events, jnp.int32))


def _live(core: CoreState, budget):
    return (~core.done) & (core.n_events < budget) & (~core.halt)


def _event(model: TaskModel, cid, chops, arrays, scn: Scenario, c, m):
    """One event of one row: the handler of the processor whose pending
    event is earliest."""
    handlers = [functools.partial(h, arrays, cid, chops, scn)
                for h in (model.on_idle, model.on_request, model.on_answer)]
    i = argmin(c.ev_time)
    t = read(c.ev_time, i)
    c = c._replace(t=t, n_events=c.n_events + 1)
    return switch(read(c.state, i), handlers, c, m, i, t)


def _simulate_impl(model: TaskModel, cid, chops, arrays, scn: Scenario):
    """Event loop with every array input passed explicitly (Pallas-friendly:
    the kernel feeds cid/chops/model arrays as refs, not closure constants)."""
    core, ms = model.init(arrays, scn, init_core(model, scn))
    budget = _budget(model, scn)

    def body(s):
        return _event(model, cid, chops, arrays, scn, *s)

    core, ms = while_loop(lambda s: _live(s[0], budget), body, (core, ms))
    return model.results(core, ms)


def simulate_block(model: TaskModel, cid, chops, arrays, scn: Scenario):
    """:func:`_simulate_impl` over a block of rows (every leaf of ``scn``
    has a leading block axis), written out for the kernel, where a vmapped
    ``while_loop`` does not lower: the loop runs while any row is live, the
    body is the vmapped one-row event, and each row's carry takes a select
    on its own live mask. So every row runs exactly its own event
    sequence, counters and budget. Traced under
    ``select_forms(every_branch=True)``, the event's switch is selects."""
    def one(s):
        return model.init(arrays, s, init_core(model, s))

    core, ms = jax.vmap(one)(scn)
    budget = _budget(model, scn)

    def cond(s):
        return jnp.max(_live(s[0], budget).astype(jnp.int32)) > 0

    def body(s):
        live = _live(s[0], budget)
        new = jax.vmap(functools.partial(_event, model, cid, chops, arrays))(
            scn, *s)
        return jax.tree.map(
            lambda a, b: _select(
                live.reshape(live.shape + (1,) * (a.ndim - 1)), a, b),
            new, s)

    core, ms = while_loop(cond, body, (core, ms))
    return jax.vmap(model.results)(core, ms)


def _simulate(model: TaskModel, scn: Scenario):
    return _simulate_impl(model, jnp.asarray(model.topology.cluster_id),
                          jnp.asarray(cluster_hop_table(model.topology)),
                          model.static_arrays(), scn)


# ---------------------------------------------------------------------------
# Segmented execution: the same event loop, cut into fixed-size event
# segments with host-side active-lane compaction between them (DESIGN.md §8).
#
# Under vmap, one monolithic while_loop convoys: every lane pays
# max(events-over-lanes) iterations, so a batch costs n_rows x max(events)
# instead of sum(events). Segmenting the loop lets the host harvest finished
# lanes between segments and gather the survivors into a smaller (pow2)
# batch, so dead lanes stop burning VPU cycles. Each lane's event sequence
# is untouched -- the inner loop body is byte-for-byte `_simulate_impl`'s
# body and lanes are independent under vmap -- so results are bit-identical
# to the monolithic loop (same ENGINE_VERSION, same store keys).
# ---------------------------------------------------------------------------


def _pow2ceil(n: int) -> int:
    return 1 << max(int(n) - 1, 0).bit_length() if n > 1 else 1


def default_segment_len(max_events: int, ev_budget=None) -> int:
    """Segment length for the segmented driver, derived from the static
    model cap and (when present) the per-row event budgets: small caps run
    as a single exact segment, large caps use short segments so finished
    lanes are harvested (and the batch compacted) long before the stragglers
    finish."""
    base = int(max_events)
    if ev_budget is not None:
        b = np.asarray(ev_budget, np.int64)
        pos = b[b > 0]
        if pos.size:
            base = int(min(base, int(pos.min())))
    return int(max(32, min(128, _pow2ceil(base))))


def _segment_impl(model: TaskModel, cid, chops, arrays, scn: Scenario,
                  core: CoreState, ms, seg_len: int):
    """Run up to ``seg_len`` further events of one lane. The loop body and
    termination condition are identical to :func:`_simulate_impl`; the only
    extra clause is the per-segment event counter, so chaining segments
    reproduces the monolithic loop exactly."""
    budget = _budget(model, scn)

    def cond(s):
        c, _, k = s
        return _live(c, budget) & (k < seg_len)

    def body(s):
        c, m, k = s
        c, m = _event(model, cid, chops, arrays, scn, c, m)
        return (c, m, k + jnp.int32(1))

    core, ms, k = lax.while_loop(cond, body, (core, ms, jnp.int32(0)))
    fin = core.done | core.halt | (core.n_events >= budget)
    return core, ms, fin, k


def _donate_ok() -> bool:
    """Buffer donation is a no-op (with a warning) on CPU; only ask for it
    where the runtime honours it."""
    try:
        return jax.default_backend() in ("gpu", "tpu")
    except RuntimeError:
        return False


@functools.lru_cache(maxsize=64)
def _segment_step(model: TaskModel, seg_len: int):
    """Jitted batched segment: (scn, state) -> (state', fin, k_max, k_sum).

    ``fin`` is the per-lane finished mask, ``k_max`` the number of batched
    loop iterations the segment actually spun (the convoy cost), ``k_sum``
    the useful events executed -- the driver's wasted-lane telemetry.
    """
    cid = jnp.asarray(model.topology.cluster_id)
    chops = jnp.asarray(cluster_hop_table(model.topology))
    arrays = model.static_arrays()

    def one(scn, state):
        core, ms = state
        return _segment_impl(model, cid, chops, arrays, scn, core, ms,
                             seg_len)

    def step(scn, state):
        core, ms, fin, k = jax.vmap(one)(scn, state)
        return (core, ms), fin, jnp.max(k), jnp.sum(k)

    donate = (1,) if _donate_ok() else ()
    return jax.jit(step, donate_argnums=donate)


@functools.lru_cache(maxsize=64)
def _init_fn(model: TaskModel):
    arrays = model.static_arrays()

    def one(scn):
        return model.init(arrays, scn, init_core(model, scn))

    return jax.jit(jax.vmap(one))


@functools.lru_cache(maxsize=64)
def _results_fn(model: TaskModel):
    return jax.jit(jax.vmap(lambda core, ms: model.results(core, ms)))


def _compact_impl(state, scn: Scenario, idx, n_real):
    """Gather lanes ``idx`` of (state, scn) into a dense batch; positions
    >= ``n_real`` are padding (copies of lane idx[k]) force-marked done so
    they never execute another event."""
    def take(x):
        return jnp.take(x, idx, axis=0)

    core, ms = jax.tree.map(take, state)
    scn = jax.tree.map(take, scn)
    pad = jnp.arange(idx.shape[0], dtype=jnp.int32) >= n_real
    core = core._replace(done=core.done | pad)
    return (core, ms), scn


@functools.lru_cache(maxsize=1)
def _compact_fn():
    donate = (0, 1) if _donate_ok() else ()
    return jax.jit(_compact_impl, donate_argnums=donate)


@dataclasses.dataclass
class SegmentStats:
    """Telemetry of one segmented run (the wasted-lane accounting the
    backend-matrix bench reports)."""
    n_segments: int = 0
    n_compactions: int = 0
    lane_cycles: int = 0      # sum over segments of batch_width * iterations
    events_executed: int = 0  # useful events actually run
    max_width: int = 0
    final_width: int = 0

    @property
    def wasted_frac(self) -> float:
        """Fraction of lane-iterations spent on finished/padded lanes."""
        if self.lane_cycles <= 0:
            return 0.0
        return 1.0 - self.events_executed / self.lane_cycles

    def merge(self, other: "SegmentStats") -> "SegmentStats":
        return SegmentStats(
            n_segments=self.n_segments + other.n_segments,
            n_compactions=self.n_compactions + other.n_compactions,
            lane_cycles=self.lane_cycles + other.lane_cycles,
            events_executed=self.events_executed + other.events_executed,
            max_width=max(self.max_width, other.max_width),
            final_width=max(self.final_width, other.final_width))


_sanitize_impl = None


def _sanitize(site: str, **ctx):
    """Lazy bridge to the opt-in determinism sanitizer
    (``repro.check.sanitizer.probe``), mirroring the ``_fault_point``
    bridge in ``core/backend.py``: core never imports the checker suite at
    module level, and a disabled probe costs one env read per segment."""
    global _sanitize_impl
    if _sanitize_impl is None:
        from repro.check.sanitizer import probe
        _sanitize_impl = probe
    return _sanitize_impl(site, **ctx)


class SegmentedRun:
    """Host-side driver of one segmented batched simulation.

    ``step()`` dispatches one segment and harvests the lanes it finished;
    when the count of survivors drops to half a power of two below the
    current batch width, the batch is compacted (gather into a dense pow2
    prefix, padding lanes marked done). Drive to completion with
    :func:`simulate_segmented`, or interleave several runs (one per device)
    via :func:`run_segmented_chunks` so their dispatches overlap.
    """

    def __init__(self, model: TaskModel, scn: Scenario,
                 seg_len: Optional[int] = None, device=None):
        n = int(scn.W.shape[0])
        if n == 0:
            raise ValueError("segmented run needs at least one scenario row")
        if seg_len is None:
            seg_len = default_segment_len(model.max_events)
        self.model = model
        self.seg_len = int(seg_len)
        self._step_fn = _segment_step(model, self.seg_len)
        self._results = _results_fn(model)
        if device is not None:
            scn = jax.device_put(scn, device)
        self.scn = scn
        self.state = _init_fn(model)(scn)
        self.idx = np.arange(n)            # original row per lane; -1 = pad
        self.n = n
        self._parts: list = []
        self._part_idx: list = []
        self.stats = SegmentStats(max_width=n, final_width=n)
        self.done = False

    def step(self):
        """Dispatch one segment; harvest finished lanes; maybe compact.

        A segment boundary is the engine's host-side tick — the one moment
        a device-resident run surfaces on the host — so it is where the
        engine's span (``engine.segment``) and metrics land."""
        if self.done:
            return
        with obs.span("engine.segment", width=len(self.idx),
                      seg_len=self.seg_len) as sp:
            self._step(sp)
        m = obs.REGISTRY
        m.counter("engine.segments").inc()
        if self.done:
            m.counter("engine.lane_cycles").inc(self.stats.lane_cycles)
            m.counter("engine.events_executed").inc(
                self.stats.events_executed)
            m.gauge("engine.wasted_frac").set(
                round(self.stats.wasted_frac, 4))

    def _step(self, sp):
        self.state, fin_d, k_max, k_sum = self._step_fn(self.scn, self.state)
        fin = np.asarray(fin_d)
        width = fin.shape[0]
        self.stats.n_segments += 1
        self.stats.lane_cycles += width * int(k_max)
        self.stats.events_executed += int(k_sum)
        # Sanitizer tick: idx still maps every lane to its original row
        # (harvest below rewrites it), state is post-segment — exactly the
        # boundary the monotonicity/conservation invariants quantify over.
        _sanitize("engine.segment", run=self, fin=fin)
        real = self.idx >= 0
        newly = fin & real
        if newly.any():
            res = self._results(*self.state)
            self._parts.append(
                jax.tree.map(lambda x: np.asarray(x)[newly], res))
            self._part_idx.append(self.idx[newly])
            self.idx = np.where(newly, -1, self.idx)
            real = self.idx >= 0
        sp.set(n_finished=int(newly.sum()))
        k = int(real.sum())
        if k == 0:
            self.done = True
            return
        new_width = _pow2ceil(k)
        if new_width <= width // 2:
            keep = np.flatnonzero(real)
            gidx = np.concatenate(
                [keep, np.zeros(new_width - k, np.int64)]).astype(np.int32)
            # host operands: they go straight to the run's device
            self.state, self.scn = _compact_fn()(
                self.state, self.scn, gidx, np.int32(k))
            self.idx = np.concatenate(
                [self.idx[keep], np.full(new_width - k, -1)])
            self.stats.n_compactions += 1
            self.stats.final_width = new_width
            sp.set(compacted_to=new_width)
            obs.REGISTRY.counter("engine.compactions").inc()

    def result(self):
        """Model result NamedTuple (numpy leaves, original row order)."""
        if not self.done:
            raise RuntimeError("segmented run not finished; call step()")
        order = np.argsort(np.concatenate(self._part_idx), kind="stable")
        return jax.tree.map(
            lambda *xs: np.concatenate(xs, axis=0)[order], *self._parts)


def simulate_segmented(model: TaskModel, scn: Scenario,
                       seg_len: Optional[int] = None, device=None):
    """Segmented batched simulation -> (results, :class:`SegmentStats`).

    Bit-identical to :func:`simulate_batch` on the same scenario batch (the
    segmentation/compaction parity suite in ``tests/test_segmented.py``
    enforces it); asymptotically ``sum(events)`` instead of
    ``n_rows x max(events)`` wall-clock under heavy-tailed event counts.
    """
    run = SegmentedRun(model, scn, seg_len=seg_len, device=device)
    while not run.done:
        run.step()
    return run.result(), run.stats


def run_segmented_chunks(model: TaskModel, scns, devices,
                         seg_len: Optional[int] = None):
    """Drive one :class:`SegmentedRun` per (scenario chunk, device) with
    round-robin stepping, so each device's next segment is dispatched while
    the others are still computing. Returns (results list, stats list)."""
    runs = [SegmentedRun(model, s, seg_len=seg_len, device=d)
            for s, d in zip(scns, devices)]
    while True:
        live = [r for r in runs if not r.done]
        if not live:
            break
        for r in live:
            r.step()
    return [r.result() for r in runs], [r.stats for r in runs]


@functools.lru_cache(maxsize=64)
def _compiled_simulator(model: TaskModel, batched: bool):
    fn = functools.partial(_simulate, model)
    if batched:
        fn = jax.vmap(fn)
    return jax.jit(fn)


def simulate(model: TaskModel, scn: Scenario):
    """Run one simulation (jitted; cached per model object)."""
    return _compiled_simulator(model, False)(scn)


def simulate_batch(model: TaskModel, scn: Scenario):
    """Run a batch: every leaf of ``scn`` has a leading batch axis."""
    return _compiled_simulator(model, True)(scn)
