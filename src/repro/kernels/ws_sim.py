"""Pallas kernel: batched Work-Stealing simulations, a block of scenarios
per grid step — the paper-representative hot spot (DESIGN.md §2, §4).

The unified event core keeps O(p) int32 state (event times, processor
states, PRNG lanes) plus the task model's pytree (deques, task pools).
Running a Monte-Carlo sweep as ordinary JAX re-reads that state from HBM on
every event; here the *entire* per-scenario state lives in VMEM/registers
for the whole event loop, so HBM is touched exactly twice: scenario
parameters in, results out. The event loop body is the same traced code as
the library engine (``repro.core.engine._simulate_impl``, or its block form
``simulate_block``), so the kernel is bit-identical to the oracle-validated
engine by construction — for EVERY task model (divisible, DAG, adaptive),
not just the divisible hot path.

Grid: ``(G / B,)`` steps of B scenarios (:func:`block_rows`). A divisible
scenario carries only scalars and int32[p] vectors, so B = 8 rows run one
per sublane: every state vector fills the vregs it would occupy alone, the
three event handlers run for every row as selects, and the loop runs while
any row is live, each row's carry frozen by its own live mask
(``engine.simulate_block``). A model that reads shared arrays or carries a
table a row (DAG, adaptive) runs B = 1, the one-row loop. The scenario
parameters are whole ``(G,)`` columns in SMEM read at the step's rows; each
result leaf has a ``(B,) + tile`` block whose last two dims are the array's
own (the TPU's block rule), reshaped back in the wrapper. Distances come
from the topology's k×k cluster hop table, not its p×p ``hops``. The
wrapper is fully generic: it derives the output pytree via
``jax.eval_shape`` on the model's result type and threads the model's static
arrays (DAG durations/edges) as kernel inputs rather than closure constants.
It builds that ``pallas_call`` once per model and shape (:func:`kernel_call`),
so a dispatch re-uses JAX's compiled kernel instead of re-tracing it.
The body is traced under ``engine.select_forms()``, so every indexed access
of the event core is a one-hot mask, select and reduction over int32 lanes:
Mosaic lowers those, and not gather/scatter or an int32 ``argmin``. It runs
in interpret mode on the CPU and compiles via Mosaic on a TPU.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import obs
from repro.core import engine as eng
from repro.core.backend import pallas_interpret_default
from repro.core.dag import DagModel
from repro.core.sweep import as_model


def _fresh(x):
    """``x`` through a select. Mosaic fails to lay out a vector loaded
    from a ref that is then carried through the event loop's branches; a
    computed value carries fine."""
    return jnp.where(lax.broadcasted_iota(jnp.int32, x.shape, 0) >= 0, x, 0)


#: Scenarios one grid step runs where each carries only scalars and
#: int32[p] vectors and reads no shared array: one per sublane of a 32-bit
#: vreg (8 × 128 lanes), so every state vector fills, eight rows deep, the
#: vregs it occupies alone. A shared array read per row would become a
#: (B, n) pass, and a table per row a third dimension, so those models run
#: one scenario a step.
BLOCK = 8


@functools.lru_cache(maxsize=64)
def block_rows(model) -> int:
    """Scenarios a grid step of ``model``'s kernel runs: :data:`BLOCK`
    where the state one scenario carries (its ``CoreState`` and the
    model's own) is scalars and int32[p] vectors and the model has no
    static arrays; 1 otherwise (DAG, adaptive, a logged trace)."""
    if model.static_arrays() or model.log_trace:
        return 1
    core, ms = jax.eval_shape(
        lambda s: model.init((), s, eng.init_core(model, s)),
        eng.make_scenario(0, 0))
    leaves = jax.tree.leaves((core._replace(trace=None), ms))
    ok = all(l.shape in ((), (model.p,)) for l in leaves)
    return BLOCK if ok else 1


def _write_out(res, out_refs, bool_mask):
    for leaf, ref, is_bool in zip(jax.tree.leaves(res), out_refs, bool_mask):
        val = leaf.astype(jnp.int32) if is_bool else leaf
        ref[...] = val.reshape(ref.shape)


def _kernel(*refs, model, n_const, n_scn, scn_def, bool_mask):
    consts = [_fresh(refs[k][...]) for k in range(n_const)]
    row = pl.program_id(0)
    scn = jax.tree.unflatten(
        scn_def, [refs[n_const + k][row] for k in range(n_scn)])
    with eng.select_forms():
        res = eng._simulate_impl(model, consts[0], consts[1],
                                 tuple(consts[2:]), scn)
    _write_out(res, refs[n_const + n_scn:], bool_mask)


def _block_kernel(*refs, model, n_const, n_scn, scn_def, bool_mask, B):
    """``B`` scenarios a grid step, one per sublane: rows ``B * step + b``
    of the SMEM columns, assembled into (B,) vectors."""
    consts = [_fresh(refs[k][...]) for k in range(n_const)]
    base = pl.program_id(0) * B
    rows = lax.broadcasted_iota(jnp.int32, (B, 1), 0)
    cols = []
    for ref in refs[n_const:n_const + n_scn]:
        col = jnp.zeros((B, 1), ref.dtype)
        for b in range(B):
            col = jnp.where(rows == b, ref[base + b], col)
        cols.append(col.reshape(B))
    with eng.select_forms(every_branch=True):
        res = eng.simulate_block(model, consts[0], consts[1],
                                 tuple(consts[2:]),
                                 jax.tree.unflatten(scn_def, cols))
    _write_out(res, refs[n_const + n_scn:], bool_mask)


def _tile_shape(shape) -> tuple:
    """A result leaf's shape with unit dims prepended up to rank 2, so that
    the last two dims of every output block equal the array's own (the
    TPU's block rule); the wrapper reshapes back."""
    shape = tuple(shape)
    return (1,) * max(2 - len(shape), 0) + shape


def _task_model(model) -> str:
    """``divisible``, ``dag`` or ``adaptive``: the kernel is
    ``ws_sim_<task model>``."""
    return type(model).__name__.removesuffix("Model").lower()


def _device_id(x) -> Optional[int]:
    """The id of the device a concrete array lives on; None for a tracer
    or a host array."""
    if isinstance(x, jax.Array) and not isinstance(x, jax.core.Tracer):
        return next(iter(x.devices())).id
    return None


def _pad_chunk(scn: eng.Scenario, c: int) -> eng.Scenario:
    """A chunk of ``n < c`` rows padded to ``c`` with copies of its first
    row whose event budget is zero: they exit the loop before a single
    event. The budget mask is a host array, where ``.at[n:]`` would stage
    its index on the default device."""
    n = int(scn.W.shape[0])
    scn = jax.tree.map(lambda x: jnp.concatenate(
        [x, jnp.broadcast_to(x[:1], (c - n,) + x.shape[1:])]), scn)
    return scn._replace(
        max_events=jnp.where(np.arange(c) < n, scn.max_events, 0))


@functools.lru_cache(maxsize=64)
def _host_consts(model) -> tuple:
    """The kernel's constant inputs (the cluster of each processor and the
    flattened cluster hop table, then the model's static arrays) as host
    arrays, fetched once per model. Each dispatch copies them from the host
    to its scenarios' device, so a row chunk on one chip never reads
    another chip's copy."""
    return ((np.asarray(model.topology.cluster_id),
             eng.cluster_hop_table(model.topology))
            + tuple(jax.device_get(model.static_arrays())))


def _state_bytes(model, consts, scn1) -> int:
    """Bytes of the state that one scenario carries through the event
    loop: the event core's ``CoreState`` and the task model's own."""
    state = jax.eval_shape(
        lambda c, s: model.init(c[2:], s, eng.init_core(model, s)),
        consts, scn1)
    return sum(l.size * l.dtype.itemsize for l in jax.tree.leaves(state))


class KernelCall(NamedTuple):
    """A built ``pallas_call`` and how to read its outputs back into the
    model's result pytree."""
    call: Callable          # (*consts, *scenario leaves) -> int32/... tiles
    res_leaves: tuple       # per-row ShapeDtypeStruct of each result leaf
    res_def: Any            # the result's treedef
    bool_mask: tuple        # leaves carried as int32 and cast back to bool


@functools.lru_cache(maxsize=64)
def kernel_call(model, G: int, interpret, scn_def, scn_dtypes: tuple
                ) -> KernelCall:
    """The ``pallas_call`` over ``G`` scenarios of ``model``, built once per
    key. The call is JAX's own jitted wrapper, so one object reused across
    dispatches traces and lowers once per device and input shape; a fresh
    kernel closure per dispatch would miss JAX's cache every time. The
    key holds avals, not values, so tracers build it too. A build sets the
    gauges ``ws_sim.state_bytes{task_model}``, the bytes of one scenario's
    carried state, and ``ws_sim.block_rows{task_model}``, the scenarios a
    grid step runs (:func:`block_rows`, which divides ``G``), so no
    dispatch pays for them."""
    B = block_rows(model)
    if G % B:
        raise ValueError(f"G={G} is not a multiple of the block of {B} rows")
    consts = _host_consts(model)
    scn1 = jax.tree.unflatten(
        scn_def, [jax.ShapeDtypeStruct((), d) for d in scn_dtypes])
    res_struct = jax.eval_shape(
        lambda c, s: eng._simulate_impl(model, c[0], c[1], c[2:], s),
        consts, scn1)
    res_leaves, res_def = jax.tree.flatten(res_struct)
    bool_mask = tuple(l.dtype == jnp.bool_ for l in res_leaves)
    labels = {"task_model": _task_model(model)}
    obs.REGISTRY.gauge("ws_sim.state_bytes", labels).set(
        _state_bytes(model, consts, scn1))
    obs.REGISTRY.gauge("ws_sim.block_rows", labels).set(B)

    def _const_spec(x):
        rank = x.ndim
        return pl.BlockSpec(x.shape, lambda i, rank=rank: (0,) * rank)

    def _out_spec(shape):
        rank = len(shape)
        return pl.BlockSpec((B,) + shape,
                            lambda i, rank=rank: (i,) + (0,) * rank)

    # Scenario scalars: the whole (G,) column in SMEM, read at the grid
    # step's rows (a (B,) VMEM block is below the TPU's 128-lane tiling).
    in_specs = ([_const_spec(c) for c in consts]
                + [pl.BlockSpec(memory_space=pltpu.SMEM)] * len(scn_dtypes))
    tiles = [_tile_shape(l.shape) for l in res_leaves]
    out_shape = [jax.ShapeDtypeStruct((G,) + tile,
                                      jnp.int32 if b else l.dtype)
                 for l, b, tile in zip(res_leaves, bool_mask, tiles)]
    out_specs = [_out_spec(tile) for tile in tiles]

    # The kernel's name rides in the custom call's ``kernel_metadata``, which
    # the device trace prints with the op. ``name=`` or a named scope would
    # rename the op itself, which the trace reduction finds as
    # ``%tpu_custom_call``; so would an outer ``jax.jit``, so the backend
    # calls this object eagerly.
    body = functools.partial(_block_kernel, B=B) if B > 1 else _kernel
    call = pl.pallas_call(
        functools.partial(body, model=model, n_const=len(consts),
                          n_scn=len(scn_dtypes), scn_def=scn_def,
                          bool_mask=bool_mask),
        grid=(G // B,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
        metadata={"kernel": f"ws_sim_{_task_model(model)}"},
    )
    return KernelCall(call, tuple(res_leaves), res_def, bool_mask)


def ws_sim_pallas(model, scn: eng.Scenario, interpret: Optional[bool] = None,
                  grid_chunk: Optional[int] = None):
    """Batched simulation; ``scn`` leaves have leading batch dim G.

    ``model`` is a TaskModel or any engine config (``EngineConfig`` /
    ``DagEngineConfig`` / ``AdaptiveEngineConfig``). Returns the model's
    result NamedTuple with a leading G axis on every leaf — bit-identical
    to ``engine.simulate_batch``.

    ``interpret=None`` defers to the backend registry's auto-detection
    (compiled via Mosaic on TPU hosts, interpret mode elsewhere;
    ``REPRO_WS_BACKEND=pallas|pallas_interpret`` overrides).

    ``grid_chunk`` splits the ``(G,)`` grid into fixed-size segments run as
    separate ``pallas_call`` dispatches: every dispatch then has the same
    grid shape, so Mosaic compiles one program per model regardless of
    batch size (and the chunks are independently shardable). The last
    chunk is padded up to the chunk size with copies of its first row whose
    event budget is zero — the padded lanes exit the loop before executing
    a single event, and their rows are dropped from the output.
    Bit-exactness is untouched: grid cells are independent. A grid that
    the model's block of rows (:func:`block_rows`) does not divide is
    padded the same way. Each chunk is
    one ``ws_sim.chunk`` span (a DAG's with its ``deque_cap``): its slice
    and pad, and the eager call of the cached ``pallas_call``
    (:func:`kernel_call`), which traces, lowers and compiles or loads only
    on its first dispatch of a shape to a device.
    Each dispatch counts ``ws_sim.kernel_cache{result=hit|miss}``.
    """
    if interpret is None:
        interpret = pallas_interpret_default()
    model = as_model(model)
    G = int(scn.W.shape[0])
    if grid_chunk is not None and G > 0:
        c = max(int(grid_chunk), 1)
        attrs = dict(task_model=_task_model(model),
                     device=_device_id(scn.W))
        if isinstance(model, DagModel):
            attrs["deque_cap"] = model.cfg.cap
        outs = []
        for lo in range(0, G, c):
            n = min(c, G - lo)
            with obs.span("ws_sim.chunk", n_rows=n, **attrs):
                ck = jax.tree.map(lambda x: x[lo:lo + n], scn)
                if n < c:
                    ck = _pad_chunk(ck, c)
                outs.append(ws_sim_pallas(model, ck, interpret=interpret))
        res = jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0), *outs)
        return jax.tree.map(lambda x: x[:G], res) if G % c else res

    B = block_rows(model)
    if G % B:
        res = ws_sim_pallas(model, _pad_chunk(scn, G + B - G % B),
                            interpret=interpret)
        return jax.tree.map(lambda x: x[:G], res)

    scn_leaves, scn_def = jax.tree.flatten(scn)
    misses = kernel_call.cache_info().misses
    kc = kernel_call(model, G, interpret, scn_def,
                     tuple(l.dtype for l in scn_leaves))
    hit = kernel_call.cache_info().misses == misses
    obs.REGISTRY.counter("ws_sim.kernel_cache",
                         {"result": "hit" if hit else "miss"}).inc()

    outs = kc.call(*_host_consts(model), *scn_leaves)
    outs = [o.reshape((G,) + l.shape) for o, l in zip(outs, kc.res_leaves)]
    outs = [o.astype(jnp.bool_) if b else o
            for o, b in zip(outs, kc.bool_mask)]
    return jax.tree.unflatten(kc.res_def, outs)


def count_blocks(model, n_events, grid_chunk: Optional[int] = None) -> None:
    """Count how one :func:`ws_sim_pallas` dispatch of rows with these
    event counts (host ints, in dispatch order) filled its blocks of
    :func:`block_rows`: ``ws_sim.block_row_events``, the rows' events, and
    ``ws_sim.block_slot_events``, B times each block's largest count (a
    block steps until its last row ends). Host arithmetic only."""
    B = block_rows(model)
    n = np.asarray(n_events, np.int64)
    c = max(int(grid_chunk or len(n)), 1)
    slots = 0
    for lo in range(0, len(n), c):
        ck = n[lo:lo + c]
        ck = np.pad(ck, (0, -len(ck) % B))
        slots += B * int(ck.reshape(-1, B).max(axis=1).sum())
    labels = {"task_model": _task_model(model)}
    obs.REGISTRY.counter("ws_sim.block_row_events", labels).inc(int(n.sum()))
    obs.REGISTRY.counter("ws_sim.block_slot_events", labels).inc(slots)


def grid_shape_hazards(grid_chunk: Optional[int],
                       G: Optional[int] = None) -> list:
    """Static shape hazards of a planned ``ws_sim_pallas`` dispatch.

    Returns human-readable hazard strings (empty list = clean); consumed by
    the jaxpr hazard analyzer (``repro.check.jaxpr_lint``, rule
    ``pallas.grid_chunk``). Every distinct padded grid shape compiles a
    distinct Mosaic program, so backends must chunk to a power of two: the
    broker already pads batches to pow2, and a pow2 ``grid_chunk`` divides
    every such batch into one repeated shape.
    """
    hazards = []
    if grid_chunk is not None:
        c = int(grid_chunk)
        if c <= 0:
            hazards.append(f"grid_chunk={c} must be a positive power of two")
        elif c & (c - 1):
            hazards.append(
                f"grid_chunk={c} is not a power of two: pow2-padded broker "
                f"batches will not divide evenly, so every distinct batch "
                f"size compiles a fresh Mosaic program shape")
    elif G is not None and G > 1 and (int(G) & (int(G) - 1)):
        hazards.append(
            f"unchunked grid G={int(G)} is not a power of two: each "
            f"distinct G compiles a fresh Mosaic program")
    return hazards
