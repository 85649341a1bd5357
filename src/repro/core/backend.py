"""Pluggable execution backends (DESIGN.md §7).

The engine's event loop is one piece of traced code; *where* it executes is
a deployment decision. This module makes that decision a value: an
:class:`ExecutionBackend` turns canonical grid rows into a
:class:`~repro.core.sweep.GridResult`, and a registry maps names to the four
substrates the repo ships —

* ``oracle``           — the serial numpy twins (``repro.core.oracle``):
                         slow, dependency-light ground truth;
* ``jax``              — the jit/vmap engine (``engine.simulate_batch``),
                         the default on CPU/GPU hosts;
* ``pallas``           — the real ``pallas_call`` through
                         ``kernels/ws_sim.py`` (Mosaic on TPU): per-scenario
                         state VMEM-resident for the whole event loop;
* ``pallas_interpret`` — the same kernel in interpret mode: CI-runnable on
                         any host, bit-identical by construction.

Every backend is **bit-identical** on the same rows (the parity tests in
``tests/test_backends.py`` enforce it), which is why the content-addressed
result store needs no backend key component: a cache fill from any backend
serves every other.

Where each dispatch decision lives: the backend is the query's explicit
one or ``default_backend_name()`` (the ``REPRO_WS_BACKEND`` environment
variable, then ``pallas`` iff a TPU is attached, else ``jax``), and it runs
every batch it is given; rows map to devices only in
:meth:`ExecutionBackend._device_chunks`; a failed dispatch is demoted only
along ``repro.service.resilience.fallback_chain``.
"""
from __future__ import annotations

import dataclasses
import os
from pathlib import Path
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
import numpy as np

from repro import obs
from repro.core import engine as eng
from repro.core import oracle as orc
from repro.core import sweep as sw
from repro.core import adaptive as ad
from repro.core import dag as dg
from repro.core import divisible as dv

#: Environment override consumed by :func:`default_backend_name` and the
#: Pallas wrapper's interpret default (:func:`pallas_interpret_default`).
BACKEND_ENV = "REPRO_WS_BACKEND"

#: Segment length override for the jax backend's segmented driver:
#: a positive int forces that segment length, "0" disables segmentation.
SEG_LEN_ENV = "REPRO_WS_SEG_LEN"

#: JAX's own variable for its persistent compilation cache, read by JAX at
#: start-up; where it is set, :func:`enable_compile_cache` sets no other
#: directory.
JAX_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

#: JAX's compile-path events (``jax.monitoring`` durations) and the
#: ``compile.*`` counter and ``compile.seconds`` phase each one feeds.
#: A backend compile includes a load from the persistent cache.
COMPILE_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": ("compile.traces", "trace"),
    "/jax/core/compile/jaxpr_to_mlir_module_duration":
        ("compile.lowerings", "lower"),
    "/jax/core/compile/backend_compile_duration":
        ("compile.backend_compiles", "compile"),
}
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def _on_compile_duration(event: str, duration: float, **_):
    counted = COMPILE_EVENTS.get(event)
    if counted is not None:
        name, phase = counted
        obs.REGISTRY.counter(name).inc()
        obs.REGISTRY.counter("compile.seconds", {"phase": phase}).inc(duration)


def _on_event(event: str, **_):
    if event == CACHE_HIT_EVENT:
        obs.REGISTRY.counter("compile.cache_hits").inc()


# Always on, like every registry series: an operator's stats() shows a
# dispatch that re-traces and re-lowers its kernel without a profiler.
jax.monitoring.register_event_duration_secs_listener(_on_compile_duration)
jax.monitoring.register_event_listener(_on_event)

_fault_point_impl = None


def _fault_point(site: str, **ctx):
    """Lazy bridge to ``repro.service.resilience.fault_point`` — imported on
    first use so ``repro.core`` keeps no module-level dependency on the
    service layer (the service imports core, not vice versa)."""
    global _fault_point_impl
    if _fault_point_impl is None:
        from repro.service.resilience import fault_point
        _fault_point_impl = fault_point
    return _fault_point_impl(site, **ctx)


_sanitize_impl = None


def _sanitize(site: str, **ctx):
    """Lazy bridge to the opt-in determinism sanitizer
    (``repro.check.sanitizer.probe``), same shape as :func:`_fault_point`:
    a disabled probe costs one env read per dispatch."""
    global _sanitize_impl
    if _sanitize_impl is None:
        from repro.check.sanitizer import probe
        _sanitize_impl = probe
    return _sanitize_impl(site, **ctx)


@dataclasses.dataclass(frozen=True)
class BackendCapabilities:
    """What a backend can run, reported without executing anything."""
    name: str
    available: bool           # can run on this host right now
    kind: str                 # "reference" | "xla" | "pallas"
    devices: Tuple[str, ...]  # jax device platforms it would execute on
    max_p: int                # largest processor count supported
    max_events_pow2: bool     # dispatcher should round static caps to pow2
    note: str = ""
    n_devices: int = 1        # local devices run_rows shards rows across
    segment_len: Optional[int] = None  # preferred event-segment length


class ExecutionBackend:
    """One execution substrate: rows in, GridResult out.

    Subclasses implement :meth:`_run_batch` (model + batched Scenario ->
    the model's result NamedTuple with a leading batch axis) and
    :meth:`capabilities`; :meth:`run_rows` is the shared entry point used by
    ``sweep.run_rows`` and the service broker. ``run_rows`` shards row
    chunks across every local device by default (``devices=`` narrows the
    set); chunk dispatches are issued back-to-back before any result is
    pulled to the host, so devices compute concurrently.
    """

    name = "?"
    #: a device chunk smaller than this is not worth a separate dispatch
    min_rows_per_device = 8

    def __init__(self):
        self.n_run_rows = 0     # dispatch counter (test/bench telemetry)
        self.last_stats = None  # SegmentStats of the last segmented run

    def capabilities(self) -> BackendCapabilities:
        raise NotImplementedError

    def local_devices(self) -> tuple:
        """Devices this backend shards row chunks across (may be empty)."""
        try:
            return tuple(jax.local_devices())
        except RuntimeError:
            return ()

    def _run_batch(self, model: eng.TaskModel, scn: eng.Scenario,
                   device=None):
        raise NotImplementedError

    def _check(self, model: eng.TaskModel):
        caps = self.capabilities()
        if not caps.available:
            raise RuntimeError(
                f"backend {self.name!r} is not available on this host"
                + (f" ({caps.note})" if caps.note else ""))
        if model.p > caps.max_p:
            raise ValueError(
                f"backend {self.name!r} supports p <= {caps.max_p}, "
                f"got p={model.p}")

    def _device_chunks(self, n: int, devices: Optional[Sequence]):
        """Contiguous balanced (lo, hi, device) row chunks, one per device
        actually worth dispatching to."""
        devs = tuple(devices) if devices is not None else self.local_devices()
        if not devs:
            return [(0, n, None)]
        nd = max(1, min(len(devs), n // max(self.min_rows_per_device, 1)))
        bounds = np.linspace(0, n, nd + 1).astype(int)
        return [(int(lo), int(hi), devs[k])
                for k, (lo, hi) in enumerate(zip(bounds[:-1], bounds[1:]))
                if hi > lo] or [(0, n, None)]

    def run_rows(self, model, rows: "sw.GridRows", remote_prob: float = 0.25,
                 ev_budget=None, devices: Optional[Sequence] = None,
                 ) -> "sw.GridResult":
        """Run one batched simulation over canonical rows.

        ``ev_budget`` is an optional per-row (or scalar) event budget; rows
        behave exactly as if the model's static ``max_events`` were their
        budget (see ``engine.Scenario.max_events``). ``devices`` narrows the
        device set row chunks are sharded across (default: every local
        device the backend can use).
        """
        model = sw.as_model(model)
        self._check(model)
        # Chaos hook (repro.service.resilience): a process-global FaultPlan
        # may raise/hang here to simulate backend failure or device loss;
        # the broker's resilient dispatch recovers. No-op without a plan.
        _fault_point("backend.run_rows", backend=self.name,
                     n_rows=len(rows), row_seeds=np.asarray(rows.seed))
        self.n_run_rows += 1
        # Reset before (not after) running: last_stats always describes THIS
        # dispatch, so a monolithic run cannot leak the previous segmented
        # run's wasted-lane telemetry.
        self.last_stats = None
        obs.REGISTRY.counter("backend.run_rows",
                             {"backend": self.name}).inc()
        with obs.span("backend.run_rows", backend=self.name,
                      n_rows=len(rows)) as sp:
            out = self._run_rows(model, rows, remote_prob, ev_budget, devices)
            if self.last_stats is not None:
                sp.set(n_segments=self.last_stats.n_segments,
                       wasted_frac=round(self.last_stats.wasted_frac, 4))
            # Sanitizer: steal-accounting check + seeded oracle replay of a
            # sampled dispatch (repro.check.sanitizer). No-op when disabled.
            _sanitize("backend.result", backend=self, model=model,
                      rows=rows, remote_prob=remote_prob,
                      ev_budget=ev_budget, grid=out)
            return out

    def _chunk_scenarios(self, rows, remote_prob, ev_budget, chunks):
        """One Scenario per (lo, hi, device) chunk, built on the host and
        placed straight onto its device; counts the rows each device
        runs (``backend.device_rows``)."""
        budgets = None if ev_budget is None else np.broadcast_to(
            np.asarray(ev_budget, np.int64), (len(rows),))
        scns = []
        for lo, hi, dev in chunks:
            scns.append(sw.scenario_from_rows(
                rows.slice(lo, hi), remote_prob=remote_prob,
                ev_budget=None if budgets is None else budgets[lo:hi],
                device=dev))
            if dev is not None:
                obs.REGISTRY.counter("backend.device_rows", {
                    "backend": self.name, "device": str(dev.id)}).inc(hi - lo)
        return scns

    def _run_rows(self, model, rows, remote_prob, ev_budget, devices):
        chunks = self._device_chunks(len(rows), devices)
        scns = self._chunk_scenarios(rows, remote_prob, ev_budget, chunks)
        # dispatch everything before any sync
        outs = [self._run_batch(model, scn, device=dev)
                for scn, (_, _, dev) in zip(scns, chunks)]
        return self._fetch(model, rows, chunks, outs)

    @staticmethod
    def _fetch(model, rows, chunks, outs) -> "sw.GridResult":
        """Each chunk's result to the host, in order: one ``backend.fetch``
        span a chunk, the wait for its device and the copy."""
        grids = []
        for (lo, hi, dev), res in zip(chunks, outs):
            with obs.span("backend.fetch",
                          device=None if dev is None else dev.id):
                grids.append(sw.grid_from_result(model.p, rows.slice(lo, hi),
                                                 res))
        return sw.concat_grids(grids)


def _device_platforms() -> Tuple[str, ...]:
    try:
        return tuple(sorted({d.platform for d in jax.devices()}))
    except RuntimeError:  # no backend at all (unusual; keep capabilities total)
        return ()


def _on_tpu() -> bool:
    return "tpu" in _device_platforms()


class OracleBackend(ExecutionBackend):
    """Serial numpy reference: loops the oracle twins row by row.

    Deliberately slow; exists so any result of any other backend can be
    reproduced with no JAX in the loop. Does not model capacity ``halt``
    (DAG deque / adaptive pool overflow) or trace logging — configs using
    those belong on the jitted backends.
    """

    name = "oracle"

    def capabilities(self) -> BackendCapabilities:
        return BackendCapabilities(
            name=self.name, available=True, kind="reference",
            devices=("cpu",), max_p=256, max_events_pow2=False,
            note="serial python loop; no capacity-halt or trace modelling")

    def local_devices(self) -> tuple:
        return ()  # pure numpy: no device sharding

    def _run_rows(self, model, rows, remote_prob, ev_budget,
                  devices) -> "sw.GridResult":
        if model.log_trace:
            raise ValueError("oracle backend does not record traces; "
                             "use the 'jax' backend for log_trace models")
        n = len(rows)
        budgets = np.broadcast_to(
            np.asarray(eng.INF32 if ev_budget is None else ev_budget,
                       np.int64), (n,))
        outs = [self._run_row(model, rows, k,
                              min(int(model.max_events), int(budgets[k])),
                              float(remote_prob))
                for k in range(n)]
        res = jax.tree.map(lambda *leaves: np.stack(leaves), *outs)
        return sw.grid_from_result(model.p, rows, res)

    def _run_row(self, model, rows, k: int, max_events: int, rp: float):
        kw = dict(seed=int(rows.seed[k]),
                  lam_local=int(rows.lam_local[k]),
                  lam_remote=int(rows.lam_remote[k]),
                  mwt=model.mwt, remote_prob=rp, max_events=max_events)
        i32 = np.int32
        trace = np.zeros((1, 4), np.int32)     # log_trace=False engine shape
        if isinstance(model, dv.DivisibleModel):
            o = orc.simulate_oracle(
                model.topology, int(rows.W[k]),
                theta_static=int(rows.theta_static[k]),
                theta_comm=int(rows.theta_comm[k]), **kw)
            return dv.SimResult(
                makespan=i32(o.makespan), n_events=i32(o.n_events),
                n_requests=i32(o.n_requests), n_success=i32(o.n_success),
                n_fail=i32(o.n_fail), total_idle=i32(o.total_idle),
                startup_end=i32(o.startup_end),
                executed=np.asarray(o.executed, np.int32),
                overflow=np.bool_(o.overflow), trace=trace,
                n_trace=i32(0))
        if isinstance(model, dg.DagModel):
            o = orc.simulate_dag_oracle(
                model.topology, model.cfg.dag,
                theta_static=int(rows.theta_static[k]),
                owner_lifo=model.cfg.owner_lifo, **kw)
            return dg.DagSimResult(
                makespan=i32(o["makespan"]), n_events=i32(o["n_events"]),
                n_requests=i32(o["n_requests"]),
                n_success=i32(o["n_success"]), n_fail=i32(o["n_fail"]),
                total_idle=i32(o["total_idle"]),
                startup_end=i32(o["startup_end"]),
                executed=np.asarray(o["executed"], np.int32),
                tasks_run=np.asarray(o["tasks_run"], np.int32),
                n_completed=i32(o["n_completed"]),
                overflow=np.bool_(o["overflow"]), trace=trace,
                n_trace=i32(0))
        if isinstance(model, ad.AdaptiveModel):
            o = orc.simulate_adaptive_oracle(
                model.topology, int(rows.W[k]),
                theta_static=int(rows.theta_static[k]),
                theta_comm=int(rows.theta_comm[k]),
                merge_alpha=model.cfg.merge_alpha,
                merge_beta_num=model.cfg.merge_beta_num,
                merge_beta_den=model.cfg.merge_beta_den, **kw)
            return ad.AdaptiveSimResult(
                makespan=i32(o["makespan"]), n_events=i32(o["n_events"]),
                n_requests=i32(o["n_requests"]),
                n_success=i32(o["n_success"]), n_fail=i32(o["n_fail"]),
                n_splits=i32(o["n_splits"]),
                total_idle=i32(o["total_idle"]),
                startup_end=i32(o["startup_end"]),
                executed=np.asarray(o["executed"], np.int32),
                total_merge_work=i32(o["total_merge_work"]),
                n_created=i32(o["n_created"]),
                n_completed=i32(o["n_completed"]),
                overflow=np.bool_(o["overflow"]), trace=trace,
                n_trace=i32(0))
        raise TypeError(f"oracle backend has no twin for {type(model)!r}")


class JaxBackend(ExecutionBackend):
    """The jit/vmap engine — the current (and CPU/GPU default) path.

    Batches at or above :attr:`seg_min_rows` run through the segmented
    driver (``engine.simulate_segmented``): the event loop is cut into
    fixed-size segments with host-side active-lane compaction in between,
    so a batch costs ~``sum(events)`` instead of ``n_rows x max(events)``
    (bit-identical results — see DESIGN.md §8). ``REPRO_WS_SEG_LEN``
    overrides the segment length (0 disables segmentation entirely);
    :attr:`last_stats` carries the wasted-lane telemetry of the most recent
    segmented dispatch.
    """

    name = "jax"
    #: below this batch width, segmentation overhead beats its convoy savings
    seg_min_rows = 32

    def capabilities(self) -> BackendCapabilities:
        return BackendCapabilities(
            name=self.name, available=True, kind="xla",
            devices=_device_platforms(), max_p=1 << 14,
            max_events_pow2=False,
            n_devices=max(len(self.local_devices()), 1),
            segment_len=eng.default_segment_len(1 << 20))

    def _segment_len(self, model, ev_budget, n: int) -> Optional[int]:
        env = os.environ.get(SEG_LEN_ENV, "").strip()
        if env:
            v = int(env)
            return v if v > 0 else None
        if n < self.seg_min_rows:
            return None
        return eng.default_segment_len(model.max_events, ev_budget)

    def _run_batch(self, model, scn, device=None):
        return eng.simulate_batch(model, scn)

    def _run_rows(self, model, rows, remote_prob, ev_budget, devices):
        n = len(rows)
        seg_len = self._segment_len(model, ev_budget, n)
        if seg_len is None or n == 0:
            return super()._run_rows(model, rows, remote_prob, ev_budget,
                                     devices)
        chunks = self._device_chunks(n, devices)
        scns = self._chunk_scenarios(rows, remote_prob, ev_budget, chunks)
        results, stats = eng.run_segmented_chunks(
            model, scns, [d for _, _, d in chunks], seg_len=seg_len)
        merged = stats[0]
        for s in stats[1:]:
            merged = merged.merge(s)
        self.last_stats = merged
        return self._fetch(model, rows, chunks, results)


class PallasBackend(ExecutionBackend):
    """Real ``pallas_call`` (Mosaic on TPU): VMEM-resident event loops."""

    name = "pallas"
    _interpret = False
    #: fixed grid-chunk width: bounds the set of program shapes Mosaic
    #: compiles and gives the multi-device path per-chunk dispatches
    grid_chunk = 128

    def capabilities(self) -> BackendCapabilities:
        return BackendCapabilities(
            name=self.name, available=_on_tpu(), kind="pallas",
            devices=_device_platforms(), max_p=1024,
            # Pow2 static caps bound the set of programs Mosaic compiles.
            max_events_pow2=True,
            note="" if _on_tpu() else "needs a TPU; use 'pallas_interpret'",
            n_devices=max(len(self.local_devices()), 1))

    def local_devices(self) -> tuple:
        try:
            return tuple(d for d in jax.local_devices()
                         if d.platform == "tpu")
        except RuntimeError:
            return ()

    def _run_batch(self, model, scn, device=None):
        from repro.kernels.ws_sim import ws_sim_pallas
        return ws_sim_pallas(model, scn, interpret=self._interpret,
                             grid_chunk=self.grid_chunk)

    def _fetch(self, model, rows, chunks, outs) -> "sw.GridResult":
        """The base fetch, then each device chunk's block fill counted from
        its rows' events, now on the host (``ws_sim.block_*_events``)."""
        from repro.kernels.ws_sim import count_blocks
        grid = super()._fetch(model, rows, chunks, outs)
        for lo, hi, _ in chunks:
            count_blocks(model, grid.extras["n_events"][lo:hi],
                         self.grid_chunk)
        return grid


class PallasInterpretBackend(PallasBackend):
    """The Pallas kernel in interpret mode: runs anywhere, CI-checkable."""

    name = "pallas_interpret"
    _interpret = True
    grid_chunk = None  # interpret mode gains nothing from chunking

    def capabilities(self) -> BackendCapabilities:
        return BackendCapabilities(
            name=self.name, available=True, kind="pallas",
            devices=_device_platforms(), max_p=1024, max_events_pow2=True,
            note="interpret mode: validates kernel semantics, not kernel perf")

    def local_devices(self) -> tuple:
        return ()  # python-interpreted: device sharding is meaningless


_REGISTRY: Dict[str, ExecutionBackend] = {}


def register_backend(backend: ExecutionBackend) -> ExecutionBackend:
    """Add (or replace) a backend under ``backend.name``."""
    _REGISTRY[backend.name] = backend
    return backend


for _b in (OracleBackend(), JaxBackend(), PallasBackend(),
           PallasInterpretBackend()):
    register_backend(_b)


def backend_names() -> Tuple[str, ...]:
    return tuple(_REGISTRY)


def available_backends() -> Tuple[ExecutionBackend, ...]:
    return tuple(b for b in _REGISTRY.values() if b.capabilities().available)


def default_backend_name() -> str:
    """Auto-detected backend: ``REPRO_WS_BACKEND`` env override, else
    ``pallas`` iff a TPU is attached, else ``jax``."""
    env = os.environ.get(BACKEND_ENV, "").strip()
    if env:
        if env not in _REGISTRY:
            raise ValueError(
                f"{BACKEND_ENV}={env!r} is not a registered backend; "
                f"choose one of {backend_names()}")
        return env
    return "pallas" if _on_tpu() else "jax"


def get_backend(
    backend: Union[None, str, ExecutionBackend] = None,
) -> ExecutionBackend:
    """Resolve a backend argument: None -> auto-detect, str -> registry
    lookup, ExecutionBackend -> itself."""
    if backend is None:
        return _REGISTRY[default_backend_name()]
    if isinstance(backend, ExecutionBackend):
        return backend
    try:
        return _REGISTRY[backend]
    except KeyError:
        raise ValueError(f"unknown backend {backend!r}; registered: "
                         f"{backend_names()}") from None


def default_jit_cache_dir() -> Path:
    return Path(__file__).resolve().parents[3] / "artifacts" / "jit_cache"


def compile_cache_dir() -> Optional[Path]:
    """Directory of JAX's persistent compilation cache, if one is on."""
    d = jax.config.jax_compilation_cache_dir
    return Path(d) if d else None


def enable_compile_cache() -> Path:
    """Turn on JAX's persistent compilation cache so processes stop
    re-jitting identical programs across runs.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps its cache
    there and no other directory is set; otherwise the cache is the fixed
    ``artifacts/jit_cache/`` of the checkout (the path is part of each
    entry's key, so it must not move). The persistence thresholds are
    dropped to zero so even the small event-loop programs are kept.
    Returns the cache directory. Safe to call repeatedly."""
    env = os.environ.get(JAX_CACHE_ENV, "").strip()
    p = Path(env) if env else default_jit_cache_dir()
    p.mkdir(parents=True, exist_ok=True)
    if not env:
        jax.config.update("jax_compilation_cache_dir", str(p))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return p


def pallas_interpret_default() -> bool:
    """Default for ``ws_sim_pallas(interpret=)``: interpret everywhere
    except on TPU hosts, overridable via ``REPRO_WS_BACKEND``
    ('pallas' -> compiled, 'pallas_interpret' -> interpret)."""
    env = os.environ.get(BACKEND_ENV, "").strip()
    if env == "pallas":
        return False
    if env == "pallas_interpret":
        return True
    return not _on_tpu()
