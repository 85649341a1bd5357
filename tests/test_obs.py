"""Observability layer (DESIGN.md §9): tracer, metrics registry, service
instrumentation, ring-bounded dispatch log, last_stats freshness, and the
artifacts-are-byte-identical-under-tracing guarantee."""
import json
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

from repro import obs
from repro.core import backend as bk
from repro.core import topology as T
from repro.core.sweep import grid_rows, resolve_model, run_rows
from repro.service import SimulationService
from repro.service.store import ResultStore


# -- tracer: spans in the profiler's trace ---------------------------------

def _one(tr, name):
    found = [e for e in tr.spans() if e["name"] == name]
    assert len(found) == 1, found
    return found[0]


def _within(inner, outer):
    return (outer["start_ns"] <= inner["start_ns"]
            and inner["start_ns"] + inner["dur_ns"]
            <= outer["start_ns"] + outer["dur_ns"])


def test_span_nesting_and_summary(tmp_path):
    with obs.trace_to(tmp_path) as tr:
        with obs.span("obs_test.outer", a=1):
            with obs.span("obs_test.inner"):
                pass
            with obs.span("obs_test.inner"):
                pass
    durs = tr.durations_ms()
    assert len(durs["obs_test.outer"]) == 1
    assert len(durs["obs_test.inner"]) == 2
    assert all(d >= 0 for v in durs.values() for d in v)
    outer = _one(tr, "obs_test.outer")
    inners = [e for e in tr.spans() if e["name"] == "obs_test.inner"]
    assert all(_within(e, outer) for e in inners)
    summary = tr.summary()
    assert "obs_test.outer" in summary and "obs_test.inner" in summary


def test_late_attrs_land_on_end_event(tmp_path):
    """A span's attributes, those given at its start and those set late,
    read back from the xplane with their types."""
    with obs.trace_to(tmp_path) as tr:
        with obs.span("obs_test.s", early=1, tier="disk") as sp:
            sp.set(late="x", frac=0.25)
    assert _one(tr, "obs_test.s")["args"] == {
        "early": 1, "tier": "disk", "late": "x", "frac": 0.25}


def test_tracer_write_valid_chrome_trace(tmp_path):
    """One session writes the xplane and a Chrome-trace (Perfetto) file in
    which the spans are complete events, nested by time."""
    with obs.trace_to(tmp_path) as tr:
        with obs.span("obs_test.a"):
            with obs.span("obs_test.b"):
                pass
    assert list(tmp_path.rglob("*.xplane.pb"))
    events = tr.chrome_events()
    json.dumps(events)
    assert any(e["ph"] == "M" and e["name"] == "process_name"
               for e in events)
    a, b = (next(e for e in events if e.get("name") == n)
            for n in ("obs_test.a", "obs_test.b"))
    assert a["ph"] == b["ph"] == "X"
    assert (a["pid"], a["tid"]) == (b["pid"], b["tid"])
    assert a["ts"] <= b["ts"] and b["ts"] + b["dur"] <= a["ts"] + a["dur"]


def test_span_and_jax_op_share_one_clock(tmp_path):
    """A program span and the XLA operation run inside it land in one
    trace, the operation's interval inside the span's."""
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.cumsum(x * 3))
    x = jnp.arange(4096, dtype=jnp.int32)
    f(x).block_until_ready()
    with obs.trace_to(tmp_path) as tr:
        with obs.span("obs_test.run"):
            f(x).block_until_ready()
    run = _one(tr, "obs_test.run")
    ops = [e for e in tr.events() if "hlo_op" in e["args"]]
    assert ops and all(_within(e, run) for e in ops)


def test_disabled_tracing_is_noop():
    assert not obs.enabled()
    sp = obs.span("anything", x=1)
    assert sp is obs.span("other")          # the shared null span
    with sp as s:
        s.set(y=2)                          # all no-ops


def test_trace_to_restores_previous_tracer(tmp_path):
    """One session at a time: a second refuses with a clear error and
    leaves the first collecting; closing the first stops the spans."""
    assert not obs.enabled()
    with obs.trace_to(tmp_path / "a") as tr:
        assert obs.enabled()
        with pytest.raises(RuntimeError, match="already collecting"):
            with obs.trace_to(tmp_path / "b"):
                pass
        assert obs.enabled()                # the first still collects
        with obs.span("obs_test.after_refusal"):
            pass
    assert not obs.enabled()
    assert _one(tr, "obs_test.after_refusal")


def test_tracer_thread_tids(tmp_path):
    with obs.trace_to(tmp_path) as tr:
        def work():
            with obs.span("obs_test.worker"):
                pass
        with obs.span("obs_test.main"):
            th = threading.Thread(target=work)
            th.start()
            th.join(timeout=30)
        assert not th.is_alive()
    worker, main = _one(tr, "obs_test.worker"), _one(tr, "obs_test.main")
    assert worker["line"] != main["line"]   # one line per thread


def test_trace_env_var_activates(tmp_path):
    """REPRO_WS_TRACE=dir traces the whole process into dir."""
    out = tmp_path / "env_trace"
    env = dict(os.environ, REPRO_WS_TRACE=str(out))
    env["PYTHONPATH"] = os.pathsep.join(
        [str(os.path.join(os.path.dirname(__file__), "..", "src")),
         env.get("PYTHONPATH", "")])
    code = ("import repro.obs as obs\n"
            "assert obs.enabled()\n"
            "with obs.span('from_env', n=3):\n"
            "    pass\n")
    subprocess.run([sys.executable, "-c", code], check=True, env=env,
                   timeout=300)
    found = [e for e in obs.Trace(out).events() if e["name"] == "from_env"]
    assert len(found) == 1 and found[0]["args"] == {"n": 3}


def test_ws_sim_chunk_spans(tmp_path):
    """One ws_sim.chunk span per 128-row grid chunk."""
    from repro.core import divisible as dv
    from repro.core import engine as eng
    from repro.kernels.ws_sim import ws_sim_pallas
    cfg = dv.EngineConfig(topology=T.one_cluster(4, 2), max_events=1 << 10)
    scn = eng.batch_scenarios(40, np.arange(256, dtype=np.uint32) + 1,
                              lam=2)
    with obs.trace_to(tmp_path) as tr:
        ws_sim_pallas(cfg, scn, interpret=True, grid_chunk=128)
    chunks = [e for e in tr.spans() if e["name"] == "ws_sim.chunk"]
    assert len(chunks) == 2
    assert all(e["args"]["task_model"] == "divisible"
               and e["args"]["n_rows"] == 128 for e in chunks)


def test_compile_counters_count_lowerings(tmp_path):
    """compile.* counters (registered by repro.core.backend) rise by one
    lowering for a new jitted function and by none for a cached call, and
    reach SimulationService.stats()."""
    import jax
    import jax.numpy as jnp

    def lowerings():
        return obs.REGISTRY.snapshot()["counters"].get("compile.lowerings", 0)

    f = jax.jit(lambda x: x * 7 + 3)
    x = jnp.ones(37, jnp.int32)
    n0 = lowerings()
    f(x).block_until_ready()
    n1 = lowerings()
    f(x).block_until_ready()
    assert (n1 - n0, lowerings() - n1) == (1, 0)
    counters = _small_service(tmp_path).stats()["metrics"]["counters"]
    for name in ("compile.traces", "compile.lowerings",
                 "compile.backend_compiles", "compile.seconds{phase=lower}"):
        assert counters[name] > 0, name


# -- metrics registry --------------------------------------------------------

def test_counter_gauge_info():
    reg = obs.MetricsRegistry()
    reg.counter("c").inc()
    reg.counter("c").inc(4)
    assert reg.counter("c") is reg.counter("c")      # get-or-create
    with pytest.raises(ValueError):
        reg.counter("c").inc(-1)
    reg.gauge("g").set(2.5)
    reg.gauge("g").inc(-1.0)
    reg.info("i").set("jax")
    snap = reg.snapshot()
    assert snap["counters"]["c"] == 5
    assert snap["gauges"]["g"] == 1.5
    assert snap["info"]["i"] == "jax"


def test_labeled_series_render():
    reg = obs.MetricsRegistry()
    reg.counter("runs", {"backend": "jax"}).inc(2)
    reg.counter("runs", {"backend": "oracle"}).inc()
    snap = reg.snapshot()["counters"]
    assert snap["runs{backend=jax}"] == 2
    assert snap["runs{backend=oracle}"] == 1


def test_histogram_buckets():
    reg = obs.MetricsRegistry()
    h = reg.histogram("h")
    for x in (1, 3, 100):
        h.observe(x)
    d = reg.snapshot()["histograms"]["h"]
    assert d["count"] == 3 and d["min"] == 1 and d["max"] == 100
    assert d["mean"] == pytest.approx(104 / 3)
    assert d["buckets"] == {"1": 1, "4": 1, "128": 1}


def test_registry_reset():
    reg = obs.MetricsRegistry()
    reg.counter("x").inc()
    reg.reset()
    assert reg.snapshot() == {"counters": {}, "gauges": {}, "info": {},
                              "histograms": {}}


# -- service integration -----------------------------------------------------

def _small_service(tmp_path, **kw):
    return SimulationService(root=tmp_path / "store", lock_wait_s=None,
                             metrics=obs.MetricsRegistry(), **kw)


def test_service_metrics_supersede_stats(tmp_path):
    svc = _small_service(tmp_path)
    topo = T.one_cluster(4, 3)
    svc.query(topo, W_list=[500], lam_list=[3], reps=4, backend="oracle")
    svc.query(topo, W_list=[500], lam_list=[3], reps=4, backend="oracle")
    s = svc.stats()
    m = s["metrics"]
    # every flat broker/store stat is covered by a metrics series
    assert m["counters"]["broker.queries"] == s["n_queries"] == 2
    assert m["counters"]["broker.cache_hits"] == s["n_cache_hits"] == 1
    assert m["counters"]["broker.dispatches"] == s["n_dispatches"] == 1
    assert m["counters"]["store.puts"] == s["store"]["puts"]
    assert m["counters"]["store.misses"] == s["store"]["misses"]
    assert m["counters"]["store.hits_mem"] == s["store"]["hits_mem"]
    assert m["gauges"]["store.lru_len"] == s["store"]["lru_len"]
    assert m["gauges"]["broker.history_cells"] == s["n_history_cells"]
    assert m["info"]["backend.default"] == s["default_backend"]
    assert m["info"]["engine.version"] == str(s["engine_version"])
    assert m["gauges"]["backend.n_devices"] == s["n_devices"]
    # engine/backend series from the global registry are grafted in
    assert any(k.startswith("backend.run_rows") for k in m["counters"])
    assert m["histograms"]["broker.rows_per_dispatch"]["count"] == 1


def test_service_trace_spans(tmp_path):
    svc = _small_service(tmp_path)
    topo = T.one_cluster(8, 5)
    with obs.trace_to(tmp_path / "trace") as tr:
        r = svc.query(topo, W_list=[2000], lam_list=[5], reps=40,
                      backend="jax")
    names = {e["name"] for e in tr.spans()}
    assert {"service.query", "broker.flush", "broker.round",
            "broker.dispatch", "estimator.update", "backend.run_rows",
            "backend.fetch", "store.get", "store.put"} <= names
    assert "engine.segment" in names        # 40 rows >= seg_min_rows
    # dispatch span carries the bucket attributes
    disp = next(e for e in tr.spans() if e["name"] == "broker.dispatch")
    assert disp["args"]["backend"] == "jax"
    assert disp["args"]["n_rows"] == 40
    assert disp["args"]["n_padded"] == 64   # pow2 padding
    # one request's spans join on its store key prefix
    query = next(e for e in tr.spans() if e["name"] == "service.query")
    assert query["args"]["keys"] == disp["args"]["keys"] == f"[{r.key[:12]}]"
    rounds = [e for e in tr.spans() if e["name"] == "broker.round"]
    assert [e["args"]["round"] for e in rounds] == [0, 1]
    assert rounds[0]["args"]["n_rows"] == 40
    assert rounds[1]["args"]["n_rows"] == 0     # answered and persisted
    put = next(e for e in tr.spans() if e["name"] == "store.put")
    assert put["args"]["bytes"] == sum(
        p.stat().st_size for p in (tmp_path / "store").iterdir())


def test_artifacts_byte_identical_with_tracing(tmp_path):
    """Tracing must observe, never perturb: the stored npz artifact is
    byte-for-byte identical with tracing on vs off."""
    topo = T.one_cluster(4, 3)
    kw = dict(W_list=[800], lam_list=[3], reps=40, backend="jax")
    svc_off = _small_service(tmp_path / "off")
    svc_off.query(topo, **kw)
    svc_on = _small_service(tmp_path / "on")
    with obs.trace_to(tmp_path / "trace"):
        svc_on.query(topo, **kw)
    off = sorted((tmp_path / "off" / "store").glob("*.npz"))
    on = sorted((tmp_path / "on" / "store").glob("*.npz"))
    assert len(off) == len(on) == 1
    assert off[0].name == on[0].name        # same content key
    assert off[0].read_bytes() == on[0].read_bytes()


def test_dispatch_log_ring_buffer(tmp_path):
    svc = _small_service(tmp_path, dispatch_log_max=2)
    topo = T.one_cluster(4, 3)
    for w in (300, 400, 500):               # three distinct dispatches
        svc.query(topo, W_list=[w], lam_list=[3], reps=4, backend="oracle")
    log = svc.broker.dispatch_log
    assert len(log) == 2                    # bounded
    assert log[0]["n_rows"] == 4            # deque keeps list-style indexing
    assert svc.broker.n_dispatches == 3
    assert svc.broker.n_dispatch_log_dropped == 1
    assert svc.stats()["n_dispatch_log_dropped"] == 1
    m = svc.stats()["metrics"]["counters"]
    assert m["broker.dispatch_log_dropped"] == 1


def test_dispatch_log_unbounded_opt_out(tmp_path):
    svc = _small_service(tmp_path, dispatch_log_max=None)
    assert svc.broker.dispatch_log.maxlen is None


def test_last_stats_reset_every_run():
    """A monolithic run must not report the previous segmented run's
    telemetry: last_stats is reset at the start of every run_rows."""
    be = bk.get_backend("jax")
    topo = T.one_cluster(4, 2)
    model = resolve_model(topo, "divisible", W_list=[900], lam_list=[2])
    run_rows(model, grid_rows([900], [2], 48), backend="jax")
    assert be.last_stats is not None        # 48 rows: segmented path
    run_rows(model, grid_rows([900], [2], 4), backend="jax")
    assert be.last_stats is None            # 4 rows: monolithic path


def test_adaptive_reps_saved_metric(tmp_path):
    svc = _small_service(tmp_path)
    topo = T.one_cluster(4, 3)
    r = svc.query(topo, W_list=[600], lam_list=[3], ci=0.05,
                  ci_relative=True, batch_reps=16, max_reps=256,
                  backend="oracle")
    assert not r.from_cache
    m = svc.stats()["metrics"]["counters"]
    assert m["broker.adaptive_reps"] == r.total_reps
    assert m["broker.adaptive_reps_saved"] == 256 - r.total_reps
