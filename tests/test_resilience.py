"""Fault injection + self-healing dispatch (DESIGN.md §10).

The acceptance story: with a FaultPlan injecting 20% backend raise-faults, a
100-query service run completes with ZERO client-visible exceptions, the
stored artifacts are byte-identical to a fault-free control run (fallback
backends are bit-identical, so recovery is invisible in results), and the
metrics show nonzero ``resilience.fallbacks`` / ``resilience.salvaged_rows``.
Around that: FaultPlan determinism and env activation, retry/backoff,
circuit-breaker state machine, bisection salvage economics, crash-safe lock
recovery (killed holder unblocks waiters in seconds), the stale-break race,
and corrupt-artifact quarantine under concurrency.

This file is also what the CI chaos job runs with ``REPRO_WS_FAULT_PLAN``
set: an autouse fixture masks the ambient plan in-process (each test scripts
its own faults), while subprocess helpers inherit the env and take the
ambient chaos with them.
"""
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

from repro import obs
from repro.core import one_cluster
from repro.core import backend as bk
from repro.core.sweep import grid_rows, resolve_model
from repro.service import ResultStore, SimulationService
from repro.service import resilience as rz

TOPO = one_cluster(4, 2)


@pytest.fixture(autouse=True)
def _mask_ambient_plan():
    """Tests script their own faults; the CI chaos job's env plan must not
    leak into in-process assertions (subprocesses still inherit it)."""
    with rz.fault_plan(rz.no_faults()):
        yield
    rz.reload_env_plan()


def _model(**kw):
    args = dict(W_list=[2000], lam_list=[2], pow2_max_events=True)
    args.update(kw)
    return resolve_model(TOPO, "divisible", **args)


# ---------------------------------------------------------------------------
# FaultPlan: determinism, serialisation, env activation
# ---------------------------------------------------------------------------

def test_fault_plan_deterministic_sequence():
    def fires(seed):
        plan = rz.FaultPlan(rng_seed=seed, sites={"s": rz.Prob(0.3)})
        out = []
        for _ in range(50):
            try:
                plan.fire("s", {})
                out.append(0)
            except rz.InjectedFault:
                out.append(1)
        return out

    a, b = fires(7), fires(7)
    assert a == b                        # same seed, same call sequence
    assert 0 < sum(a) < 50               # actually probabilistic
    assert fires(8) != a                 # seed matters


def test_fault_plan_json_roundtrip():
    plan = rz.FaultPlan(rng_seed=3, sites={
        "backend.run_rows": rz.Prob(0.2, kind="raise", per_row=True,
                                    match={"backend": "jax"}),
        "store.put": [rz.Prob(0.5, kind="torn_write", max_faults=2),
                      rz.At(4, kind="oserror")],
    })
    plan2 = rz.FaultPlan.from_json(plan.to_json())
    assert plan2.rng_seed == plan.rng_seed
    assert plan2.sites == plan.sites
    assert plan2.to_json() == plan.to_json()


def test_fault_plan_custom_exc_not_serialisable():
    with pytest.raises(TypeError):
        rz.FaultPlan(sites={"s": rz.At(1, exc=RuntimeError)}).to_json()


def test_fault_plan_env_activation(monkeypatch):
    plan = rz.FaultPlan(rng_seed=1, sites={"s": rz.Prob(1.0)})
    monkeypatch.setenv(rz.FAULT_PLAN_ENV, plan.to_json())
    rz.install(None)                     # unmask the env plan
    rz.reload_env_plan()
    with pytest.raises(rz.InjectedFault):
        rz.fault_point("s")
    monkeypatch.delenv(rz.FAULT_PLAN_ENV)
    rz.reload_env_plan()
    assert rz.fault_point("s") is None


def test_at_fires_once_each():
    plan = rz.FaultPlan(sites={"s": rz.At(2, 5)})
    hits = []
    for i in range(8):                   # index from ctx, like train.step
        try:
            plan.fire("s", {"index": i})
        except rz.InjectedFault:
            hits.append(i)
    assert hits == [2, 5]
    for i in range(8):                   # once each: replay fires nothing
        plan.fire("s", {"index": i})


def test_per_row_poisoning_is_stable_and_match_filters():
    spec = rz.Prob(0.2, per_row=True, match={"backend": "jax"})
    plan = rz.FaultPlan(rng_seed=7, sites={"backend.run_rows": spec})
    seeds = list(range(1, 201))
    poisoned = [s for s in seeds if plan.row_poisoned(spec, s)]
    assert poisoned == [s for s in seeds if plan.row_poisoned(spec, s)]
    assert 10 < len(poisoned) < 80       # ~20% of 200
    # a dispatch containing a poisoned row fails on the matched backend...
    with pytest.raises(rz.InjectedFault):
        plan.fire("backend.run_rows",
                  {"backend": "jax", "row_seeds": poisoned[:1]})
    # ...on every retry (deterministic poison, not a per-call draw)...
    with pytest.raises(rz.InjectedFault):
        plan.fire("backend.run_rows",
                  {"backend": "jax", "row_seeds": poisoned[:1]})
    clean = [s for s in seeds if s not in poisoned]
    assert plan.fire("backend.run_rows",
                     {"backend": "jax", "row_seeds": clean[:5]}) is None
    # ...and never on other backends (match filter)
    assert plan.fire("backend.run_rows",
                     {"backend": "oracle", "row_seeds": poisoned}) is None


def test_max_faults_bounds_injection():
    plan = rz.FaultPlan(sites={"s": rz.Prob(1.0, max_faults=2)})
    n = 0
    for _ in range(10):
        try:
            plan.fire("s", {})
        except rz.InjectedFault:
            n += 1
    assert n == 2


def test_fault_point_is_noop_without_plan(monkeypatch):
    rz.install(None)
    monkeypatch.delenv(rz.FAULT_PLAN_ENV, raising=False)
    rz.reload_env_plan()
    assert rz.fault_point("backend.run_rows", backend="jax") is None


def test_failure_injector_is_a_fault_plan_wrapper():
    from repro.runtime.fault import FailureInjector, InjectedFailure
    inj = FailureInjector(fail_at=(3, 7))
    seen = []
    for step in range(10):
        try:
            inj.maybe_fail(step)
        except InjectedFailure:
            seen.append(step)
    assert seen == [3, 7]
    inj.maybe_fail(3)                    # once each


# ---------------------------------------------------------------------------
# RetryPolicy / jitter
# ---------------------------------------------------------------------------

def test_retry_recovers_from_transient_and_counts():
    m = obs.MetricsRegistry()
    pol = rz.RetryPolicy(max_attempts=4, base_s=0.0, cap_s=0.0)
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise OSError("transient")
        return "ok"

    assert pol.call(flaky, metrics=m, label="t") == "ok"
    assert len(calls) == 3
    snap = m.snapshot()["counters"]
    assert snap["resilience.retries"] == 2
    assert snap["resilience.retries{op=t}"] == 2


def test_retry_exhausts_and_reraises():
    pol = rz.RetryPolicy(max_attempts=3, base_s=0.0, cap_s=0.0)
    calls = []

    def dead():
        calls.append(1)
        raise OSError("persistent")

    with pytest.raises(OSError):
        pol.call(dead)
    assert len(calls) == 3


def test_retry_does_not_catch_unlisted_exceptions():
    pol = rz.RetryPolicy(max_attempts=5, base_s=0.0, cap_s=0.0)
    calls = []

    def bug():
        calls.append(1)
        raise ValueError("caller bug")

    with pytest.raises(ValueError):
        pol.call(bug)
    assert len(calls) == 1               # no retry on caller bugs


def test_backoff_bounds():
    import random
    rng = random.Random(0)
    pol = rz.RetryPolicy(base_s=0.01, cap_s=0.08)
    for k in range(10):
        s = pol.sleep_s(k, rng)
        assert 0.0 <= s <= min(0.08, 0.01 * 2 ** k)
    prev = 0.05
    for _ in range(50):
        nxt = rz.decorrelated_jitter(prev, 0.01, 0.5, rng)
        assert 0.01 <= nxt <= 0.5
        prev = nxt


# ---------------------------------------------------------------------------
# circuit breaker
# ---------------------------------------------------------------------------

def test_breaker_trip_halfopen_close_cycle():
    m = obs.MetricsRegistry()
    br = rz.CircuitBreaker(k_failures=3, cooldown_s=0.05, metrics=m)
    assert br.allow("jax")
    for _ in range(3):
        br.record_failure("jax")
    assert br.state("jax") == rz.BREAKER_OPEN
    assert not br.allow("jax")           # open: rejects
    snap = m.snapshot()
    assert snap["gauges"]["resilience.breaker_state{backend=jax}"] == 1.0
    assert snap["counters"]["resilience.breaker_trips{backend=jax}"] == 1
    time.sleep(0.06)
    assert br.state("jax") == rz.BREAKER_HALF_OPEN
    assert br.allow("jax")               # one probe allowed
    assert not br.allow("jax")           # ...but only one per window
    br.record_success("jax")
    assert br.state("jax") == rz.BREAKER_CLOSED
    assert br.allow("jax")
    assert m.snapshot()["gauges"][
        "resilience.breaker_state{backend=jax}"] == 0.0


def test_breaker_failed_probe_reopens():
    br = rz.CircuitBreaker(k_failures=1, cooldown_s=0.05)
    br.record_failure("b")
    time.sleep(0.06)
    assert br.allow("b")                 # probe
    br.record_failure("b")               # probe fails -> cooldown restarts
    assert br.state("b") == rz.BREAKER_OPEN
    assert not br.allow("b")


# ---------------------------------------------------------------------------
# fallback chain
# ---------------------------------------------------------------------------

def test_fallback_chain_divisible_reaches_oracle():
    chain = rz.fallback_chain("jax", _model())
    assert chain[0] == "jax"
    assert "oracle" in chain
    assert chain.index("oracle") >= 1


def test_fallback_chain_excludes_incompatible_oracle():
    # The oracle twins neither trace logging nor non-divisible models.
    from repro.core import dag_gen as gen
    assert "oracle" not in rz.fallback_chain("jax", _model(log_trace=True))
    dag = resolve_model(TOPO, "dag", W_list=[100], lam_list=[2],
                        dag=gen.binary_tree(4))
    assert "oracle" not in rz.fallback_chain("jax", dag)


def test_fallback_chain_never_demotes_to_interpret_mode():
    # Interpret mode is a test substrate: a demotion onto it would hide
    # that the device path failed.
    from repro.core import dag_gen as gen
    dag = resolve_model(TOPO, "dag", W_list=[100], lam_list=[2],
                        dag=gen.binary_tree(4))
    for primary in ("pallas", "jax"):
        for model in (_model(), dag):
            chain = rz.fallback_chain(primary, model)
            assert "pallas_interpret" not in chain
            assert chain[0] == primary


# ---------------------------------------------------------------------------
# dispatch_resilient: bisection salvage economics
# ---------------------------------------------------------------------------

def _resilient_run(n_rows, poisoned_seeds, **cfg_kw):
    """Dispatch n_rows through dispatch_resilient against a fake 'jax' that
    raises whenever its batch contains a poisoned seed; 'oracle' computes
    everything. Returns (grid, degraded, calls, metrics registry)."""
    m = obs.MetricsRegistry()
    model = _model()
    rows = grid_rows([2000], [2], n_rows)
    oracle = bk.get_backend("oracle")
    calls = []

    def call(rws, buds, name):
        calls.append((name, len(rws)))
        if name == "jax" and set(np.asarray(rws.seed)) & poisoned_seeds:
            raise rz.InjectedFault("poisoned row")
        return oracle.run_rows(model, rws, 0.25, ev_budget=buds)

    cfg = rz.ResilienceConfig(
        retry=rz.RetryPolicy(max_attempts=1, base_s=0.0, cap_s=0.0),
        breaker_failures=10_000, **cfg_kw)
    grid, degraded = rz.dispatch_resilient(
        call, rows, None, ["jax", "oracle"], retry=cfg.retry,
        breaker=cfg.make_breaker(m), metrics=m, salvage=cfg.salvage)
    return grid, degraded, calls, m


def test_salvage_one_poisoned_row_costs_log_n():
    n = 32
    rows = grid_rows([2000], [2], n)
    bad = {int(np.asarray(rows.seed)[11])}
    grid, degraded, calls, m = _resilient_run(n, bad)
    assert degraded
    # fault-free control: identical rows on the (bit-identical) oracle
    want = bk.get_backend("oracle").run_rows(_model(), rows, 0.25)
    assert np.array_equal(grid.makespan, want.makespan)
    assert np.array_equal(grid.seed, want.seed)
    # economics: O(log n) jax attempts, exactly one row demoted
    jax_calls = [c for c in calls if c[0] == "jax"]
    assert len(jax_calls) <= 2 * (n.bit_length() + 1)
    assert [c for c in calls if c[0] == "oracle"] == [("oracle", 1)]
    snap = m.snapshot()["counters"]
    assert snap["resilience.salvaged_rows"] == n - 1
    assert snap["resilience.fallbacks"] == 1


def test_salvage_disabled_falls_back_whole_batch():
    n = 16
    rows = grid_rows([2000], [2], n)
    bad = {int(np.asarray(rows.seed)[3])}
    grid, degraded, calls, m = _resilient_run(n, bad, salvage=False)
    assert degraded
    assert ("oracle", n) in calls        # whole batch demoted in one go
    assert m.snapshot()["counters"].get("resilience.salvaged_rows", 0) == 0


def test_dispatch_resilient_clean_path_is_one_call():
    grid, degraded, calls, m = _resilient_run(8, set())
    assert not degraded
    assert calls == [("jax", 8)]
    assert "resilience.fallbacks" not in m.snapshot()["counters"]


def test_dispatch_resilient_nonrecoverable_propagates():
    m = obs.MetricsRegistry()
    rows = grid_rows([2000], [2], 4)

    def call(rws, buds, name):
        raise ValueError("config bug")

    cfg = rz.ResilienceConfig()
    with pytest.raises(ValueError):
        rz.dispatch_resilient(call, rows, None, ["jax", "oracle"],
                              retry=cfg.retry, breaker=cfg.make_breaker(m),
                              metrics=m)


def test_dispatch_resilient_exhausted_chain_reraises():
    m = obs.MetricsRegistry()
    rows = grid_rows([2000], [2], 1)    # single row: no bisection possible

    def call(rws, buds, name):
        raise rz.InjectedFault(f"{name} down")

    cfg = rz.ResilienceConfig(
        retry=rz.RetryPolicy(max_attempts=1, base_s=0.0, cap_s=0.0))
    with pytest.raises(rz.InjectedFault):
        rz.dispatch_resilient(call, rows, None, ["jax", "oracle"],
                              retry=cfg.retry, breaker=cfg.make_breaker(m),
                              metrics=m)


# ---------------------------------------------------------------------------
# acceptance: 100 queries, 20% injected faults, byte-identical artifacts
# ---------------------------------------------------------------------------

def _chaos_queries(svc):
    return [svc.make_query(TOPO, W_list=[2000], lam_list=[3], reps=1,
                           seed0=s, backend="jax") for s in range(1, 101)]


def test_chaos_run_zero_exceptions_byte_identical(tmp_path):
    cfg = rz.ResilienceConfig(
        retry=rz.RetryPolicy(max_attempts=1, base_s=0.0, cap_s=0.0),
        breaker_failures=10_000)         # keep bisecting; see DESIGN.md §10

    # control: fault-free
    m0 = obs.MetricsRegistry()
    svc0 = SimulationService(root=tmp_path / "a", metrics=m0, resilience=cfg)
    r0 = svc0.query_many(_chaos_queries(svc0))

    # chaos: 20% of rows poisoned on the jax backend, every retry
    plan = rz.FaultPlan(rng_seed=7, sites={
        "backend.run_rows": rz.Prob(0.2, kind="raise", per_row=True,
                                    match={"backend": "jax"})})
    m1 = obs.MetricsRegistry()
    svc1 = SimulationService(root=tmp_path / "b", metrics=m1, resilience=cfg)
    with rz.fault_plan(plan):
        r1 = svc1.query_many(_chaos_queries(svc1))   # must not raise

    # answers identical
    assert len(r0) == len(r1) == 100
    for a, b in zip(r0, r1):
        assert np.array_equal(a.cells.mean, b.cells.mean)

    # stored artifacts byte-identical: same keys, same npz bytes
    a_npz = sorted((tmp_path / "a").glob("*.npz"))
    b_npz = sorted((tmp_path / "b").glob("*.npz"))
    assert [p.name for p in a_npz] == [p.name for p in b_npz]
    assert len(a_npz) == 100
    for pa, pb in zip(a_npz, b_npz):
        assert pa.read_bytes() == pb.read_bytes(), pa.name

    # recovery really happened and is visible in stats()
    st = svc1.stats()
    counters = st["metrics"]["counters"]
    assert counters.get("resilience.fallbacks", 0) > 0
    assert counters.get("resilience.salvaged_rows", 0) > 0
    assert st["degraded"]["degraded"]
    # ...and the control run stayed clean
    st0 = svc0.stats()
    assert not st0["degraded"]["degraded"]
    assert "resilience.fallbacks" not in st0["metrics"]["counters"]


def test_degraded_summary_shape():
    m = obs.MetricsRegistry()
    out = rz.degraded_summary(m)
    assert out["degraded"] is False
    m.counter("resilience.fallbacks").inc(2)
    m.counter("resilience.dispatch_failures", {"backend": "jax"}).inc(3)
    out = rz.degraded_summary(m)
    assert out["fallbacks"] == 2
    assert out["dispatch_failures"] == 3
    assert out["degraded"] is True


# ---------------------------------------------------------------------------
# crash-safe locks
# ---------------------------------------------------------------------------

_HOLDER_CRASH = """
import os, sys
sys.path.insert(0, {src!r})
from repro.service import ResultStore
store = ResultStore(root={root!r}, lock_stale_s=300.0)
assert store.try_lock({key!r})
print("LOCKED", flush=True)
os._exit(0)          # crash while holding: no unlock, no cleanup
"""


def _src():
    return str(Path(__file__).resolve().parents[1] / "src")


def test_killed_lock_holder_unblocks_waiter_fast(tmp_path):
    root = tmp_path / "store"
    key = "deadbeef"
    out = subprocess.run(
        [sys.executable, "-c",
         _HOLDER_CRASH.format(src=_src(), root=str(root), key=key)],
        capture_output=True, text=True, timeout=60)
    assert "LOCKED" in out.stdout, out.stderr
    store = ResultStore(root=root, lock_stale_s=300.0)
    assert (root / f"{key}.lock").exists()      # wreckage on disk
    t0 = time.monotonic()
    assert store.try_lock(key)                  # breaks the dead holder's
    took = time.monotonic() - t0                # lock, far under stale_s
    assert took < 5.0
    assert store.locks_broken == 1
    store.unlock(key)


def test_killed_lock_holder_unblocks_service_query(tmp_path):
    root = tmp_path / "store"
    svc = SimulationService(root=root, lock_wait_s=30.0)
    svc.store.lock_stale_s = 300.0
    q = svc.make_query(TOPO, W_list=[1000], lam_list=[2], reps=2)
    out = subprocess.run(
        [sys.executable, "-c",
         _HOLDER_CRASH.format(src=_src(), root=str(root), key=q.key())],
        capture_output=True, text=True, timeout=60)
    assert "LOCKED" in out.stdout, out.stderr
    t0 = time.monotonic()
    res = svc.query_many([q])[0]                # must not wait lock_wait_s
    assert time.monotonic() - t0 < 5.0
    assert res.cells.mean.size == 1 and np.isfinite(res.cells.mean).all()


def test_lock_holder_crash_via_fault_plan(tmp_path):
    """kind="exit" at store.lock.acquired really kills the subprocess."""
    code = """
import os, sys
sys.path.insert(0, {src!r})
from repro.service import ResultStore, resilience as rz
rz.install(rz.FaultPlan(sites={{"store.lock.acquired": rz.Prob(1.0, kind="exit")}}))
store = ResultStore(root={root!r})
store.try_lock("k")
print("UNREACHABLE")
""".format(src=_src(), root=str(tmp_path / "s"))
    out = subprocess.run([sys.executable, "-c", code],
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 17
    assert "UNREACHABLE" not in out.stdout
    assert (tmp_path / "s" / "k.lock").exists()


_RACER = """
import os, sys, time
sys.path.insert(0, {src!r})
from repro.service import ResultStore
store = ResultStore(root={root!r}, lock_stale_s=0.5)
print("READY", flush=True)
go = {go!r}
while not os.path.exists(go):
    time.sleep(0.001)
print("WON" if store.try_lock({key!r}) else "LOST", flush=True)
"""


def test_stale_break_race_single_winner(tmp_path):
    """N processes breaking the same stale lock: exactly one wins."""
    root = tmp_path / "store"
    key = "cafef00d"
    store = ResultStore(root=root, lock_stale_s=0.5)
    for round_i in range(3):
        assert store.try_lock(key)       # a live-pid lock...
        lock = root / f"{key}.lock"
        old = time.time() - 60
        os.utime(lock, (old, old))       # ...made stale by age
        go = tmp_path / f"go{round_i}"
        procs = [subprocess.Popen(
            [sys.executable, "-c",
             _RACER.format(src=_src(), go=str(go), root=str(root), key=key)],
            stdout=subprocess.PIPE, text=True) for _ in range(3)]
        for p in procs:                  # barrier: all imported and waiting
            assert p.stdout.readline().strip() == "READY"
        go.touch()
        outs = [p.communicate(timeout=60)[0].strip() for p in procs]
        assert sorted(outs) == ["LOST", "LOST", "WON"], outs
        store.unlock(key)
        assert not lock.with_suffix(".lock-break").exists()


def test_live_lock_blocks_and_heartbeat_defers_staleness(tmp_path):
    store = ResultStore(root=tmp_path, lock_stale_s=0.4)
    other = ResultStore(root=tmp_path, lock_stale_s=0.4)
    assert store.try_lock("k")
    assert not other.try_lock("k")       # live same-pid holder blocks
    time.sleep(0.25)
    store.heartbeat("k")                 # holder still working
    time.sleep(0.25)                     # age since acquire > stale_s...
    assert store.lock_live("k")          # ...but heartbeat keeps it live
    store.unlock("k")
    assert other.try_lock("k")
    other.unlock("k")


def test_gc_never_evicts_under_live_lock(tmp_path):
    from repro.core.sweep import run_grid
    g = run_grid(TOPO, W_list=[1500], lam_list=[2], reps=2)
    store = ResultStore(root=tmp_path, lock_stale_s=300.0)
    store.put("held", g)
    assert store.try_lock("held")        # in-flight: a waiter may need it
    for i in range(6):
        store.put(f"fill{i}", g)
    one = store._entry_bytes("held")
    store.gc(max_bytes=2 * one)          # far below what 7 artifacts need
    assert store._path("held").exists()  # survived: its lock is live
    assert not store._path("fill0").exists()
    store.unlock("held")
    store.gc(max_bytes=0)
    assert not store._path("held").exists()


# ---------------------------------------------------------------------------
# store I/O faults: retry, torn writes, corrupt-artifact quarantine
# ---------------------------------------------------------------------------

def test_store_get_retries_transient_oserror(tmp_path):
    from repro.core.sweep import run_grid
    g = run_grid(TOPO, W_list=[1500], lam_list=[2], reps=2)
    store = ResultStore(root=tmp_path)
    store.put("k", g)
    store.clear_memory()
    plan = rz.FaultPlan(sites={"store.get": rz.Prob(1.0, kind="oserror",
                                                    max_faults=2)})
    with rz.fault_plan(plan):
        g2 = store.get("k")              # 2 transient failures, then reads
    assert g2 is not None
    assert np.array_equal(g2.makespan, g.makespan)
    assert store.corrupt == 0            # recovered, nothing quarantined


def test_store_torn_write_is_quarantined_and_recomputable(tmp_path):
    from repro.core.sweep import run_grid
    g = run_grid(TOPO, W_list=[1500], lam_list=[2], reps=2)
    store = ResultStore(root=tmp_path)
    plan = rz.FaultPlan(sites={"store.put": rz.Prob(1.0, kind="torn_write",
                                                    max_faults=1)})
    with rz.fault_plan(plan):
        store.put("k", g)
    assert store.get("k") is g           # this process's LRU masks the tear
    store.clear_memory()
    assert store.get("k") is None        # torn npz: clean miss...
    assert (tmp_path / "k.corrupt").exists()   # ...quarantined
    store.put("k", g)                    # recomputable
    store.clear_memory()
    assert np.array_equal(store.get("k").makespan, g.makespan)


_READER = """
import sys
sys.path.insert(0, {src!r})
from repro.service import ResultStore
store = ResultStore(root={root!r})
print("MISS" if store.get({key!r}) is None else "HIT", flush=True)
"""


@pytest.mark.parametrize("corruption", ["zero", "bit_flip"])
def test_corrupt_artifact_two_readers_one_quarantine(tmp_path, corruption):
    from repro.core.sweep import run_grid
    g = run_grid(TOPO, W_list=[1500], lam_list=[2], reps=2)
    root = tmp_path / "store"
    store = ResultStore(root=root)
    store.put("k", g)
    path = root / "k.npz"
    if corruption == "zero":
        path.write_bytes(b"")
    else:
        raw = bytearray(path.read_bytes())
        raw[len(raw) // 2] ^= 0xFF
        path.write_bytes(bytes(raw))
    procs = [subprocess.Popen(
        [sys.executable, "-c",
         _READER.format(src=_src(), root=str(root), key="k")],
        stdout=subprocess.PIPE, text=True) for _ in range(2)]
    outs = [p.communicate(timeout=60)[0].strip() for p in procs]
    assert outs == ["MISS", "MISS"]      # both miss cleanly, no crash
    assert not path.exists()
    assert list(root.glob("*.corrupt")) == [root / "k.corrupt"]
    store.clear_memory()
    store.put("k", g)                    # the key is recomputable
    store.clear_memory()
    assert np.array_equal(store.get("k").makespan, g.makespan)


# ---------------------------------------------------------------------------
# broker integration: poll backoff, lock_polls, degraded plumbing
# ---------------------------------------------------------------------------

def test_broker_lock_wait_counts_polls(tmp_path):
    m = obs.MetricsRegistry()
    svc = SimulationService(root=tmp_path, metrics=m, lock_wait_s=0.3)
    svc.broker.lock_poll_s = 0.01
    q = svc.make_query(TOPO, W_list=[1000], lam_list=[2], reps=2)
    assert svc.store.try_lock(q.key())   # our own live pid: broker waits
    res = svc.query_many([q])[0]         # timeout -> computes anyway
    assert res.cells.mean.size == 1 and np.isfinite(res.cells.mean).all()
    assert m.snapshot()["counters"]["broker.lock_polls"] >= 2
    svc.store.unlock(q.key())


def test_broker_dispatch_log_records_degraded(tmp_path):
    cfg = rz.ResilienceConfig(
        retry=rz.RetryPolicy(max_attempts=1, base_s=0.0, cap_s=0.0))
    svc = SimulationService(root=tmp_path, resilience=cfg)
    plan = rz.FaultPlan(rng_seed=1, sites={
        "backend.run_rows": rz.Prob(1.0, kind="raise", max_faults=1,
                                    match={"backend": "jax"})})
    with rz.fault_plan(plan):
        svc.query(TOPO, W_list=[1000], lam_list=[2], reps=2, backend="jax")
    assert any(e.get("degraded") for e in svc.broker.dispatch_log)
    svc2 = SimulationService(root=tmp_path / "clean")
    svc2.query(TOPO, W_list=[1000], lam_list=[2], reps=2)
    assert all(not e.get("degraded") for e in svc2.broker.dispatch_log)


def test_resilience_disabled_propagates_faults(tmp_path):
    svc = SimulationService(root=tmp_path,
                            resilience=rz.ResilienceConfig(enabled=False))
    plan = rz.FaultPlan(sites={
        "backend.run_rows": rz.Prob(1.0, match={"backend": "jax"})})
    with rz.fault_plan(plan):
        with pytest.raises(rz.InjectedFault):
            svc.query(TOPO, W_list=[1000], lam_list=[2], reps=2,
                      backend="jax")
