"""Invariant checker suite (repro.check): each pass runs clean on the real
tree, and — the part that keeps the suite honest — each rule catches a
deliberately seeded violation (poisoned key field, unbalanced lock path,
forced bit-mismatch dispatch, ...)."""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.check import (Finding, jaxpr_lint, load_baseline, protocol_lint,
                         default_baseline_path, sanitizer as sz,
                         split_against_baseline)
from repro.core import backend as bk
from repro.core import engine as eng
from repro.core import one_cluster, sweep
from repro.kernels import ws_sim
from repro.service import SimulationService
from repro.service import resilience as rz

TOPO = one_cluster(4, 2)


@pytest.fixture(autouse=True)
def _isolated():
    """Mask any ambient REPRO_WS_FAULTS plan; each test arms the sanitizer
    explicitly and never leaks it."""
    with rz.fault_plan(rz.no_faults()):
        yield
    rz.reload_env_plan()
    sz.uninstall()
    sz.reset()


def _against_baseline(findings):
    new, _ = split_against_baseline(findings,
                                    load_baseline(default_baseline_path()))
    return new


# ---------------------------------------------------------------------------
# the suite is clean on the real tree (modulo the committed baseline)
# ---------------------------------------------------------------------------

def test_protocol_pass_clean_on_repo():
    assert _against_baseline(protocol_lint.run()) == []


def test_jaxpr_pass_clean_on_repo():
    assert _against_baseline(jaxpr_lint.run()) == []


def test_finding_fingerprint_is_line_stable():
    a = Finding("protocol", "r", "src/x.py:10", "f", "m")
    b = Finding("protocol", "r", "src/x.py:99", "f", "m")
    c = Finding("protocol", "r", "src/y.py:10", "f", "m")
    assert a.fingerprint() == b.fingerprint() != c.fingerprint()


# ---------------------------------------------------------------------------
# protocol lint: seeded violations
# ---------------------------------------------------------------------------

def _rules(findings):
    return {f.rule for f in findings}


def test_lock_unlock_path_negative():
    bad = (
        "def f(store, key):\n"
        "    if store.try_lock(key):\n"
        "        work()\n"
        "        store.unlock(key)\n")  # release not in a finally
    assert "lock.unlock_path" in _rules(
        protocol_lint.lint_source(bad, "src/repro/service/fake.py"))


def test_lock_unlock_path_positive():
    good = (
        "def f(store, keys):\n"
        "    owned = [k for k in keys if store.try_lock(k)]\n"
        "    try:\n"
        "        work()\n"
        "    finally:\n"
        "        for k in owned:\n"
        "            store.unlock(k)\n")
    assert protocol_lint.lint_source(good, "src/repro/service/fake.py") == []


def test_heartbeat_before_dispatch_negative():
    bad = (
        "def g(self, owned, buckets):\n"
        "    while True:\n"
        "        for b in buckets:\n"
        "            self._dispatch_bucket(b, owned)\n")
    assert "lock.heartbeat_before_dispatch" in _rules(
        protocol_lint.lint_source(bad, "src/repro/service/fake.py"))


def test_heartbeat_before_dispatch_positive():
    good = (
        "def g(self, owned, buckets):\n"
        "    while True:\n"
        "        for key in owned:\n"
        "            self.store.heartbeat(key)\n"
        "        for b in buckets:\n"
        "            self._dispatch_bucket(b, {})\n")
    assert protocol_lint.lint_source(good, "src/repro/service/fake.py") == []


def test_atomic_write_negative_and_allowlist():
    bad = (
        "def save(path, blob):\n"
        "    with open(path, 'wb') as f:\n"
        "        f.write(blob)\n")
    assert "store.atomic_write" in _rules(
        protocol_lint.lint_source(bad, "src/repro/service/fake.py"))
    # same write is fine inside the atomic primitive or as its writer arg
    ok = (
        "def _write_atomic(path, writer):\n"
        "    with open(path, 'wb') as f:\n"
        "        writer(f)\n"
        "def _put(self, path, arrs):\n"
        "    self._write_atomic(path, lambda f: np.savez_compressed(f))\n")
    assert protocol_lint.lint_source(ok, "src/repro/service/fake.py") == []
    # ...and outside src/repro/service/ the rule does not apply
    assert protocol_lint.lint_source(bad, "src/repro/core/fake.py") == []


def test_retry_nonrecoverable_negative_positive():
    bad = (
        "def h():\n"
        "    for attempt in range(3):\n"
        "        try:\n"
        "            op()\n"
        "        except ValueError:\n"
        "            continue\n")
    assert "resilience.retry_nonrecoverable" in _rules(
        protocol_lint.lint_source(bad, "src/repro/service/fake.py"))
    good = bad.replace("continue", "raise")
    assert protocol_lint.lint_source(good, "src/repro/service/fake.py") == []


def test_socket_cleanup_negative():
    bad = (
        "def serve(self):\n"
        "    conn, _ = self._sock.accept()\n"
        "    handle(conn)\n")  # no finally/except-raise/with release
    assert "socket.close_path" in _rules(
        protocol_lint.lint_source(bad, "src/repro/service/fake.py"))
    bad2 = (
        "def dial(path):\n"
        "    s = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)\n"
        "    s.connect(path)\n"
        "    s.close()\n")  # close exists but not on the exception path
    assert "socket.close_path" in _rules(
        protocol_lint.lint_source(bad2, "src/repro/service/fake.py"))
    # outside src/repro/service/ the rule does not apply
    assert protocol_lint.lint_source(bad, "src/repro/core/fake.py") == []


def test_socket_cleanup_positive():
    good = (
        "def serve(self):\n"
        "    conn, _ = self._sock.accept()\n"
        "    try:\n"
        "        handle(conn)\n"
        "    finally:\n"
        "        conn.close()\n"
        "def dial(path):\n"                    # ownership-transfer idiom
        "    s = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)\n"
        "    try:\n"
        "        s.connect(path)\n"
        "    except BaseException:\n"
        "        s.close()\n"
        "        raise\n"
        "    return s\n"
        "def bind(self):\n"                    # attribute-held: exempt
        "    self._sock = socket.socket(socket.AF_UNIX)\n"
        "def probe(path):\n"                   # with-statement release
        "    s = socket.create_connection(path)\n"
        "    with contextlib.closing(s):\n"
        "        s.sendall(b'ping')\n")
    assert protocol_lint.lint_source(
        good, "src/repro/service/fake.py") == []


def test_import_shadow_negative():
    assert "imports.shadow" in _rules(
        protocol_lint.lint_source("import analysis\n",
                                  "src/repro/core/fake.py"))
    assert "imports.shadow" in _rules(
        protocol_lint.lint_source("from check import sanitizer\n",
                                  "src/repro/core/fake.py"))
    assert protocol_lint.lint_source(
        "from repro.core import analysis\nfrom repro import check\n",
        "src/repro/core/fake.py") == []


def test_key_purity_check_canonical():
    dirty = {"kind": "X", "backend": "jax"}
    got = protocol_lint.check_canonical(dirty, symbol="t")
    assert [f.rule for f in got] == ["keys.purity"]
    assert "forbidden" in got[0].message
    unknown = {"kind": "X", "wibble": 1}
    got = protocol_lint.check_canonical(unknown, symbol="t")
    assert [f.rule for f in got] == ["keys.purity"]
    assert "whitelist" in got[0].message


# ---------------------------------------------------------------------------
# jaxpr lint: seeded hazards
# ---------------------------------------------------------------------------

def test_jaxpr_flags_host_callback():
    def f(x):
        return jax.pure_callback(
            lambda v: np.asarray(v), jax.ShapeDtypeStruct((), jnp.float32), x)

    closed = jax.make_jaxpr(f)(jnp.float32(1.0))
    got = jaxpr_lint.scan_jaxpr(closed, where="synthetic", symbol="t")
    assert "host_sync.callback" in {g.rule for g in got}


def test_jaxpr_flags_float64():
    with jax.enable_x64(True):
        closed = jax.make_jaxpr(lambda x: x * 2.0)(jnp.float64(1.0))
    got = jaxpr_lint.scan_jaxpr(closed, where="synthetic", symbol="t")
    assert "dtype.f64" in {g.rule for g in got}


@pytest.mark.parametrize("name", ["divisible", "dag", "adaptive"])
def test_kernel_body_has_no_mosaic_unlowerable_op(name):
    model = dict(jaxpr_lint.tiny_models())[name]
    closed = jaxpr_lint.trace_pallas(model, 4)
    prims = {e.primitive.name for e in jaxpr_lint.iter_eqns(closed.jaxpr)}
    assert "pallas_call" in prims
    assert jaxpr_lint.kernel_op_findings(closed, name) == []


def test_kernel_op_rule_flags_scatter_and_int_argmin():
    from jax.experimental import pallas as pl

    def kernel(x_ref, o_ref):
        x = x_ref[...]
        i = jnp.argmin(x).astype(jnp.int32)
        o_ref[...] = x.at[i].set(0)

    def f(x):
        return pl.pallas_call(kernel, out_shape=jax.ShapeDtypeStruct(
            x.shape, x.dtype), interpret=True)(x)

    closed = jax.make_jaxpr(f)(jnp.arange(8, dtype=jnp.int32))
    got = {g.message.split("'")[1]
           for g in jaxpr_lint.kernel_op_findings(closed, "t")}
    assert got == {"argmin[int32]", "scatter"}


def test_structural_signature_catches_shape_branch():
    def branchy(x):
        if x.shape[0] > 4:          # Python branch on a traced shape
            return x.sum()
        return (x * 2).sum()

    s4 = jaxpr_lint.structural_signature(jax.make_jaxpr(branchy)(
        jnp.zeros(4, jnp.float32)))
    s8 = jaxpr_lint.structural_signature(jax.make_jaxpr(branchy)(
        jnp.zeros(8, jnp.float32)))
    assert s4 != s8

    def straight(x):
        return (x * 2).sum()

    assert jaxpr_lint.structural_signature(
        jax.make_jaxpr(straight)(jnp.zeros(4, jnp.float32))) == \
        jaxpr_lint.structural_signature(
            jax.make_jaxpr(straight)(jnp.zeros(8, jnp.float32)))


def test_static_arg_findings_flag_float_cfg():
    @dataclasses.dataclass(frozen=True)
    class FloatCfg(eng.EngineConfig):
        alpha: float = 0.5

    from repro.core.divisible import DivisibleModel
    model = DivisibleModel(FloatCfg(topology=TOPO))
    got = jaxpr_lint.static_arg_findings("poisoned", model)
    assert {g.rule for g in got} == {"retrace.static_args"}
    assert "alpha" in got[0].message


def test_grid_shape_hazards():
    assert ws_sim.grid_shape_hazards(128) == []
    assert ws_sim.grid_shape_hazards(None) == []
    assert ws_sim.grid_shape_hazards(96)      # non-pow2 chunk
    assert ws_sim.grid_shape_hazards(0)
    assert ws_sim.grid_shape_hazards(None, G=48)
    assert ws_sim.grid_shape_hazards(None, G=64) == []


def test_donation_lint_negative():
    bad = "import jax\nf = jax.jit(g, donate_argnums=(1,))\n"
    got = jaxpr_lint.lint_donation_source(bad, "x.py")
    assert [g.rule for g in got] == ["donation.ungated"]
    ok = "donate = (1,) if _donate_ok() else ()\n" \
         "f = jax.jit(g, donate_argnums=donate)\n"
    assert jaxpr_lint.lint_donation_source(ok, "x.py") == []


# ---------------------------------------------------------------------------
# sanitizer: clean on real runs, loud on seeded corruption
# ---------------------------------------------------------------------------

def _rows(W=5_000, lam=2, n=8, seed0=1):
    return sweep.grid_rows([W], [lam], n, seed0=seed0)


def test_sanitizer_clean_on_segmented_run():
    sz.install(replay_denom=1, replay_rows=2)
    sz.reset()
    model = sweep.make_model("divisible", topology=TOPO, max_events=1 << 14)
    scn = sweep.scenario_from_rows(_rows(n=64))
    res, stats = eng.simulate_segmented(model, scn, seg_len=16)
    assert stats.n_segments > 1
    s = sz.summary()
    assert s["violations_total"] == 0
    assert s["n_probes"] >= stats.n_segments


def test_sanitizer_flags_clock_regression():
    sz.install(replay_denom=1_000_000)   # no replay noise in this test
    sz.reset()
    model = sweep.make_model("divisible", topology=TOPO, max_events=1 << 14)
    run = eng.SegmentedRun(model, sweep.scenario_from_rows(_rows(n=8)),
                           seg_len=16)
    run.step()
    assert not run.done, "workload too small to span two segments"
    run._san_prev_t[:] = 1e12            # corrupt the per-row clock memory
    run.step()
    assert sz.summary()["violations_by_rule"].get("clock_monotonic")


def test_sanitizer_flags_conservation_break():
    sz.install(replay_denom=1_000_000)
    sz.reset()
    model = sweep.make_model("divisible", topology=TOPO, max_events=1 << 14)
    run = eng.SegmentedRun(model, sweep.scenario_from_rows(_rows(n=8)),
                           seg_len=16)
    run.step()
    assert not run.done
    # Claim every lane spawned one more unit than it actually did: the
    # conservation probe (executed + in-flight == W) must fail on every
    # live lane at the next boundary.
    run.scn = run.scn._replace(W=run.scn.W + 1)
    run.step()
    assert sz.summary()["violations_by_rule"].get("work_conservation")


def test_sanitizer_flags_steal_accounting():
    sz.install(replay_denom=1_000_000)
    sz.reset()
    model = sweep.make_model("divisible", topology=TOPO, max_events=1 << 14)
    rows = _rows(n=4)
    oracle = bk.get_backend("oracle")
    grid = oracle.run_rows(model, rows)
    assert sz.summary()["violations_total"] == 0   # honest grid is clean
    grid.n_requests = grid.n_requests + 1          # lose/duplicate requests
    sz.probe("backend.result", backend=oracle, model=model, rows=rows,
             remote_prob=0.25, ev_budget=None, grid=grid)
    assert sz.summary()["violations_by_rule"].get("steal_accounting")


class _EvilBackend(bk.JaxBackend):
    """Bit-exact jax backend, then +7 on every makespan — the exact failure
    mode (silently wrong results) the oracle replay exists to catch."""
    name = "evil"

    def _run_rows(self, model, rows, remote_prob, ev_budget, devices):
        grid = super()._run_rows(model, rows, remote_prob, ev_budget,
                                 devices)
        grid.makespan = grid.makespan + 7
        return grid


def test_sanitizer_replay_catches_bit_mismatch():
    sz.install(replay_denom=1, replay_rows=2)
    sz.reset()
    model = sweep.make_model("divisible", topology=TOPO, max_events=1 << 14)
    _EvilBackend().run_rows(model, _rows(n=8))
    s = sz.summary()
    assert s["n_replayed_dispatches"] == 1
    assert s["violations_by_rule"].get("replay_mismatch")
    diff = [v for v in sz.violations() if v["rule"] == "replay_mismatch"]
    assert diff and any(d["field"] == "makespan" for d in diff[0]["diff"])


def test_sanitizer_replay_passes_honest_backend():
    sz.install(replay_denom=1, replay_rows=2)
    sz.reset()
    model = sweep.make_model("divisible", topology=TOPO, max_events=1 << 14)
    bk.get_backend("jax").run_rows(model, _rows(n=8))
    s = sz.summary()
    assert s["n_replayed_dispatches"] == 1
    assert s["violations_total"] == 0


def test_sanitizer_flags_event_history_poison():
    from repro.service.broker import EventHistory
    sz.install()
    sz.reset()
    cols = np.array([[100, 2, 2, 0, 0]], np.int64)
    sz.probe("broker.observe", sig="s", cols=cols,
             ev=np.array([0]), cap=256, history=EventHistory(), p=4)
    assert sz.summary()["violations_by_rule"].get("event_history")


def test_sanitizer_chaos_run_zero_violations(tmp_path):
    """Acceptance slice: the PR 8 chaos workload under the sanitizer —
    faults fire, recovery heals them, and every invariant probe (clock,
    conservation, steal accounting, oracle replay of every dispatch)
    stays silent."""
    sz.install(replay_denom=1, replay_rows=2)
    sz.reset()
    cfg = rz.ResilienceConfig(
        retry=rz.RetryPolicy(max_attempts=1, base_s=0.0, cap_s=0.0),
        breaker_failures=10_000)
    plan = rz.FaultPlan(rng_seed=7, sites={
        "backend.run_rows": rz.Prob(0.2, kind="raise", per_row=True,
                                    match={"backend": "jax"})})
    svc = SimulationService(root=tmp_path, resilience=cfg)
    qs = [svc.make_query(TOPO, W_list=[2000], lam_list=[3], reps=1,
                         seed0=s, backend="jax") for s in range(1, 41)]
    with rz.fault_plan(plan):
        res = svc.query_many(qs)
    assert len(res) == 40
    s = svc.stats()["sanitizer"]
    assert s["enabled"] and s["n_probes"] > 0
    assert s["violations_total"] == 0, s["violations_by_rule"]
    assert s["n_replayed_rows"] > 0


def test_stats_exposes_sanitizer_summary(tmp_path):
    svc = SimulationService(root=tmp_path)
    svc.query(TOPO, W_list=[1000], lam_list=[2], reps=2)
    s = svc.stats()["sanitizer"]
    assert s["enabled"] is False and s["violations_total"] == 0


def test_violations_reach_metrics_registry():
    from repro import obs
    sz.install()
    sz.reset()
    before = sum(c.value for _, c in
                 obs.REGISTRY.find("counter", "check.violations"))
    sz.violation("unit_test", "nowhere", message="seeded")
    found = obs.REGISTRY.find("counter", "check.violations")
    assert sum(c.value for _, c in found) == before + 1
    assert any(lbl.get("pass") == "sanitizer" and
               lbl.get("rule") == "unit_test" for lbl, _ in found)


# ---------------------------------------------------------------------------
# CLI / baseline plumbing
# ---------------------------------------------------------------------------

def test_baseline_gate_roundtrip(tmp_path):
    f = Finding("protocol", "unit.rule", "src/x.py:3", "f", "seeded")
    base = tmp_path / "baseline.json"
    base.write_text(json.dumps({"version": 1, "findings": []}))
    new, known = split_against_baseline([f], load_baseline(base))
    assert new == [f] and known == []
    from repro.check import write_baseline
    write_baseline([f], base)
    new, known = split_against_baseline([f], load_baseline(base))
    assert new == [] and known == [f]
    # moving the finding to another line keeps it baselined
    moved = Finding("protocol", "unit.rule", "src/x.py:99", "f", "seeded")
    new, known = split_against_baseline([moved], load_baseline(base))
    assert new == [] and known == [moved]
