"""Execution-backend layer (DESIGN.md §7): registry/auto-detection, the
oracle == jax == pallas_interpret parity matrix, exact max_events
relaxation + truncation, cross-backend store hits, and cross-process
in-flight dedup via advisory file locks."""
import dataclasses
import threading
import time

import numpy as np
import pytest

from repro.core import backend as bk
from repro.core import dag_gen as gen
from repro.core import topology as T
from repro.core.sweep import grid_rows, resolve_model, run_grid, run_rows
from repro.kernels.ws_sim import ws_sim_pallas
from repro.service import SimulationService
from repro.service.store import ResultStore

BACKENDS = ("oracle", "jax", "pallas_interpret")


def assert_grids_equal(a, b, msg=""):
    for f in dataclasses.fields(a):
        if f.name == "extras":
            assert set(a.extras) == set(b.extras), msg
            for k in a.extras:
                np.testing.assert_array_equal(
                    np.asarray(a.extras[k]), np.asarray(b.extras[k]),
                    err_msg=f"{msg} extras[{k}]")
        else:
            np.testing.assert_array_equal(
                np.asarray(getattr(a, f.name)),
                np.asarray(getattr(b, f.name)), err_msg=f"{msg} {f.name}")


# ---------------------------------------------------------------------------
# Registry + auto-detection.
# ---------------------------------------------------------------------------

def test_registry_roundtrip():
    assert set(bk.backend_names()) >= {"oracle", "jax", "pallas",
                                       "pallas_interpret"}
    for name in BACKENDS:
        be = bk.get_backend(name)
        assert be.name == name
        assert bk.get_backend(be) is be
        caps = be.capabilities()
        assert caps.available and caps.max_p >= 256
    with pytest.raises(ValueError):
        bk.get_backend("tpu_v7_hyperdrive")


def test_default_backend_env_override(monkeypatch):
    monkeypatch.setenv(bk.BACKEND_ENV, "oracle")
    assert bk.default_backend_name() == "oracle"
    monkeypatch.setenv(bk.BACKEND_ENV, "nope")
    with pytest.raises(ValueError):
        bk.default_backend_name()
    monkeypatch.delenv(bk.BACKEND_ENV)
    # No TPU in this container -> jax.
    assert bk.default_backend_name() == ("pallas" if bk._on_tpu() else "jax")


def test_pallas_interpret_default_env(monkeypatch):
    monkeypatch.setenv(bk.BACKEND_ENV, "pallas")
    assert bk.pallas_interpret_default() is False
    monkeypatch.setenv(bk.BACKEND_ENV, "pallas_interpret")
    assert bk.pallas_interpret_default() is True
    monkeypatch.delenv(bk.BACKEND_ENV)
    assert bk.pallas_interpret_default() == (not bk._on_tpu())


def test_resolve_model_respects_backend_caps():
    topo = T.one_cluster(4, 1)
    # oracle max_p is bounded
    big = T.one_cluster(300, 1)
    with pytest.raises(ValueError):
        resolve_model(big, "divisible", W_list=[100], lam_list=[1],
                      backend="oracle")
    # The backend must NOT change the resolved model: store/chunk keys are
    # derived from its canonical form, and cross-backend cache sharing
    # (and chunked-sweep resume across hosts) needs them backend-free.
    from repro.service.store import canonical_model
    ms = [resolve_model(topo, "divisible", W_list=[5000], lam_list=[3],
                        backend=b) for b in (None,) + BACKENDS]
    assert len({str(canonical_model(m)) for m in ms}) == 1


# ---------------------------------------------------------------------------
# Parity matrix: oracle == jax == pallas_interpret, bit-exact.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("strategy", [T.UNIFORM, T.LOCAL_FIRST,
                                      T.INV_DISTANCE, T.ROUND_ROBIN])
@pytest.mark.parametrize("mwt", [False, True])
def test_parity_matrix_divisible(strategy, mwt):
    topo = T.two_clusters(3, 9).with_strategy(strategy, remote_prob=0.2)
    rows = grid_rows([1500], [(1, 9)], 2, theta=((0, 0), (3, 1)))
    model = resolve_model(topo, "divisible", W_list=[1500], lam_list=[(1, 9)],
                          mwt=mwt)
    ref = run_rows(model, rows, remote_prob=0.2, backend="jax")
    assert not ref.overflow.any()
    for name in ("oracle", "pallas_interpret"):
        got = run_rows(model, rows, remote_prob=0.2, backend=name)
        assert_grids_equal(ref, got, msg=f"{name} strat={strategy} mwt={mwt}")


def test_parity_dag_and_adaptive():
    topo = T.two_clusters(3, 11).with_strategy(T.LOCAL_FIRST, remote_prob=0.3)
    rows = grid_rows([0], [(1, 11)], 2)
    dag_model = resolve_model(topo, "dag", dag=gen.merge_sort(300, 32),
                              max_events=1 << 16)
    ad_rows = grid_rows([900], [(1, 11)], 2)
    ad_model = resolve_model(topo, "adaptive", W_list=[900],
                             lam_list=[(1, 11)], merge_alpha=2,
                             merge_beta_num=1)
    for model, rws in ((dag_model, rows), (ad_model, ad_rows)):
        ref = run_rows(model, rws, remote_prob=0.3, backend="jax")
        for name in ("oracle", "pallas_interpret"):
            got = run_rows(model, rws, remote_prob=0.3, backend=name)
            assert_grids_equal(ref, got, msg=f"{type(model).__name__}/{name}")


def test_run_grid_backend_param():
    topo = T.one_cluster(4, 2)
    a = run_grid(topo, W_list=[800], lam_list=[2], reps=2, backend="jax")
    b = run_grid(topo, W_list=[800], lam_list=[2], reps=2, backend="oracle")
    assert_grids_equal(a, b)


def test_oracle_rejects_trace_models():
    topo = T.one_cluster(4, 1)
    model = resolve_model(topo, "divisible", W_list=[500], lam_list=[1],
                          log_trace=True, max_trace=64)
    with pytest.raises(ValueError):
        run_rows(model, grid_rows([500], [1], 1), backend="oracle")


def test_ws_sim_pallas_default_interpret_runs_on_cpu():
    """interpret=None resolves via the registry (no TPU here -> interpret),
    so the kernel is callable with no explicit flag on any host."""
    from repro.core import divisible as dv, engine as eng
    topo = T.one_cluster(4, 2)
    cfg = dv.EngineConfig(topology=topo, max_events=1 << 14)
    scn = eng.batch_scenarios(600, np.arange(2, dtype=np.uint32) + 1, lam=2)
    got = ws_sim_pallas(cfg, scn)
    expect = dv.simulate_batch(cfg, scn)
    np.testing.assert_array_equal(np.asarray(got.makespan),
                                  np.asarray(expect.makespan))


# ---------------------------------------------------------------------------
# Per-row event budgets: exact max_events relaxation/truncation.
# ---------------------------------------------------------------------------

def test_ev_budget_truncates_exactly_incl_overflow():
    topo = T.one_cluster(6, 30)
    rows = grid_rows([40_000], [30], 3)
    small = resolve_model(topo, "divisible", W_list=[40_000], lam_list=[30],
                          max_events=128)
    big = dataclasses.replace(
        small, cfg=dataclasses.replace(small.cfg, max_events=1 << 18))
    ref = run_rows(small, rows, backend="jax")
    assert ref.overflow.any()          # the small cap genuinely truncates
    for name in BACKENDS:
        got = run_rows(big, rows, backend=name, ev_budget=128)
        assert_grids_equal(ref, got, msg=name)


def test_broker_relaxation_coalesces_and_matches_unrelaxed(tmp_path):
    """Acceptance: a 2-query workload whose λ buckets have different
    max_events caps coalesces to 1 relaxed dispatch, with per-query results
    and stored artifacts byte-identical to each query answered alone at its
    own cap — including a query whose cap overflows."""
    kw = dict(W_list=[30_000], reps=3)
    mk = lambda svc: [
        svc.make_query(T.one_cluster(8, 1), lam_list=[2],
                       max_events=128, **kw),      # overflows at 128
        svc.make_query(T.one_cluster(8, 1), lam_list=[60],
                       max_events=1 << 15, **kw),
    ]
    svc_r = SimulationService(root=tmp_path / "relaxed")
    res_r = svc_r.query_many(mk(svc_r))
    assert svc_r.n_dispatches == 1
    assert svc_r.broker.dispatch_log[0]["relaxed"]
    assert svc_r.broker.dispatch_log[0]["n_queries"] == 2
    assert res_r[0].grid.overflow.any()

    for k, r in enumerate(res_r):
        # The unrelaxed reference: the query alone, at its own static cap.
        root_u = tmp_path / f"alone{k}"
        svc_u = SimulationService(root=root_u)
        q = mk(svc_u)[k]
        u = svc_u.query_many([q])[0]
        d = svc_u.broker.dispatch_log[0]
        assert svc_u.n_dispatches == 1 and not d["relaxed"]
        assert d["max_events"] == q.model.max_events
        assert r.key == u.key          # store keys unchanged by relaxation
        assert_grids_equal(r.grid, u.grid)
        art_r = (tmp_path / "relaxed" / f"{r.key}.npz").read_bytes()
        art_u = (root_u / f"{u.key}.npz").read_bytes()
        assert art_r == art_u          # byte-identical artifacts


def test_cross_backend_store_hit(tmp_path):
    """A cache fill from one backend serves every other: keys carry no
    backend component and artifacts are bit-identical."""
    root = tmp_path / "store"
    svc = SimulationService(root=root)
    topo = T.one_cluster(6, 1)
    kw = dict(W_list=[2000], lam_list=[3], reps=2)
    q_jax = svc.make_query(topo, backend="jax", **kw)
    q_pi = svc.make_query(topo, backend="pallas_interpret", **kw)
    q_orc = svc.make_query(topo, backend="oracle", **kw)
    assert q_jax.key() == q_pi.key() == q_orc.key()

    r1 = svc.query_many([q_jax])[0]
    assert not r1.from_cache and svc.n_dispatches == 1

    svc2 = SimulationService(root=root)    # fresh process-level tiers
    r2 = svc2.query_many([q_pi])[0]
    assert r2.from_cache and svc2.n_dispatches == 0
    assert_grids_equal(r1.grid, r2.grid)

    # And computing through different backends stores identical bytes.
    alt = SimulationService(root=tmp_path / "alt")
    r3 = alt.query_many([q_pi])[0]
    assert alt.broker.dispatch_log[0]["backend"] == "pallas_interpret"
    assert (root / f"{r1.key}.npz").read_bytes() == \
        (tmp_path / "alt" / f"{r3.key}.npz").read_bytes()


def test_backend_dispatch_log_and_mixed_backends(tmp_path):
    """Queries pinned to different backends never share a bucket; same
    backend still coalesces; an *identical* question on a different
    backend aliases (backend-free keys) instead of re-dispatching."""
    svc = SimulationService(root=tmp_path)
    topo = T.one_cluster(6, 1)
    mk = lambda backend, seed0: svc.make_query(
        topo, W_list=[1500], lam_list=[2], reps=2, seed0=seed0,
        backend=backend)
    # Distinct questions on jax/oracle/jax + q3 = q0's question on oracle.
    res = svc.query_many([mk("jax", 1), mk("oracle", 5), mk("jax", 9),
                          mk("oracle", 1)])
    assert svc.n_dispatches == 2       # {jax, jax} coalesce; oracle separate
    assert {d["backend"] for d in svc.broker.dispatch_log} == {"jax",
                                                               "oracle"}
    assert res[3].from_cache           # aliased onto q0 across backends
    assert_grids_equal(res[0].grid, res[3].grid)


# ---------------------------------------------------------------------------
# Cross-process in-flight dedup: advisory file locks.
# ---------------------------------------------------------------------------

def test_store_lock_primitives(tmp_path):
    store = ResultStore(root=tmp_path, lock_stale_s=0.2)
    assert store.try_lock("k")
    assert store.lock_held("k")
    assert not store.try_lock("k")     # second taker loses
    store.unlock("k")
    assert not store.lock_held("k")
    assert store.try_lock("k")
    time.sleep(0.25)                   # holder "died"; lock goes stale
    assert not store.lock_held("k")
    assert store.try_lock("k")         # stale lock is broken and re-taken
    store.unlock("k")


def test_flush_waits_for_other_process_and_serves_from_store(tmp_path):
    """Process B holds the key's lock; process A's flush polls the store,
    the answer lands, and A serves it with ZERO dispatches of its own."""
    root = tmp_path / "shared"
    warm = SimulationService(root=tmp_path / "warmup")
    topo = T.one_cluster(6, 1)
    kw = dict(W_list=[1200], lam_list=[4], reps=2)
    grid = warm.query(topo, **kw).grid   # the answer "B" will produce

    svc = SimulationService(root=root, lock_wait_s=10.0)
    q = svc.make_query(topo, **kw)
    key = q.key()
    other = ResultStore(root=root)       # "process B"
    assert other.try_lock(key)

    def b_finishes():
        time.sleep(0.3)
        other.put(key, grid)
        other.unlock(key)

    t = threading.Thread(target=b_finishes)
    t.start()
    res = svc.query_many([q])[0]
    t.join()
    assert res.from_cache
    assert svc.n_dispatches == 0
    assert svc.broker.n_lock_waits == 1 and svc.broker.n_lock_served == 1
    assert_grids_equal(res.grid, grid)


def test_flush_computes_after_lock_wait_timeout(tmp_path):
    """A lock whose holder never delivers only delays, never blocks: after
    lock_wait_s the flush computes the answer itself."""
    root = tmp_path / "shared"
    svc = SimulationService(root=root, lock_wait_s=0.2)
    svc.broker.lock_poll_s = 0.02
    topo = T.one_cluster(6, 1)
    q = svc.make_query(topo, W_list=[1200], lam_list=[4], reps=2)
    other = ResultStore(root=root)
    assert other.try_lock(q.key())       # dead holder, fresh lock
    res = svc.query_many([q])[0]
    assert not res.from_cache
    assert svc.n_dispatches == 1
    assert svc.broker.n_lock_waits == 1 and svc.broker.n_lock_served == 0


def test_lock_released_after_flush(tmp_path):
    svc = SimulationService(root=tmp_path)
    q = svc.make_query(T.one_cluster(4, 1), W_list=[600], lam_list=[2],
                       reps=2)
    svc.query_many([q])
    assert not svc.store.lock_held(q.key())
    assert not list(tmp_path.glob("*.lock"))
