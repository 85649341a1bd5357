"""Segmented execution layer (DESIGN.md §8): bit-exactness of the
segmented driver vs the monolithic while_loop across task models /
victim-selection strategies / SWT-MWT, per-row budget overflow, active-lane
compaction telemetry, multi-device row sharding, small batches on the
selected backend, straggler-aware dispatch ordering, and the persistent
compile cache."""
import dataclasses
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core import backend as bk
from repro.core import dag_gen as gen
from repro.core import divisible as dv
from repro.core import engine as eng
from repro.core import topology as T
from repro.core.sweep import (grid_rows, resolve_model, run_rows,
                              scenario_from_rows)
from repro.service import SimulationService
from repro.service.broker import EventHistory, _rows_cols
from repro.service.store import ResultStore, canonical_model


def assert_trees_equal(a, b, msg=""):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb), msg
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=msg)


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
# Segment sizing + capability surface.
# ---------------------------------------------------------------------------

def test_default_segment_len_bounds():
    assert eng.default_segment_len(1 << 20) == 128   # clamp high
    assert eng.default_segment_len(8) == 32          # clamp low
    assert eng.default_segment_len(48) == 64         # pow2 ceil
    # A finite per-row budget tightens the segment; zero budgets are pads.
    assert eng.default_segment_len(1 << 20, ev_budget=[64, 0]) == 64
    assert eng.default_segment_len(1 << 20, ev_budget=[1 << 20]) == 128


def test_capability_fields():
    jb = bk.get_backend("jax").capabilities()
    assert jb.n_devices >= 1
    assert jb.segment_len == 128
    ob = bk.get_backend("oracle").capabilities()
    assert ob.n_devices == 1
    assert bk.get_backend("oracle").local_devices() == ()
    assert bk.get_backend("pallas_interpret").grid_chunk is None


def test_device_chunks_layout():
    be = bk.get_backend("jax")
    # 3 fake devices, 20 rows, min 8 rows/device -> only 2 worth using.
    chunks = be._device_chunks(20, ["d0", "d1", "d2"])
    assert [c[:2] for c in chunks] == [(0, 10), (10, 20)]
    assert [c[2] for c in chunks] == ["d0", "d1"]
    # Tiny batch: never split below min_rows_per_device.
    assert be._device_chunks(7, ["d0", "d1"]) == [(0, 7, "d0")]
    # No devices at all (oracle / interpret): one host-side chunk.
    assert be._device_chunks(100, ()) == [(0, 100, None)]


# ---------------------------------------------------------------------------
# Bit-exactness: segmented driver == monolithic while_loop.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("strategy", [T.UNIFORM, T.LOCAL_FIRST,
                                      T.INV_DISTANCE, T.ROUND_ROBIN])
@pytest.mark.parametrize("mwt", [False, True])
def test_segmented_parity_divisible(strategy, mwt):
    topo = T.two_clusters(3, 9).with_strategy(strategy, remote_prob=0.2)
    rows = grid_rows([1500], [(1, 9)], 2, theta=((0, 0), (3, 1)))
    model = resolve_model(topo, "divisible", W_list=[1500],
                          lam_list=[(1, 9)], mwt=mwt)
    scn = scenario_from_rows(rows, remote_prob=0.2)
    ref = eng.simulate_batch(model, scn)
    got, stats = eng.simulate_segmented(model, scn, seg_len=16)
    assert_trees_equal(ref, got, msg=f"strat={strategy} mwt={mwt}")
    # Every useful lane-iteration is one executed event, no more, no less.
    assert stats.n_segments >= 1
    assert stats.events_executed == int(np.asarray(ref.n_events).sum())


def test_segmented_parity_dag_and_adaptive():
    topo = T.two_clusters(3, 11).with_strategy(T.LOCAL_FIRST, remote_prob=0.3)
    dag_model = resolve_model(topo, "dag", dag=gen.merge_sort(300, 32),
                              max_events=1 << 16)
    ad_model = resolve_model(topo, "adaptive", W_list=[900],
                             lam_list=[(1, 11)], merge_alpha=2,
                             merge_beta_num=1)
    for model, rows in ((dag_model, grid_rows([0], [(1, 11)], 2)),
                        (ad_model, grid_rows([900], [(1, 11)], 2))):
        scn = scenario_from_rows(rows, remote_prob=0.3)
        ref = eng.simulate_batch(model, scn)
        got, _ = eng.simulate_segmented(model, scn, seg_len=32)
        assert_trees_equal(ref, got, msg=type(model).__name__)


def test_segmented_ev_budget_overflow_parity():
    topo = T.one_cluster(6, 30)
    rows = grid_rows([40_000], [30], 4)
    model = resolve_model(topo, "divisible", W_list=[40_000], lam_list=[30],
                          max_events=1 << 18)
    # Uniform tight budget: every row truncates at exactly 128 events.
    scn = scenario_from_rows(rows, ev_budget=128)
    ref = eng.simulate_batch(model, scn)
    assert np.asarray(ref.overflow).any()
    got, _ = eng.simulate_segmented(model, scn, seg_len=32)
    assert_trees_equal(ref, got, msg="uniform budget")
    # Mixed budgets: truncated and full rows interleaved in one batch.
    mixed = np.array([128, 1 << 18, 128, 1 << 18], np.int64)
    scn_m = scenario_from_rows(rows, ev_budget=mixed)
    ref_m = eng.simulate_batch(model, scn_m)
    assert np.asarray(ref_m.overflow).any()
    assert not np.asarray(ref_m.overflow).all()
    got_m, _ = eng.simulate_segmented(model, scn_m, seg_len=32)
    assert_trees_equal(ref_m, got_m, msg="mixed budgets")


def test_compaction_down_to_single_lane():
    """15 budget-capped rows + 1 long straggler: the batch must compact to
    width 1 and waste fewer lane-cycles than the convoyed vmap."""
    topo = T.one_cluster(4, 2)
    model = resolve_model(topo, "divisible", W_list=[300], lam_list=[2],
                          max_events=1 << 14)
    rows = grid_rows([300], [2], 16)
    budgets = np.full(16, 64, np.int64)  # short rows truncate at 64 events
    budgets[0] = 1 << 14                 # the straggler runs to completion
    scn = scenario_from_rows(rows, ev_budget=budgets)
    W = np.asarray(scn.W).copy()
    W[0] = 10_000_000                    # ~170 events vs ~40-77
    scn = scn._replace(W=W)
    ref = eng.simulate_batch(model, scn)
    assert np.asarray(ref.overflow).any()       # some rows hit the budget
    assert not np.asarray(ref.overflow)[0]      # the straggler does not
    got, stats = eng.simulate_segmented(model, scn, seg_len=64)
    assert_trees_equal(ref, got)
    assert stats.n_compactions >= 1
    assert stats.max_width == 16
    assert stats.final_width == 1
    ev = np.asarray(ref.n_events, np.float64)
    convoy = 1.0 - ev.sum() / (len(ev) * ev.max())
    assert 0.0 < stats.wasted_frac < convoy


def test_seg_len_env_override_and_stats(monkeypatch):
    be = bk.get_backend("jax")
    topo = T.one_cluster(4, 2)
    model = resolve_model(topo, "divisible", W_list=[900], lam_list=[2])
    rows = grid_rows([900], [2], 48)         # >= seg_min_rows
    monkeypatch.setenv(bk.SEG_LEN_ENV, "0")  # env kill-switch
    be.last_stats = None
    a = run_rows(model, rows, backend="jax")
    assert be.last_stats is None             # monolithic path ran
    monkeypatch.setenv(bk.SEG_LEN_ENV, "64")
    b = run_rows(model, rows, backend="jax")
    st = be.last_stats
    assert st is not None and st.n_segments >= 1
    assert 0 < st.events_executed <= st.lane_cycles
    assert 0.0 <= st.wasted_frac < 1.0
    monkeypatch.delenv(bk.SEG_LEN_ENV)
    c = run_rows(model, rows, backend="jax")  # default: segmented at n=48
    assert_grids_equal(a, b, msg="env=64")
    assert_grids_equal(a, c, msg="default seg")


def test_pallas_grid_chunk_parity():
    from repro.kernels.ws_sim import ws_sim_pallas
    topo = T.one_cluster(4, 2)
    cfg = dv.EngineConfig(topology=topo, max_events=1 << 14)
    scn = eng.batch_scenarios(600, np.arange(6, dtype=np.uint32) + 1, lam=2)
    ref = ws_sim_pallas(cfg, scn, interpret=True)
    # 6 rows at chunk 4: two chunks, the second padded 2 -> 4.
    got = ws_sim_pallas(cfg, scn, interpret=True, grid_chunk=4)
    assert_trees_equal(ref, got, msg="chunk=4")
    # Chunk larger than the grid: a single padded call.
    got8 = ws_sim_pallas(cfg, scn, interpret=True, grid_chunk=8)
    assert_trees_equal(ref, got8, msg="chunk=8")


# ---------------------------------------------------------------------------
# Multi-device row sharding (forced 4-device CPU host in a subprocess).
# ---------------------------------------------------------------------------

MULTIDEV_SCRIPT = """
import dataclasses
import sys
import numpy as np
import jax

assert jax.device_count() == 4, jax.devices()
from repro.core import backend as bk
from repro.core import dag_gen as gen
from repro.core import topology as T
from repro.core.sweep import grid_rows, resolve_model, run_rows

be = bk.get_backend("jax")
assert be.capabilities().n_devices == 4
chunks = be._device_chunks(32, None)
assert [c[:2] for c in chunks] == [(0, 8), (8, 16), (16, 24), (24, 32)]
assert len({c[2] for c in chunks}) == 4

topo = T.one_cluster(4, 2)
task_model = sys.argv[1]
if task_model == "dag":
    model = resolve_model(topo, "dag", dag=gen.merge_sort(200, 32),
                          lam_list=[2])
    rows = grid_rows([0], [2], 32)
else:
    model = resolve_model(topo, task_model, W_list=[800], lam_list=[2])
    rows = grid_rows([800], [2], 32)
ref = run_rows(model, rows, backend="jax", devices=[jax.local_devices()[0]])
got = run_rows(model, rows, backend="jax")   # every device by default
for f in dataclasses.fields(ref):
    a, b = getattr(ref, f.name), getattr(got, f.name)
    if f.name == "extras":
        for k in a:
            np.testing.assert_array_equal(np.asarray(a[k]),
                                          np.asarray(b[k]), err_msg=k)
    else:
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f.name)
assert not ref.overflow.any()
assert be.last_stats is not None and be.last_stats.n_segments >= 4
print("MULTIDEV_OK")
"""


@pytest.mark.parametrize("task_model", ["divisible", "dag", "adaptive"])
def test_run_rows_shards_across_forced_host_devices(tmp_path, task_model):
    import repro
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4").strip()
    env["JAX_PLATFORMS"] = "cpu"
    src = str(Path(list(repro.__path__)[0]).resolve().parent)
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    script = tmp_path / "multidev.py"
    script.write_text(MULTIDEV_SCRIPT)
    proc = subprocess.run([sys.executable, str(script), task_model], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "MULTIDEV_OK" in proc.stdout


NO_CROSSING_SCRIPT = r"""
import jax, numpy as np
from repro import obs
from repro.core import backend as bk
from repro.core import topology as T
from repro.core.sweep import (grid_from_result, grid_rows, resolve_model,
                              run_rows, scenario_from_rows)
from repro.kernels.ws_sim import ws_sim_pallas

devs = jax.local_devices()
model = resolve_model(T.one_cluster(4, 2), "divisible", W_list=[800],
                      lam_list=[2])
rows = grid_rows([800], [2, 5], 15)                 # 30: not a multiple of 4
ref = run_rows(model, rows, backend="jax", devices=devs[:1])


def same(g):
    return all(np.array_equal(np.asarray(getattr(g, c)),
                              np.asarray(getattr(ref, c)))
               for c in ("makespan", "n_requests", "total_idle", "overflow"))


def device_rows(backend):
    c = obs.REGISTRY.snapshot()["counters"]
    return [c.get(f"backend.device_rows{{backend={backend},device={d.id}}}",
                  0) for d in devs]


# A chunk bound for device k is placed from the host and never staged on,
# or copied from, another device.
with jax.transfer_guard_device_to_device("disallow_explicit"):
    assert same(run_rows(model, rows, backend="jax"))
    scn = scenario_from_rows(rows, device=devs[2])
    out = ws_sim_pallas(model, scn, interpret=True, grid_chunk=8)
    assert out.makespan.devices() == {devs[2]}
    assert same(grid_from_result(model.p, rows, jax.device_get(out)))
# The reference's 30 rows on device 0, then three 10-row chunks (at least
# eight rows a device) on devices 0-2.
assert device_rows("jax") == [40, 10, 10, 0], device_rows("jax")
print("NO_CROSSING_OK")
"""


def test_sharded_chunks_never_cross_devices(tmp_path):
    import repro
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=4").strip()
    env["JAX_PLATFORMS"] = "cpu"
    src = str(Path(list(repro.__path__)[0]).resolve().parent)
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    script = tmp_path / "no_crossing.py"
    script.write_text(NO_CROSSING_SCRIPT)
    proc = subprocess.run([sys.executable, str(script)], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "NO_CROSSING_OK" in proc.stdout


# ---------------------------------------------------------------------------
# Small batches run on the selected backend.
# ---------------------------------------------------------------------------

def test_small_batch_runs_on_selected_backend(monkeypatch):
    monkeypatch.setenv(bk.BACKEND_ENV, "jax")  # deterministic auto-detect
    topo = T.one_cluster(4, 2)
    model = resolve_model(topo, "divisible", W_list=[500], lam_list=[2])
    orc_be, jax_be = bk.get_backend("oracle"), bk.get_backend("jax")
    for n in (2, 8):
        rows = grid_rows([500], [2], n)
        o0, j0 = orc_be.n_run_rows, jax_be.n_run_rows
        got = run_rows(model, rows)            # auto-selected backend
        assert (orc_be.n_run_rows, jax_be.n_run_rows) == (o0, j0 + 1)
        ref = run_rows(model, rows, backend="oracle")
        assert_grids_equal(ref, got, msg=f"{n} rows")
    # A trace-logging model, which the oracle cannot run, runs there too.
    trace = resolve_model(topo, "divisible", W_list=[500], lam_list=[2],
                          log_trace=True, max_trace=64)
    rows = grid_rows([500], [2], 2)
    o0, j0 = orc_be.n_run_rows, jax_be.n_run_rows
    got = run_rows(trace, rows)
    assert (orc_be.n_run_rows, jax_be.n_run_rows) == (o0, j0 + 1)
    assert_grids_equal(run_rows(trace, rows, backend="jax"), got,
                       msg="log_trace")


# ---------------------------------------------------------------------------
# Straggler-aware dispatch ordering.
# ---------------------------------------------------------------------------

def test_event_history_ema_overrides_heuristic():
    rows = grid_rows([1000, 2000], [3], 1)
    cols = _rows_cols(rows)
    h = EventHistory()
    base = h.predict("sig", 8, cols)
    assert base.shape == (2,) and (base > 0).all()
    h.observe("sig", cols[:1], [12_345.0])     # first observation: taken
    assert len(h) == 1
    got = h.predict("sig", 8, cols)
    assert got[0] == 12_345.0
    assert got[1] == base[1]                   # unobserved cell: heuristic
    h.observe("sig", cols[:1], [0.0])          # EMA with alpha=0.5
    assert h.predict("sig", 8, cols)[0] == pytest.approx(6_172.5)
    # Different signature: a fresh slate.
    assert h.predict("other", 8, cols)[0] == base[0]


def test_straggler_sort_orders_dispatch_bitexact(tmp_path):
    # W descending in the grid -> expected-events descending -> the sort
    # must actually permute; results and artifacts stay byte-identical.
    kw = dict(W_list=[40_000, 500], lam_list=[2], reps=2,
              max_events=1 << 15)
    svc = SimulationService(root=tmp_path / "sorted")
    q = svc.make_query(T.one_cluster(6, 1), **kw)
    r = svc.query_many([q])[0]
    d = svc.broker.dispatch_log[0]
    assert d["sorted"] is True
    assert len(svc.broker.history) > 0         # fed back after dispatch

    # The unsorted reference: the query's rows in grid order, run directly.
    ref = run_rows(q.model, grid_rows(q.W_list, q.lam_list, q.reps,
                                      q.theta, seed0=q.seed0),
                   remote_prob=q.remote_prob)
    assert_grids_equal(ref, r.grid, msg="sorted vs grid order")
    ResultStore(root=tmp_path / "plain").put(
        r.key, ref, meta={"grid": q.grid_dict(),
                          "model": canonical_model(q.model)})
    art_a = (tmp_path / "sorted" / f"{r.key}.npz").read_bytes()
    art_b = (tmp_path / "plain" / f"{r.key}.npz").read_bytes()
    assert art_a == art_b

    # A cache hit still teaches the history (no dispatch needed).
    svc2 = SimulationService(root=tmp_path / "sorted")
    r2 = svc2.query(T.one_cluster(6, 1), **kw)
    assert r2.from_cache and svc2.n_dispatches == 0
    assert len(svc2.broker.history) > 0


# ---------------------------------------------------------------------------
# Persistent compile cache: JAX_COMPILATION_CACHE_DIR, else artifacts/.
# ---------------------------------------------------------------------------

def test_compile_cache_opt_in(tmp_path, monkeypatch):
    from jax.experimental.compilation_cache import compilation_cache as cc
    monkeypatch.delenv(bk.JAX_CACHE_ENV, raising=False)
    prev = jax.config.jax_compilation_cache_dir
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        svc0 = SimulationService(root=tmp_path / "s0")
        assert svc0.compile_cache_dir is None          # default: off
        assert svc0.stats()["compile_cache"] is None

        # Turned on without the variable: the fixed checkout directory.
        assert bk.enable_compile_cache() == bk.default_jit_cache_dir()
        assert jax.config.jax_compilation_cache_dir == str(
            bk.default_jit_cache_dir())

        # With the variable set (JAX reads it at start-up), the program
        # sets no other directory and the cache is written there alone.
        env_dir = tmp_path / "env_jit"
        monkeypatch.setenv(bk.JAX_CACHE_ENV, str(env_dir))
        jax.config.update("jax_compilation_cache_dir", str(env_dir))
        cc.reset_cache()
        svc = SimulationService(root=tmp_path / "s1", compile_cache=True)
        assert svc.compile_cache_dir == env_dir and env_dir.is_dir()
        assert jax.config.jax_compilation_cache_dir == str(env_dir)
        r = svc.query(T.one_cluster(4, 1), W_list=[500], lam_list=[2],
                      reps=16)
        assert not r.grid.overflow.any()
        assert any(env_dir.iterdir())
        st = svc.stats()
        assert st["compile_cache"] == str(env_dir)
        assert st["n_devices"] >= 1 and "n_history_cells" in st
        assert SimulationService(
            root=tmp_path / "s2").compile_cache_dir == env_dir
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)
        cc.reset_cache()
