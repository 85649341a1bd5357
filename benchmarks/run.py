"""Benchmark harness — one function per paper table/figure + framework perf.

Prints ``name,us_per_call,derived`` CSV rows (plus per-figure CSV files under
artifacts/bench/). Figures:

  fig10_overhead_ratio   paper §4.1: bound/simulated overhead, 4-5.5x
  fig11_accept_latency   paper §4.2: W/p ≈ 470·λ law
  fig12_mwt_swt          paper §4.3: MWT startup vs overall effect
  sim_throughput         simulator speed: events/second (divisible engine)
  model_throughput       scenarios/sec + events/sec for ALL task models
                         (divisible, dag, adaptive) through the unified core
  sched_planner          planner decision quality on a 2-pod fleet
  service_throughput     sweep service: cold vs warm queries/sec, broker
                         coalescing batch sizes, adaptive-vs-fixed-reps
                         replication savings at equal CI width
  paired_comparison      paired CRN A/B queries vs independent arms:
                         reps-to-significance for a small policy gap
  backend_matrix         the same grid on every available execution backend
                         (oracle / jax / pallas / pallas_interpret): rows/s
                         + bit-parity columns, emitted as
                         artifacts/bench/BENCH_backends.json
  obs_overhead           a traced cold + warm service query: cache-hit
                         ratio, emitted as artifacts/bench/BENCH_obs.json
                         (+ obs_trace/ / obs_metrics.json CI artifacts)
  fault_recovery         p50/p99 query latency at 0/5/20% injected backend
                         failure rate (retry + bisection salvage + fallback
                         chain), emitted as artifacts/bench/BENCH_fault.json
  daemon_throughput      N client processes × M queries: warm shared daemon
                         vs cold per-process library mode (q/s, dispatches,
                         p50/p99), emitted as artifacts/bench/BENCH_daemon.json
  roofline               per-(arch×shape) terms from the dry-run artifacts

Reduced repetition counts (CI-friendly); pass --full for paper-scale reps.
"""
from __future__ import annotations

import argparse
import json
import os
import time
from pathlib import Path

import numpy as np

from repro.core import analysis, one_cluster
from repro.core import divisible as dv

ART = Path(__file__).resolve().parents[1] / "artifacts"
BENCH = ART / "bench"


def _row(name: str, us: float, derived: str):
    print(f"{name},{us:.1f},{derived}")


def fig10_overhead_ratio(reps: int):
    rows = []
    t0 = time.time()
    for p in (32, 64, 128):
        topo = one_cluster(p, 1)
        for W in (10**5, 10**6, 10**7):
            for lam in (2, 62, 262, 482):
                cfg = dv.EngineConfig(
                    topology=topo,
                    max_events=dv.default_max_events(W, p, lam))
                scn = dv.batch_scenarios(
                    W, np.arange(reps, dtype=np.uint32) + 1, lam=lam)
                res = dv.simulate_batch(cfg, scn)
                ms = np.asarray(res.makespan)
                r = analysis.summarize(analysis.overhead_ratio(ms, W, p, lam))
                c = analysis.summarize(analysis.fitted_constant(ms, W, p, lam))
                rows.append(dict(p=p, W=W, lam=lam, ratio_med=r["median"],
                                 ratio_q1=r["q1"], ratio_q3=r["q3"],
                                 fit_med=c["median"]))
    us = (time.time() - t0) * 1e6 / len(rows)
    med = float(np.median([r["ratio_med"] for r in rows]))
    fit = float(np.median([r["fit_med"] for r in rows]))
    _write_csv("fig10_overhead_ratio", rows)
    _row("fig10_overhead_ratio", us,
         f"median_ratio={med:.2f} (paper 4-5.5); fit_c={fit:.2f} (paper 3.8)")


def fig11_accept_latency(reps: int):
    rows = []
    t0 = time.time()
    for p in (32, 64):
        topo = one_cluster(p, 1)
        for W in (10**5, 10**6, 10**7):
            lam_th = analysis.theoretical_limit_latency(W, p)
            by_lam = {}
            for lam in np.unique(np.linspace(max(lam_th * 0.4, 1),
                                             lam_th * 2.2, 8).astype(int)):
                cfg = dv.EngineConfig(
                    topology=topo,
                    max_events=dv.default_max_events(W, p, int(lam)))
                scn = dv.batch_scenarios(
                    W, np.arange(reps, dtype=np.uint32) + 3, lam=int(lam))
                by_lam[int(lam)] = np.asarray(
                    dv.simulate_batch(cfg, scn).makespan)
            lam_exp = analysis.experimental_limit_latency(by_lam, W, p)
            rows.append(dict(p=p, W=W, lam_theory=lam_th, lam_exp=lam_exp,
                             ratio=(W / p) / max(lam_exp, 1)))
    us = (time.time() - t0) * 1e6 / len(rows)
    med = float(np.median([r["ratio"] for r in rows]))
    _write_csv("fig11_accept_latency", rows)
    _row("fig11_accept_latency", us, f"(W/p)/lam*={med:.0f} (paper ~470)")


def fig12_mwt_swt(reps: int, full: bool):
    rows = []
    W = 10**8 if full else 10**6
    lam = 262
    t0 = time.time()
    for p in (16, 32, 64, 128):
        topo = one_cluster(p, lam)
        out = {}
        for mwt in (False, True):
            cfg = dv.EngineConfig(
                topology=topo, mwt=mwt,
                max_events=dv.default_max_events(W, p, lam))
            scn = dv.batch_scenarios(W, np.arange(reps, dtype=np.uint32) + 5,
                                     lam=lam)
            res = dv.simulate_batch(cfg, scn)
            out[mwt] = (np.asarray(res.makespan), np.asarray(res.startup_end))
        su = float(np.median(out[False][1]) / np.median(out[True][1]))
        ov = float(np.median(out[False][0]) / np.median(out[True][0]))
        rows.append(dict(p=p, W=W, lam=lam, startup_speedup=su,
                         overall_speedup=ov))
    us = (time.time() - t0) * 1e6 / len(rows)
    _write_csv("fig12_mwt_swt", rows)
    best = max(r["startup_speedup"] for r in rows)
    flat = float(np.median([r["overall_speedup"] for r in rows]))
    _row("fig12_mwt_swt", us,
         f"startup_speedup<= x{best:.2f}; overall x{flat:.2f} (paper: flat)")


def steal_threshold(reps: int):
    """Paper §2.4.2 / Fig 3: a communication-dependent steal threshold
    prevents 'artificial idle times' at high latency. Quantifies the effect
    the paper only illustrates."""
    rows = []
    W = 10**6
    t0 = time.time()
    for p, lam in ((8, 482), (32, 262), (64, 482), (128, 262)):
        topo = one_cluster(p, lam)
        out = {}
        for tc in (0, 1, 2, 4):
            cfg = dv.EngineConfig(
                topology=topo, max_events=dv.default_max_events(W, p, lam))
            scn = dv.batch_scenarios(W, np.arange(reps, dtype=np.uint32) + 1,
                                     lam=lam, theta_comm=tc)
            out[tc] = float(np.median(
                np.asarray(dv.simulate_batch(cfg, scn).makespan)))
        best_tc = min(out, key=out.get)
        rows.append(dict(p=p, lam=lam, base=out[0], best_theta_comm=best_tc,
                         gain=out[0] / out[best_tc],
                         **{f"ms_tc{t}": out[t] for t in out}))
    us = (time.time() - t0) * 1e6 / len(rows)
    _write_csv("steal_threshold", rows)
    med = float(np.median([r["gain"] for r in rows]))
    _row("steal_threshold", us,
         f"comm-scaled threshold gains x{med:.3f} median at high lambda "
         f"(paper Fig 3: prevents artificial idle times)")


def multicluster(reps: int):
    """Beyond-paper: the analysis the simulator was BUILT for (paper §1.1) —
    WS overhead across multi-cluster topologies × victim strategies. The
    paper presents the tool; this produces its target science: locality-aware
    stealing (LOCAL_FIRST) vs uniform across cluster counts/topologies."""
    from repro.core import topology as T
    from repro.configs.ws_paper import MULTICLUSTER_SCENARIOS
    rows = []
    W = 10**6
    t0 = time.time()
    for (k, m, lam_r, inter) in MULTICLUSTER_SCENARIOS:
        p = k * m
        for strat, rp in ((T.UNIFORM, 0.25), (T.LOCAL_FIRST, 0.1)):
            topo = (T.multi_cluster(k, m, lam_r, inter=inter)
                    .with_strategy(strat, remote_prob=rp))
            cfg = dv.EngineConfig(
                topology=topo,
                max_events=dv.default_max_events(W, p, lam_r))
            scn = dv.batch_scenarios(W, np.arange(reps, dtype=np.uint32) + 7,
                                     lam_local=1, lam_remote=lam_r,
                                     remote_prob=rp)
            res = dv.simulate_batch(cfg, scn)
            med = float(np.median(np.asarray(res.makespan)))
            rows.append(dict(clusters=k, per_cluster=m, lam_remote=lam_r,
                             inter=inter, strategy=T.strategy_name(strat),
                             median_makespan=med,
                             overhead=med - W / p,
                             fail_frac=float(np.mean(
                                 np.asarray(res.n_fail)
                                 / np.maximum(np.asarray(res.n_requests), 1)))))
    us = (time.time() - t0) * 1e6 / len(rows)
    _write_csv("multicluster", rows)
    # locality gain: median over scenarios of uniform/local_first overhead
    gains = []
    for i in range(0, len(rows), 2):
        gains.append(rows[i]["overhead"] / max(rows[i + 1]["overhead"], 1))
    _row("multicluster", us,
         f"local_first cuts WS overhead x{float(np.median(gains)):.2f} "
         f"(median over {len(gains)} fleet topologies)")


def sim_throughput(reps: int):
    """Events/second of the vmapped engine (the simulator's own perf)."""
    p, W, lam = 64, 10**6, 50
    topo = one_cluster(p, lam)
    cfg = dv.EngineConfig(topology=topo,
                          max_events=dv.default_max_events(W, p, lam))
    scn = dv.batch_scenarios(W, np.arange(reps, dtype=np.uint32) + 1, lam=lam)
    res = dv.simulate_batch(cfg, scn)          # compile + warm
    res.makespan.block_until_ready()
    t0 = time.time()
    res = dv.simulate_batch(cfg, scn)
    res.makespan.block_until_ready()
    dt = time.time() - t0
    ev = int(np.asarray(res.n_events).sum())
    _row("sim_throughput", dt * 1e6 / reps,
         f"{ev / dt:,.0f} events/s over {reps} parallel sims (p={p})")


def model_throughput(reps: int):
    """Scenarios/sec and events/sec per task model through the unified
    engine — the perf trajectory now covers more than the divisible hot
    path (DESIGN.md §2)."""
    from repro.core import engine as eng
    from repro.core import dag_gen as gen
    from repro.core.sweep import make_model

    p = 32
    topo = one_cluster(p, 10)
    W = 200_000
    models = {
        "divisible": make_model(
            "divisible", topology=topo,
            max_events=dv.default_max_events(W, p, 10)),
        "dag": make_model(
            "dag", topology=topo, dag=gen.merge_sort(20_000, 64),
            max_events=1 << 20),
        "adaptive": make_model(
            "adaptive", topology=topo, pool_cap=1 << 13,
            max_events=dv.default_max_events(W, p, 10)),
    }
    rows = []
    for name, model in models.items():
        scn = eng.batch_scenarios(W, np.arange(reps, dtype=np.uint32) + 1,
                                  lam=10)
        res = eng.simulate_batch(model, scn)          # compile + warm
        res.makespan.block_until_ready()
        t0 = time.time()
        res = eng.simulate_batch(model, scn)
        res.makespan.block_until_ready()
        dt = time.time() - t0
        ev = int(np.asarray(res.n_events).sum())
        rows.append(dict(model=name, scn_per_s=reps / dt,
                         events_per_s=ev / dt, us_per_scn=dt * 1e6 / reps))
        _row(f"model_throughput_{name}", dt * 1e6 / reps,
             f"{reps / dt:,.1f} scn/s; {ev / dt:,.0f} events/s (p={p})")
    _write_csv("model_throughput", rows)


def sched_planner(reps: int):
    from repro.sched.planner import plan_for_mesh
    t0 = time.time()
    dec = plan_for_mesh(n_pods=2, chips_per_pod=32, dcn_delay=100,
                        work_per_group=4096, reps=min(reps, 12))
    us = (time.time() - t0) * 1e6
    gain = dec.baseline_makespan / max(dec.expected_makespan, 1)
    _row("sched_planner", us,
         f"policy={dec.strategy_name}/theta=({dec.theta_static}"
         f";{dec.theta_comm})/mwt={dec.mwt}; x{gain:.2f} vs uniform")


def service_throughput(reps: int):
    """The caching/coalescing/adaptive wins of the sweep service
    (DESIGN.md §5), measured:

    * cold vs warm: the same batch of queries against an empty store and
      again against the populated one (warm answers touch no simulator);
    * coalescing: concurrent queries per dispatched device program;
    * adaptive savings: replications the adaptive estimator spent to reach
      a CI target vs what a fixed-reps sweep needs for the same width
      (n_fixed = ceil((z·sigma/h)²) per cell, from the measured variance).
    """
    import shutil
    import tempfile
    from repro.core import one_cluster
    from repro.service import SimulationService
    from repro.service.estimator import fixed_reps_for_width

    p, W = 32, 200_000
    lams = (2, 10, 30, 50)
    rows = []

    tmp = tempfile.mkdtemp(prefix="bench_store_")
    svc = SimulationService(root=tmp)
    # Concurrent queries over different θ thresholds share one task-model
    # bucket (θ is a traced scenario field), so the broker coalesces them
    # into a single device program — the planner's access pattern.
    thetas = ((0, 0), (0, 2), (8, 0), (16, 2))
    def make():
        return [svc.make_query(one_cluster(p, 1), W_list=[W],
                               lam_list=list(lams), theta=(th,),
                               reps=reps, seed0=11)
                for th in thetas]
    t0 = time.time()
    svc.query_many(make())                      # compile + simulate
    cold_s = time.time() - t0
    d_cold = svc.n_dispatches
    t0 = time.time()
    warm_res = svc.query_many(make())
    warm_s = time.time() - t0
    d_warm = svc.n_dispatches - d_cold
    assert all(r.from_cache for r in warm_res) and d_warm == 0
    sizes = [d["n_queries"] for d in svc.broker.dispatch_log]
    coalesce = sum(sizes) / max(len(sizes), 1)

    # adaptive vs fixed at the width the adaptive run achieved
    tgt_rel = 0.01
    t0 = time.time()
    ares = svc.query(one_cluster(p, 1), W_list=[W], lam_list=list(lams),
                     ci=tgt_rel, ci_relative=True, batch_reps=8,
                     max_reps=64 * max(reps, 16), seed0=23)
    adapt_s = time.time() - t0
    cells = ares.cells
    n_adapt = int(cells.n.sum())
    n_fixed_per_cell = max(
        fixed_reps_for_width(float(cells.std[c]),
                             tgt_rel * float(cells.mean[c]))
        for c in range(len(cells)))
    n_fixed = n_fixed_per_cell * len(cells)     # fixed reps are uniform
    rows.append(dict(
        n_queries=len(thetas), cold_s=round(cold_s, 4),
        warm_s=round(warm_s, 4),
        cold_qps=round(len(thetas) / cold_s, 2),
        warm_qps=round(len(thetas) / warm_s, 2),
        speedup=round(cold_s / max(warm_s, 1e-9), 1),
        dispatches_cold=d_cold, dispatches_warm=d_warm,
        mean_queries_per_dispatch=round(coalesce, 2),
        adaptive_reps=n_adapt, fixed_reps_equiv=n_fixed,
        rep_savings=round(n_fixed / max(n_adapt, 1), 2),
        adaptive_s=round(adapt_s, 4), ci_rel_target=tgt_rel))
    _write_csv("service_throughput", rows)
    r = rows[0]
    _row("service_throughput", warm_s * 1e6 / len(thetas),
         f"warm x{r['speedup']} vs cold ({r['warm_qps']:,.0f} vs "
         f"{r['cold_qps']:.1f} q/s); {r['mean_queries_per_dispatch']} "
         f"queries/dispatch; adaptive {n_adapt} reps vs fixed {n_fixed} "
         f"for ±{tgt_rel:.0%} CI (x{r['rep_savings']} fewer)")
    shutil.rmtree(tmp, ignore_errors=True)


def paired_comparison(reps: int):
    """Paired (common-random-numbers) vs independent A/B policy queries:
    replications needed for a *significant* verdict on a small policy gap.

    The paired estimator replicates until the CI on the per-seed makespan
    difference excludes zero; the independent-arms baseline needs
    n >= (z·sqrt(var_A + var_B)/|delta|)² pairs for the same verdict
    (computed from the measured per-arm variances). CRN cancels the shared
    Monte-Carlo noise, so paired reaches significance with far fewer reps —
    which is what makes small policy gaps (e.g. localized stealing, MWT)
    resolvable inside a planning budget.
    """
    import shutil
    import tempfile
    from repro.core import one_cluster
    from repro.service import PairedPolicy, SimulationService
    from repro.service.estimator import z_value

    p, W, lam = 32, 10**6, 262
    tmp = tempfile.mkdtemp(prefix="bench_paired_")
    svc = SimulationService(root=tmp)
    topo = one_cluster(p, lam)
    rows = []
    t0 = time.time()
    # Two A/B gaps of different sizes: SWT vs MWT (small), θ_comm 0 vs 2
    # (latency-dependent).
    arms = {
        "swt_vs_mwt": (dict(mwt=False), dict(mwt=True)),
        "theta0_vs_theta2": (dict(theta=((0, 0),)), dict(theta=((0, 2),))),
    }
    for name, (kw_a, kw_b) in arms.items():
        base = dict(W_list=[W], lam_list=[lam], reps=8, seed0=31)
        qa = svc.make_query(topo, **{**base, **kw_a})
        qb = svc.make_query(topo, **{**base, **kw_b})
        res = svc.query_pair(qa, qb, policy=PairedPolicy(
            batch_reps=8, min_reps=8, max_reps=64 * max(reps, 16)))
        pc = res.paired
        n_paired = int(pc.n[0])
        delta = float(pc.delta_mean[0])
        var_sum = float(pc.var_a[0] + pc.var_b[0])
        z = z_value(pc.confidence)
        n_indep = int(np.ceil(z * z * var_sum / max(delta * delta, 1e-12))) \
            if pc.significant[0] else np.inf
        rows.append(dict(
            pair=name, p=p, W=W, lam=lam,
            delta=round(delta, 1),
            delta_hw=round(float(pc.delta_half_width[0]), 1),
            indep_hw_same_n=round(float(pc.independent_half_width()[0]), 1),
            significant=bool(pc.significant[0]),
            n_paired=n_paired, n_indep_equiv=n_indep,
            savings=round(n_indep / max(n_paired, 1), 1)
            if np.isfinite(n_indep) else ""))
    us = (time.time() - t0) * 1e6 / len(rows)
    _write_csv("paired_comparison", rows)
    sig = [r for r in rows if r["significant"] and r["savings"] != ""]
    med = float(np.median([r["savings"] for r in sig])) if sig else 0.0
    _row("paired_comparison", us,
         f"{len(sig)}/{len(rows)} gaps significant; paired needs "
         f"x{med:.1f} fewer reps than independent arms")
    shutil.rmtree(tmp, ignore_errors=True)


def backend_matrix(reps: int):
    """One grid, every available execution backend: throughput + parity +
    wasted-lane accounting.

    The parity column asserts the backend contract (bit-identical rows on
    every backend — what makes the store's keys backend-free); the rows/s
    column is the cross-substrate perf trajectory (BENCH_backends.json is
    uploaded per commit by the extended CI job, and guarded against
    regression by benchmarks/check_regression.py). The λ spread makes the
    per-row event counts heavy-tailed, so ``wasted_frac_convoy`` — the
    fraction of lane-iterations a single monolithic vmap batch burns on
    already-finished rows, ``1 − sum(events)/(n_rows × max(events))`` — is
    high; the jax backend's ``wasted_frac_actual`` shows how much of that
    the segmented driver's compaction recovers. ``pallas_interpret`` is
    ~1000× slower than compiled paths, so it runs (and parity-checks) a
    small row slice only — its record carries ``comparable: false``
    because an 8-row rows/s is not the same workload as the 66-row grid,
    and check_regression.py must not treat it as a like-for-like perf
    series."""
    from repro.core import engine as eng
    from repro.core.backend import (backend_names, default_backend_name,
                                    get_backend)
    from repro.core.sweep import grid_rows, resolve_model, run_rows

    p, W, lams = 16, 30_000, (2, 6, 20)
    n_reps = max(reps + 6, 22)    # >= 66 rows: the convoy regime (batch >= 64)
    topo = one_cluster(p, 1)
    rows = grid_rows([W], lams, n_reps)
    model = resolve_model(topo, "divisible", W_list=[W], lam_list=lams,
                          pow2_max_events=True)
    ref = run_rows(model, rows, backend="jax", reroute=False)
    ev = np.asarray(ref.extras["n_events"], np.float64)
    convoy = 1.0 - ev.sum() / (len(rows) * ev.max())
    interp_n = min(8, len(rows))
    out = []
    for name in backend_names():
        be = get_backend(name)
        caps = be.capabilities()
        if not caps.available:
            out.append(dict(backend=name, available=False, note=caps.note))
            continue
        rows_b = rows.slice(0, interp_n) if name == "pallas_interpret" \
            else rows
        nb = len(rows_b)
        def run():
            return run_rows(model, rows_b, backend=name, reroute=False)
        run()                                # compile + warm
        t0 = time.time()
        g = run()
        dt = max(time.time() - t0, 1e-9)
        parity = all(
            np.array_equal(np.asarray(getattr(g, f)),
                           np.asarray(getattr(ref, f))[:nb])
            for f in ("makespan", "n_requests", "n_success", "n_fail",
                      "total_idle", "startup_end", "overflow")) \
            and np.array_equal(g.extras["executed"],
                               ref.extras["executed"][:nb])
        rec = dict(
            backend=name, available=True, kind=caps.kind,
            devices="+".join(caps.devices), n_rows=nb,
            comparable=nb == len(rows),
            n_devices=caps.n_devices,
            rows_per_s=round(nb / dt, 2),
            events_per_s=round(float(g.extras["n_events"].sum()) / dt, 1),
            us_per_row=round(dt * 1e6 / nb, 1),
            wasted_frac_convoy=round(convoy, 4),
            parity_vs_jax=bool(parity))
        if name == "jax" and be.last_stats is not None:
            st = be.last_stats
            rec.update(wasted_frac_actual=round(st.wasted_frac, 4),
                       n_segments=st.n_segments,
                       n_compactions=st.n_compactions,
                       segment_len=caps.segment_len)
        out.append(rec)
    _write_csv("backend_matrix", out)
    BENCH.mkdir(parents=True, exist_ok=True)
    with open(BENCH / "BENCH_backends.json", "w") as f:
        json.dump({"engine_version": eng.ENGINE_VERSION,
                   "default_backend": default_backend_name(),
                   "grid": dict(p=p, W=W, lams=list(lams), reps=n_reps,
                                n_rows=len(rows)),
                   "backends": out}, f, indent=1, sort_keys=True)
    ran = [r for r in out if r.get("available")]
    bad = [r["backend"] for r in ran if not r["parity_vs_jax"]]
    fastest = max(ran, key=lambda r: r["rows_per_s"])
    by_name = {r["backend"]: r for r in ran}
    vs = ""
    if "jax" in by_name and "oracle" in by_name:
        ratio = by_name["jax"]["rows_per_s"] / by_name["oracle"]["rows_per_s"]
        vs = f"; jax x{ratio:.2f} vs oracle at batch {len(rows)}"
        jr = by_name["jax"]
        if "wasted_frac_actual" in jr:
            vs += (f" (lanes wasted {jr['wasted_frac_actual']:.0%} vs "
                   f"{jr['wasted_frac_convoy']:.0%} convoy)")
    _row("backend_matrix", fastest["us_per_row"],
         f"{len(ran)}/{len(out)} backends available; parity "
         f"{'OK' if not bad else 'FAIL ' + ','.join(bad)}; fastest "
         f"{fastest['backend']} at {fastest['rows_per_s']:,.0f} rows/s{vs}")


def obs_overhead(reps: int):
    """The observability layer (DESIGN.md §9) on a traced service pass:
    a cold then a warm query of the ``backend_matrix`` workload on the jax
    backend, traced into ``obs_trace/`` (the profiler's xplane and
    Perfetto trace of a real query's span tree), with the metrics snapshot
    (``obs_metrics.json``) and BENCH_obs.json carrying the cache-hit ratio
    that check_regression.py guards. What tracing costs is measured on the
    chip (PERF.md), not here."""
    import shutil
    import tempfile
    from repro import obs
    from repro.service import SimulationService

    p, W, lams, n_reps = 16, 30_000, (2, 6, 20), 16
    topo = one_cluster(p, 1)
    tmp = tempfile.mkdtemp(prefix="bench_obs_")
    reg = obs.MetricsRegistry()
    svc = SimulationService(root=tmp, metrics=reg)
    qkw = dict(W_list=[W], lam_list=list(lams), reps=n_reps, seed0=7,
               backend="jax")
    trace_dir = BENCH / "obs_trace"
    shutil.rmtree(trace_dir, ignore_errors=True)
    t0 = time.time()
    with obs.trace_to(trace_dir) as qtr:
        svc.query(topo, **qkw)               # cold: dispatches
        svc.query(topo, **qkw)               # warm: store hit
    dt = time.time() - t0
    snap = svc.stats()["metrics"]
    c = snap["counters"]
    hits = c.get("store.hits_mem", 0) + c.get("store.hits_disk", 0)
    lookups = hits + c.get("store.misses", 0)
    hit_ratio = round(hits / lookups, 4) if lookups else None
    BENCH.mkdir(parents=True, exist_ok=True)
    with open(BENCH / "obs_metrics.json", "w") as f:
        json.dump(snap, f, indent=1, sort_keys=True)
    shutil.rmtree(tmp, ignore_errors=True)

    n_rows = n_reps * len(lams)
    out = dict(n_rows=n_rows,
               trace_query_spans=len(qtr.durations_ms()),
               cache_hit_ratio=hit_ratio)
    _write_csv("obs_overhead", [out])
    with open(BENCH / "BENCH_obs.json", "w") as f:
        json.dump(out, f, indent=1, sort_keys=True)
    _row("obs_overhead", dt * 1e6 / n_rows,
         f"{out['trace_query_spans']} span names traced; "
         f"cache_hit_ratio={hit_ratio}")


def sanitizer_overhead(reps: int):
    """Cost of the determinism sanitizer (repro.check.sanitizer) on the
    ``obs_overhead`` workload: armed (replay 1/16, 2 rows) vs disarmed
    throughput on the jax backend. Target: <5% overhead armed — the probes
    are numpy reductions at segment/dispatch boundaries plus an amortized
    2-row oracle replay. seed0 is chosen so the dispatch IS in the 1-in-16
    replay sample (xor-folded seeds), so the measured cost includes the
    replay, not just the cheap probes. Emits BENCH_check.json for the
    check_regression.py warn-only guard."""
    from repro.check import sanitizer as san
    from repro.core.sweep import grid_rows, resolve_model, run_rows

    p, W, lams = 16, 30_000, (2, 6, 20)
    n_reps = max(reps + 6, 22)
    topo = one_cluster(p, 1)
    denom = 16

    def _sampled(cand) -> bool:
        seeds = np.asarray(cand.seed, dtype=np.uint32)
        return int(np.bitwise_xor.reduce(seeds)) % denom == 0

    # The production cost is amortized: 1 dispatch in ``denom`` replays.
    # Time a ``denom``-dispatch workload containing exactly one sampled
    # dispatch, so the measured overhead includes the replay at exactly
    # its real rate. The xor-fold residue class depends on the row count
    # as much as on seed0 (seeds are structured), so the sampled grid is
    # searched over a few widths too.
    grids = [grid_rows([W], lams, n_reps, seed0=s)
             for s in range(1, denom + 1)]
    if not any(_sampled(g) for g in grids):
        hit = None
        for nr in range(n_reps, n_reps + 4):
            for seed0 in range(1, 65):
                cand = grid_rows([W], lams, nr, seed0=seed0)
                if _sampled(cand):
                    hit = cand
                    break
            if hit is not None:
                break
        if hit is not None:
            grids[0] = hit
    n_rows_total = sum(len(g) for g in grids)
    model = resolve_model(topo, "divisible", W_list=[W], lam_list=lams,
                          pow2_max_events=True)

    def timed() -> float:
        t0 = time.time()
        for g in grids:
            run_rows(model, g, backend="jax", reroute=False)
        return time.time() - t0

    timed()                                  # compile + warm (both widths)
    offs, ons = [], []
    try:
        for _ in range(5):
            san.uninstall()
            offs.append(timed())
            san.install(replay_denom=denom, replay_rows=2)
            san.reset()
            ons.append(timed())
        summ = san.summary()
    finally:
        san.uninstall()
        san.reset()
    dt_off, dt_on = min(offs), min(ons)
    overhead = dt_on / dt_off - 1.0

    out = dict(
        n_rows=n_rows_total,
        disarmed_rows_per_s=round(n_rows_total / dt_off, 2),
        armed_rows_per_s=round(n_rows_total / dt_on, 2),
        overhead_frac=round(overhead, 4),
        replay_denom=denom,
        n_dispatch_probes=summ["n_dispatch_probes"],
        n_replayed_dispatches=summ["n_replayed_dispatches"],
        n_replayed_rows=summ["n_replayed_rows"],
        violations_total=summ["violations_total"])
    _write_csv("sanitizer_overhead", [out])
    with open(BENCH / "BENCH_check.json", "w") as f:
        json.dump(out, f, indent=1, sort_keys=True)
    _row("sanitizer_overhead", dt_on * 1e6 / n_rows_total,
         f"sanitizer overhead {overhead:+.1%} ({out['armed_rows_per_s']:,.0f}"
         f" vs {out['disarmed_rows_per_s']:,.0f} rows/s; target <5%); "
         f"replayed {summ['n_replayed_rows']} rows in "
         f"{summ['n_replayed_dispatches']} dispatches; "
         f"violations={summ['violations_total']}")


def fault_recovery(reps: int):
    """Query latency under injected backend faults (DESIGN.md §10): p50/p99
    per-query service latency at 0% / 5% / 20% per-row backend failure rate
    (``per_row`` faults on the jax backend; poisoned rows fail on every
    retry, forcing bisection salvage + oracle fallback). Emits
    BENCH_fault.json with the recovery counters so check_regression.py can
    guard the recovered-path latency like any other perf series. The 0% row
    doubles as the clean-path overhead control: the resilience layer on a
    healthy dispatch is one extra function frame."""
    import shutil
    import tempfile
    from repro import obs
    from repro.service import SimulationService
    from repro.service import resilience as rz

    p, W = 8, 20_000
    topo = one_cluster(p, 1)
    n_q = max(3 * reps, 48)
    cfg = rz.ResilienceConfig(
        retry=rz.RetryPolicy(max_attempts=1, base_s=0.0, cap_s=0.0),
        breaker_failures=1 << 30)   # keep bisecting instead of tripping
    out_rows = []
    per_rate = {}
    for rate in (0.0, 0.05, 0.20):
        plan = rz.FaultPlan(rng_seed=11, sites={
            "backend.run_rows": rz.Prob(rate, kind="raise", per_row=True,
                                        match={"backend": "jax"})})
        tmp = tempfile.mkdtemp(prefix="bench_fault_")
        reg = obs.MetricsRegistry()
        svc = SimulationService(root=tmp, metrics=reg, resilience=cfg)
        def mk(s):
            return svc.make_query(topo, W_list=[W], lam_list=[3],
                                  reps=1, seed0=s, backend="jax")
        with rz.fault_plan(rz.no_faults()):
            svc.query_many([mk(0)])          # compile warm-up, fault-free
        lats = []
        with rz.fault_plan(plan):
            for s in range(1, n_q + 1):      # one query per flush: the
                t0 = time.time()             # latency a single caller sees
                svc.query_many([mk(s)])
                lats.append((time.time() - t0) * 1e3)
        deg = svc.stats()["degraded"]
        shutil.rmtree(tmp, ignore_errors=True)
        entry = dict(
            fault_rate=rate, n_queries=n_q,
            p50_ms=round(float(np.percentile(lats, 50)), 3),
            p99_ms=round(float(np.percentile(lats, 99)), 3),
            retries=int(deg["retries"]), fallbacks=int(deg["fallbacks"]),
            salvaged_rows=int(deg["salvaged_rows"]),
            dispatch_failures=int(deg["dispatch_failures"]))
        out_rows.append(entry)
        per_rate[f"{rate:g}"] = entry
    _write_csv("fault_recovery", out_rows)
    BENCH.mkdir(parents=True, exist_ok=True)
    from repro.core import engine as _eng
    with open(BENCH / "BENCH_fault.json", "w") as f:
        json.dump({"engine_version": _eng.ENGINE_VERSION,
                   "workload": dict(p=p, W=W, n_queries=n_q),
                   "rates": per_rate}, f, indent=1, sort_keys=True)
    clean, worst = per_rate["0"], per_rate["0.2"]
    _row("fault_recovery", worst["p99_ms"] * 1e3,
         f"p99 {clean['p99_ms']:.1f}ms@0% -> {worst['p99_ms']:.1f}ms@20% "
         f"({worst['fallbacks']} fallbacks, {worst['retries']} retries, "
         f"0 client errors)")


#: Child process of the ``daemon_throughput`` bench: answers the same
#: queries either through a DaemonClient (shared daemon) or through its
#: own private SimulationService (per-process library mode, paying import
#: + JIT warmup itself — the cost the daemon amortizes).
_DAEMON_BENCH_CLIENT = """
import json, sys, time
cfg = json.loads(sys.argv[1])
sys.path.insert(0, cfg["src"])
from repro.core import one_cluster
topo = one_cluster(cfg["p"], 1)
kw = dict(W_list=[cfg["W"]], lam_list=cfg["lams"], reps=cfg["reps"])
if cfg["mode"] == "daemon":
    from repro.service import DaemonClient
    svc = DaemonClient(root=cfg["root"], fallback=False)
else:
    from repro.service import SimulationService
    svc = SimulationService(root=cfg["root"])
lats = []
for i in range(cfg["n_queries"]):
    t0 = time.time()
    svc.query(topo, seed0=cfg["seed0"] + i, **kw)
    lats.append((time.time() - t0) * 1e3)
print(json.dumps({"lats": lats,
                  "dispatches": getattr(svc, "n_dispatches", 0)}))
"""


def daemon_throughput(reps: int):
    """The daemon's reason to exist, measured (DESIGN.md §12): N client
    processes × M queries against one warm shared daemon vs the same
    clients each running per-process library mode from cold.

    The daemon pays interpreter start + JIT compile once and shares the
    broker across clients (identical concurrent questions coalesce into
    one dispatch; answered ones are store hits). Library mode is the
    pre-daemon workflow: one process invocation per query — a planner CLI
    call — each paying interpreter start + jax import + JIT compile for a
    query that computes in milliseconds, and dispatching N×M times in
    total. Emits BENCH_daemon.json (q/s, dispatches, per-query p50/p99
    per mode).

    A chip belongs to one process at a time, so this process never opens
    JAX here: the daemon runs through its CLI and holds the device while
    the daemon-mode clients talk to it; after it stops, the library-mode
    processes each need the device themselves, and on an accelerator they
    run one at a time."""
    import shutil
    import subprocess
    import sys
    import tempfile
    from jax._src import xla_bridge
    from repro.core import one_cluster
    from repro.service import DaemonClient

    if xla_bridge.backends_are_initialized():
        import jax
        if jax.default_backend() != "cpu":
            raise RuntimeError(
                "daemon_throughput starts processes that need the device, "
                "which this process already holds; run it first or alone "
                "(--only daemon_throughput)")
    n_clients, n_queries = 3, 4
    p, W, lams, reps_q = 8, 20_000, [3, 5], max(min(reps, 8), 2)
    src = str(Path(__file__).resolve().parents[1] / "src")
    topo = one_cluster(p, 1)
    tmps = []

    def run_round(mode, roots, per_proc, seed0, parallel=True):
        cfgs = [dict(mode=mode, src=src, root=str(r), p=p, W=W, lams=lams,
                     reps=reps_q, n_queries=per_proc, seed0=seed0)
                for r in roots]

        def start(c):
            return subprocess.Popen(
                [sys.executable, "-c", _DAEMON_BENCH_CLIENT, json.dumps(c)],
                stdout=subprocess.PIPE, text=True)

        def finish(pr):
            out = json.loads(pr.communicate()[0].strip().splitlines()[-1])
            assert pr.returncode == 0
            return out

        if parallel:
            outs = [finish(pr) for pr in [start(c) for c in cfgs]]
        else:
            outs = [finish(start(c)) for c in cfgs]
        lats = [l for o in outs for l in o["lats"]]
        return lats, sum(o["dispatches"] for o in outs)

    # Warm shared daemon: JIT warmed by a *disjoint* query (seed0=999), so
    # the measured queries still exercise real dispatches, coalescing and
    # store hits — not a pure pre-filled-cache replay. The N clients are
    # long-lived processes issuing all M queries over one connection.
    tmp = Path(tempfile.mkdtemp(prefix="bench_daemon_"))
    tmps.append(tmp)
    root = tmp / "store"
    daemon = subprocess.Popen(
        [sys.executable, "-m", "repro.service.daemon", "--root", str(root),
         "--coalesce-window-s", "0.02"],
        stdout=subprocess.PIPE, text=True,
        env=dict(os.environ, PYTHONPATH=src))
    try:
        assert daemon.stdout.readline().startswith("READY")
        warm = DaemonClient(root=root, fallback=False)
        assert warm.alive()
        platform = warm.daemon_platform
        warm.query(topo, W_list=[W], lam_list=lams, reps=reps_q, seed0=999)
        d0 = warm.stats()["n_dispatches"]
        t0 = time.time()
        lats_d, _ = run_round("daemon", [root] * n_clients, n_queries,
                              seed0=100)
        wall_d = time.time() - t0
        disp_d = warm.stats()["n_dispatches"] - d0
        warm.shutdown()
        daemon.wait(timeout=60)
    finally:
        if daemon.poll() is None:
            daemon.kill()
            daemon.wait()

    # Cold per-process library mode: the same N×M queries, but each in a
    # fresh process with a private store root (the pre-daemon CLI
    # workflow) — N invocations per round (in parallel on the CPU), M
    # sequential rounds.
    t0 = time.time()
    lats_l, disp_l = [], 0
    for i in range(n_queries):
        roots = [Path(tempfile.mkdtemp(prefix="bench_daemon_lib_"))
                 for _ in range(n_clients)]
        tmps.extend(roots)
        lats, disp = run_round("library", roots, 1, seed0=100 + i,
                               parallel=platform == "cpu")
        lats_l.extend(lats)
        disp_l += disp
    wall_l = time.time() - t0

    total = n_clients * n_queries
    qps_d, qps_l = total / wall_d, total / wall_l
    speedup = qps_d / max(qps_l, 1e-9)
    stats = {
        "daemon": dict(qps=round(qps_d, 2), wall_s=round(wall_d, 3),
                       n_dispatches=int(disp_d),
                       p50_ms=round(float(np.percentile(lats_d, 50)), 2),
                       p99_ms=round(float(np.percentile(lats_d, 99)), 2)),
        "library": dict(qps=round(qps_l, 2), wall_s=round(wall_l, 3),
                        n_dispatches=int(disp_l),
                        p50_ms=round(float(np.percentile(lats_l, 50)), 2),
                        p99_ms=round(float(np.percentile(lats_l, 99)), 2)),
    }
    out = dict(workload=dict(n_clients=n_clients, n_queries=n_queries,
                             p=p, W=W, lams=list(lams), reps=reps_q),
               platform=platform,
               speedup_vs_library=round(speedup, 2), **stats)
    _write_csv("daemon_throughput", [dict(
        mode=m, **stats[m]) for m in ("daemon", "library")])
    BENCH.mkdir(parents=True, exist_ok=True)
    with open(BENCH / "BENCH_daemon.json", "w") as f:
        json.dump(out, f, indent=1, sort_keys=True)
    for t in tmps:
        shutil.rmtree(t, ignore_errors=True)
    _row("daemon_throughput", wall_d * 1e6 / total,
         f"warm daemon x{speedup:.1f} vs cold per-process library "
         f"({qps_d:.2f} vs {qps_l:.2f} q/s, {n_clients} clients x "
         f"{n_queries} queries); dispatches {disp_d} vs {disp_l}; "
         f"daemon p50/p99 {stats['daemon']['p50_ms']:.0f}/"
         f"{stats['daemon']['p99_ms']:.0f}ms on {platform}")


def roofline(_reps: int):
    """Aggregate the dry-run artifacts into the §Roofline table."""
    cells = sorted((ART / "dryrun").glob("*.json"))
    if not cells:
        _row("roofline", 0.0, "no dry-run artifacts (run repro.launch.dryrun)")
        return
    rows = []
    for f in cells:
        d = json.loads(f.read_text())
        if d.get("skipped"):
            rows.append(dict(arch=d["arch"], shape=d["shape"], mesh=d["mesh"],
                             skipped=d["reason"]))
            continue
        r = d["roofline"]
        rows.append(dict(
            arch=d["arch"], shape=d["shape"], mesh=d["mesh"],
            compute_ms=round(r["compute_s"] * 1e3, 3),
            memory_ms=round(r["memory_s"] * 1e3, 3),
            collective_ms=round(r["collective_s"] * 1e3, 3),
            dominant=r["dominant"],
            model_flops=r["model_flops"], useful_ratio=round(r["useful_ratio"], 4),
            peak_gib=round(d["memory"]["peak_bytes_estimate"] / 2**30, 2)))
    _write_csv("roofline", rows)
    done = [r for r in rows if "dominant" in r]
    doms = {}
    for r in done:
        doms[r["dominant"]] = doms.get(r["dominant"], 0) + 1
    _row("roofline", 0.0, f"{len(done)} cells; dominant terms: {doms}")


def _write_csv(name: str, rows):
    BENCH.mkdir(parents=True, exist_ok=True)
    if not rows:
        return
    keys = sorted({k for r in rows for k in r})
    with open(BENCH / f"{name}.csv", "w") as f:
        f.write(",".join(keys) + "\n")
        for r in rows:
            f.write(",".join(str(r.get(k, "")) for k in keys) + "\n")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale reps (slow)")
    ap.add_argument("--only", default=None)
    args, _ = ap.parse_known_args()
    reps = 100 if args.full else 16

    from repro.core.backend import enable_compile_cache
    enable_compile_cache()
    print("name,us_per_call,derived")
    # daemon_throughput first: it starts processes that need the device,
    # so it must run before this process opens JAX.
    benches = {
        "daemon_throughput": lambda: daemon_throughput(reps),
        "fig10_overhead_ratio": lambda: fig10_overhead_ratio(reps),
        "fig11_accept_latency": lambda: fig11_accept_latency(reps),
        "fig12_mwt_swt": lambda: fig12_mwt_swt(reps, args.full),
        "steal_threshold": lambda: steal_threshold(reps),
        "multicluster": lambda: multicluster(reps),
        "sim_throughput": lambda: sim_throughput(max(reps, 32)),
        "model_throughput": lambda: model_throughput(max(reps, 32)),
        "sched_planner": lambda: sched_planner(reps),
        "service_throughput": lambda: service_throughput(reps),
        "paired_comparison": lambda: paired_comparison(reps),
        "backend_matrix": lambda: backend_matrix(reps),
        "obs_overhead": lambda: obs_overhead(reps),
        "sanitizer_overhead": lambda: sanitizer_overhead(reps),
        "fault_recovery": lambda: fault_recovery(reps),
        "roofline": lambda: roofline(reps),
    }
    for name, fn in benches.items():
        if args.only and args.only != name:
            continue
        fn()


if __name__ == "__main__":
    main()
