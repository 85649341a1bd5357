"""Reproduce the paper's §4 experiments at reduced repetition count.

Fig 10: overhead ratio 4-5.5x; fitted constant ~3.8.
Fig 11: acceptable-latency law  W/p ~= 470*lambda.
Fig 12/14: MWT vs SWT: startup-phase speedup, flat overall gain.

Fig 10 runs through the sweep *service* (DESIGN.md §5): each table cell is
adaptively replicated until E[Cmax] has a 1% confidence interval, instead of
a fixed rep count, and the printed table carries the CI columns plus the
paper's boxplot-style distribution columns — median/p10/p90 from the
*streaming* P² estimator (no stored ensemble needed). The MWT-vs-SWT
comparison (Fig 12/14) is a paired common-random-numbers A/B query: both
arms simulate the same seeds, so the speedup carries a CI on the per-seed
difference. Rerunning this script answers every cell from the
content-addressed store.

Full-scale parameters (1000 reps, W to 1e8) run the same code; see
benchmarks/ for the CSV versions used in EXPERIMENTS.md.

  PYTHONPATH=src python examples/paper_sweep.py
"""
import numpy as np

from repro import obs
from repro.core import analysis, engine as eng, make_model, one_cluster
from repro.core import divisible as dv
from repro.service import PairedPolicy, SimulationService


def overhead_and_fit(service=None, rel_hw=0.01):
    print("=== Fig 10: overhead ratio + fitted constant "
          f"(adaptive, ±{rel_hw:.0%} CI on E[Cmax]; "
          "p10/med/p90 via streaming P²) ===")
    svc = service or SimulationService()
    ratios_all, fits_all, total_reps = [], [], 0
    for p in (32, 64):
        topo = one_cluster(p, 1)
        res = svc.query(topo, W_list=[10**5, 10**6, 10**7],
                        lam_list=[2, 62, 262], ci=rel_hw, ci_relative=True,
                        batch_reps=8, max_reps=96, seed0=1)
        cells = res.cells
        total_reps += int(cells.n.sum())
        p10 = cells.quantile(0.1)
        p50 = cells.quantile(0.5)
        p90 = cells.quantile(0.9)
        for c in range(len(cells)):
            W, lam = int(cells.W[c]), int(cells.lam_remote[c])
            mean, hw, n = cells.mean[c], cells.half_width[c], int(cells.n[c])
            # ratio/fit are affine in Cmax, so the CI transfers directly.
            r = analysis.overhead_ratio(mean, W, p, lam)
            r_hw = r - analysis.overhead_ratio(mean + hw, W, p, lam)
            fit = analysis.fitted_constant(mean, W, p, lam)
            ratios_all.append(float(r))
            fits_all.append(float(fit))
            print(f"  p={p:3d} W=1e{int(np.log10(W))} lam={lam:3d}: "
                  f"Cmax={mean:12.1f} ±{hw:8.1f} (n={n:3d})  "
                  f"p10/med/p90={p10[c]:10.0f}/{p50[c]:10.0f}/{p90[c]:10.0f}  "
                  f"ratio={r:5.2f}±{abs(r_hw):4.2f} fit_c={fit:5.2f}")
    print(f"  => median overhead ratio {np.median(ratios_all):.2f} "
          f"(paper: 4-5.5); fitted constant {np.median(fits_all):.2f} "
          f"(paper: 3.8); {total_reps} adaptive replications")


def acceptable_latency(reps=16):
    print("\n=== Fig 11: acceptable latency (overhead <= 10%) ===")
    p = 32
    topo = one_cluster(p, 1)
    for W in (10**5, 10**6, 10**7):
        lam_th = analysis.theoretical_limit_latency(W, p)
        by_lam = {}
        for lam in np.unique(np.linspace(max(lam_th * 0.4, 1), lam_th * 2.2,
                                         8).astype(int)):
            model = make_model(
                "divisible", topology=topo,
                max_events=dv.default_max_events(W, p, int(lam)))
            scn = eng.batch_scenarios(W, np.arange(reps, dtype=np.uint32) + 3,
                                      lam=int(lam))
            by_lam[int(lam)] = np.asarray(eng.simulate_batch(model, scn).makespan)
        lam_exp = analysis.experimental_limit_latency(by_lam, W, p)
        print(f"  W=1e{int(np.log10(W))}: theoretical lam*={lam_th:7.1f} "
              f"experimental lam*={lam_exp:7.1f} "
              f"(W/p)/lam*={(W / p) / max(lam_exp, 1):6.0f} (paper: ~470)")


def mwt_vs_swt(service=None, reps=24):
    """Fig 12/14 as a paired CRN A/B query: arm A = SWT, arm B = MWT, both
    simulating the *same* seed streams, replicated until the CI on the
    per-seed makespan difference resolves the verdict (or the budget ends).
    """
    print("\n=== Fig 12/14: MWT vs SWT (paired CRN A/B) ===")
    svc = service or SimulationService()
    W, lam = 10**6, 262
    for p in (16, 32, 64):
        topo = one_cluster(p, lam)
        q_swt = svc.make_query(topo, W_list=[W], lam_list=[lam], reps=reps,
                               seed0=5, mwt=False)
        q_mwt = svc.make_query(topo, W_list=[W], lam_list=[lam], reps=reps,
                               seed0=5, mwt=True)
        res = svc.query_pair(q_swt, q_mwt, policy=PairedPolicy(
            batch_reps=8, min_reps=8, max_reps=4 * reps))
        pc = res.paired
        ms_gain = float(pc.mean_a[0] / pc.mean_b[0])
        su_gain = float(np.mean(res.grid_a.startup_end)
                        / np.mean(res.grid_b.startup_end))
        verdict = ("MWT faster" if pc.delta_mean[0] > 0 else "SWT faster") \
            if pc.significant[0] else "no significant gap"
        print(f"  p={p:3d}: startup speedup x{su_gain:4.2f} "
              f"overall speedup x{ms_gain:4.2f}; "
              f"dCmax={pc.delta_mean[0]:8.1f} ±{pc.delta_half_width[0]:7.1f} "
              f"(n={int(pc.n[0])} pairs) -> {verdict} "
              f"(paper: startup up to 2x+, overall ~flat)")


def execution_backends(reps=4):
    """Beyond-paper: the same grid through every available execution
    backend (DESIGN.md §7). The table's parity column is the contract that
    lets the content-addressed store share cached answers across backends —
    a TPU fleet's Pallas fills serve CPU replicas and vice versa."""
    from repro.core.backend import backend_names, get_backend
    from repro.core.sweep import grid_rows, resolve_model, run_rows

    print("\n=== Execution backends: one grid, every substrate ===")
    topo = one_cluster(8, 1)
    rows = grid_rows([20_000], [2, 30], reps)
    model = resolve_model(topo, "divisible", W_list=[20_000], lam_list=[2, 30],
                          pow2_max_events=True)
    ref = None
    for name in backend_names():
        caps = get_backend(name).capabilities()
        if not caps.available:
            print(f"  {name:16s} unavailable ({caps.note})")
            continue
        g = run_rows(model, rows, backend=name)
        if ref is None:
            ref = g
        ok = np.array_equal(g.makespan, ref.makespan) and np.array_equal(
            g.extras["executed"], ref.extras["executed"])
        print(f"  {name:16s} kind={caps.kind:9s} devices={caps.devices} "
              f"median Cmax={float(np.median(g.makespan)):8.0f} "
              f"bit-parity={'OK' if ok else 'FAIL'}")


def all_task_models(reps=8):
    """Beyond-paper: one sweep program per task model (§2.1.1-§2.1.3),
    all through the unified event core + batching layer."""
    from repro.core import dag_gen as gen
    from repro.core.sweep import run_grid

    print("\n=== Unified sweeps: divisible / dag / adaptive ===")
    topo = one_cluster(8, 1)
    g = run_grid(topo, W_list=[10**5], lam_list=[2, 62], reps=reps)
    print(f"  divisible: {len(g)} cells, median makespan "
          f"{float(np.median(g.makespan)):.0f}")
    g = run_grid(topo, lam_list=[2, 62], reps=reps, task_model="dag",
                 dag=gen.merge_sort(20_000, 64))
    print(f"  dag:       {len(g)} cells, median makespan "
          f"{float(np.median(g.makespan)):.0f} "
          f"(tasks completed {int(g.extras['n_completed'][0])})")
    g = run_grid(topo, W_list=[10**5], lam_list=[2, 62], reps=reps,
                 task_model="adaptive", merge_alpha=2, merge_beta_num=1)
    print(f"  adaptive:  {len(g)} cells, median makespan "
          f"{float(np.median(g.makespan)):.0f} "
          f"(median splits {float(np.median(g.extras['n_splits'])):.0f})")


def trace_and_metrics(out="paper_sweep_trace"):
    """Beyond-paper: the observability layer (DESIGN.md §9). Trace one
    query end-to-end — service.query -> broker.flush -> broker.round ->
    broker.dispatch -> backend.run_rows -> engine.segment / ws_sim.chunk ->
    backend.fetch, estimator.update, store puts/gets — in a profiler
    session written to the directory ``out`` (the xplane, and a
    Perfetto-loadable trace beside JAX's own events), and print the span
    summary plus the metrics snapshot that a monitoring daemon would
    scrape, ``compile.*`` counters included. The same tracing is available
    process-wide via ``REPRO_WS_TRACE=<dir>``."""
    print("\n=== Observability: one traced query + metrics snapshot ===")
    svc = SimulationService(metrics=obs.MetricsRegistry())
    topo = one_cluster(16, 5)
    with obs.trace_to(out) as tr:
        svc.query(topo, W_list=[10**5], lam_list=[5], reps=32)
        svc.query(topo, W_list=[10**5], lam_list=[5], reps=32)  # cache hit
    print(tr.summary())
    print(f"  profiler trace -> {out}/ ({len(tr.spans())} spans among "
          f"{len(tr.events())} host events; open perfetto_trace.json.gz "
          f"in ui.perfetto.dev)")
    snap = svc.stats()["metrics"]
    print("  metrics snapshot (daemon payload):")
    for kind in ("counters", "gauges"):
        for k, v in sorted(snap[kind].items()):
            print(f"    {k}: {v}")


if __name__ == "__main__":
    svc = SimulationService()
    overhead_and_fit(svc)
    acceptable_latency()
    mwt_vs_swt(svc)
    all_task_models()
    execution_backends()
    trace_and_metrics()
    print(f"\nservice: {svc.stats()}")
