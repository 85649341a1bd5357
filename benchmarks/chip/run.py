"""Chip benchmark of the work-stealing simulator service: one run of a cell.

    python3 benchmarks/chip/run.py --workload paper_batch --seed 7 \\
        --seconds 30 --trace 0

A cell (``BENCHMARK.json`` ``workloads``) is a configuration under a traffic
mix. A run drives the served path in library mode, as a planner's script
does: ``SimulationService.query`` on the default backend, which is the
compiled Pallas kernel on a TPU, with a fresh store in a temporary
directory. One client asks one question at a time and waits for each
answer. Set-up builds the service and answers one warm-up question of the
cell's shape; then the window asks questions, a block of equal work at a
time (``cell.blocks``), until the first block that ends ``--seconds``
seconds or more after the window's start.
Once it has closed, every answer is held to the plain reference
(``check.py``).

With ``--trace 0`` the result holds the cell's end-to-end metrics; with
``--trace 1`` a profiler trace of the window gives its per-layer metrics
and a breakdown. The last line of standard output is the result; the lines
before it are observations that are no metric, and the last lines of
standard error give each compared number beside its limit. A run that finds
no TPU, or fewer chips than the cell asks for, exits non-zero and prints no
result.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))

import cell as cellmod  # noqa: E402
import check  # noqa: E402
import devtrace  # noqa: E402
import guards  # noqa: E402
from readings import Run  # noqa: E402

#: JAX's persistent compilation cache, at a fixed path inside the
#: checkout where ``JAX_COMPILATION_CACHE_DIR`` is not set.
CACHE_DIR = ROOT / "artifacts" / "jit_cache"


def info(**kw):
    print(json.dumps({"info": kw}), flush=True)


class SpanRecorder:
    """Records JAX's own ``jax.monitoring`` time spans while open."""

    def __init__(self):
        self.spans = []

    def __call__(self, event, start, end, **_):
        self.spans.append((event, start, end))

    def __enter__(self):
        import jax
        jax.monitoring.register_event_time_span_listener(self)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_time_span_listener(self)


def _jax_setup():
    """Turn on the persistent compilation cache before JAX starts: every
    program is kept, however quick its compile. The TPU runtime's logs,
    which go to a fixed directory under /tmp by default, are turned off."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(CACHE_DIR))
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")
    Path(os.environ["JAX_COMPILATION_CACHE_DIR"]).mkdir(parents=True,
                                                        exist_ok=True)


def _topology_and_kwargs(cell, control: bool):
    """The topology the cell's configuration names (``one_cluster``, the
    only one ``cell.validate`` admits) and the shared query arguments."""
    from repro.core.topology import one_cluster
    kw = cellmod.query_kwargs(cell)
    if control:
        kw["mwt"] = True
    return one_cluster(cell.config["p"], cell.traffic["lam_list"][0]), kw


def _device(devs, traced: Run = None) -> dict:
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    out = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs),
           "memory_peak_bytes": max(peaks) if peaks else None}
    if traced is not None and traced.trace is not None:
        lo, hi = traced.trace_window
        busy = devtrace.busy_ns(traced.trace, lo, hi)
        out["busy_s"] = sum(busy.values()) / max(len(busy), 1) / 1e9
        out["window_s"] = (hi - lo) / 1e9
    return out


def serve(cell, seed: int, seconds: float, traced: bool, work: Path,
          control: bool = False, require_chip: bool = True):
    """Set up, warm up and run the window; returns (Run, devices, failed,
    fallbacks)."""
    import jax
    devs = guards.open_chip(cell.chips) if require_chip else jax.devices()
    t_chip = time.perf_counter()
    from repro import obs
    from repro.core import backend as bk
    from repro.service.api import SimulationService

    traffic = cell.traffic
    topo, kw = _topology_and_kwargs(cell, control)
    svc = SimulationService(root=work / "store")
    expect = guards.KERNEL if require_chip else bk.default_backend_name()
    t_service = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.warmup"):
        svc.query(topo, seed0=cellmod.warm_seed(traffic, seed), **kw)
    t_warm = time.perf_counter()
    faults = guards.kernel_faults(bk) if require_chip else []
    for f in faults:
        print(f"guard: {f}", file=sys.stderr)

    blocks = cellmod.blocks(traffic, seed)
    answers, failed = [], 0
    before = dict(obs.REGISTRY.snapshot()["counters"])
    n_log, hits = len(svc.broker.dispatch_log), svc.broker.n_cache_hits
    trace_dir = work / "trace"
    with contextlib.ExitStack() as stack:
        spans = stack.enter_context(SpanRecorder())
        if traced:
            jax.profiler.start_trace(str(trace_dir), profiler_options=(
                _profile_options()))
            stack.callback(jax.profiler.stop_trace)
        t_start = time.perf_counter()
        epoch_start = time.time()
        with jax.profiler.TraceAnnotation(devtrace.WINDOW):
            for block in blocks:
                if time.perf_counter() - t_start >= seconds:
                    break
                for seed0 in block:
                    try:
                        with jax.profiler.TraceAnnotation("bench.query"):
                            res = svc.query(topo, seed0=seed0, **kw)
                    except Exception:  # noqa: BLE001 — counted failed
                        print(f"query seed0={seed0} raised:",
                              file=sys.stderr)
                        traceback.print_exc()
                        failed += 1
                        continue
                    answers.append(check.Answer(seed0, res,
                                                time.perf_counter()))
        t_end = answers[-1].t_done if answers else time.perf_counter()
        epoch_end = epoch_start + (t_end - t_start)
    after = dict(obs.REGISTRY.snapshot()["counters"])
    log = list(svc.broker.dispatch_log)[n_log:]
    run = Run(answers=answers, t_start=t_start, t_end=t_end,
              setup_s=t_start - T_PROCESS, counters_before=before,
              counters_after=after, spans=spans.spans,
              span_window=(epoch_start, epoch_end))
    if traced:
        run.trace = devtrace.load(trace_dir)
        run.trace_window = devtrace.window(run.trace)
    failed += sum(1 for a in answers
                  if bool(a.result.grid.overflow.any()))
    rows = [d["n_rows"] for d in log]
    info(answers=len(answers), store_hits=svc.broker.n_cache_hits - hits,
         compiles_in_window=sum(1 for e, _, _ in spans.spans if e.endswith(
             "backend_compile_duration")),
         dispatches=len(log), rows_per_dispatch=rows[:64],
         window_s=run.window_s,
         setup_parts_s={"chip": t_chip - T_PROCESS,
                        "service": t_service - t_chip,
                        "warmup": t_warm - t_service})
    fallbacks = guards.hidden_fallbacks(before, after, log, expect) \
        + len(faults)
    return run, devs, failed, fallbacks


def _profile_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return opts


def measure(cell, run: Run, traced: bool) -> dict:
    metrics = {}
    for m in (cell.per_layer if traced else cell.end_to_end):
        v = m.read(run)
        if v is not None:
            metrics[m.name] = {"value": float(v), "unit": m.unit}
    return metrics


def breakdown(run: Run) -> dict:
    lo, hi = run.trace_window
    return {"device_ops": [[n, s] for n, s in
                           devtrace.top_ops(run.trace, lo, hi)],
            "idle_gaps": [[n, s] for n, s in
                          devtrace.idle_gaps(run.trace, lo, hi)]}


def main(argv=None, require_chip: bool = True, control: bool = False,
         root: Path = ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = cellmod.load(args.workload, root)
    _jax_setup()
    sys.path.insert(0, str(ROOT / "src"))
    work = Path(tempfile.mkdtemp(prefix="wsbench_"))
    try:
        try:
            run, devs, failed, fallbacks = serve(
                cell, args.seed, args.seconds, bool(args.trace), work,
                control=control, require_chip=require_chip)
        except guards.NoChip as e:
            print(f"run.py: {e}", file=sys.stderr)
            return 2
        device = _device(devs, run)
        metrics = measure(cell, run, bool(args.trace))
        numbers = check.compare(cell, run.answers, args.seed,
                                fallbacks=fallbacks, control=control)
    finally:
        shutil.rmtree(work, ignore_errors=True)
    correct = check.verdict(numbers) and failed == 0 and bool(run.answers)
    out = {"correct": correct, "attempted": len(run.answers) + failed,
           "failed": failed, "metrics": metrics, "device": device}
    if args.trace and run.trace_window is not None:
        out["breakdown"] = breakdown(run)
    out["checks"] = {k: {"value": v, "limit": check.LIMITS[k]}
                     for k, v in numbers.items()}
    for k, v in numbers.items():
        print(f"check {k} {v} limit {check.LIMITS[k]}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
