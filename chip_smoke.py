"""Chip smoke test: the simulator service and its Pallas kernel on one TPU.

    python chip_smoke.py              # one chip: every phase below
    python chip_smoke.py --chips 4    # four chips: the sharded main path only

Phases on one chip, in this order (a chip belongs to one process at a time,
so the daemon runs first, while this process has not opened JAX):

1. daemon:   ``python -m repro.service.daemon`` answers three
             ``DaemonClient(fallback=False)`` clients, two paper-scale
             queries each; its ``ping`` must report a TPU. After it has
             stopped, the same queries in library mode must give
             byte-equal grids and store artifacts.
2. main:     one row of the paper's grid (p=256, W=1e7, four latencies,
             256 reps: 1,024 scenarios) through ``SimulationService.query``,
             once on the default backend (must be the compiled ``pallas``
             kernel) and once on ``jax``; the grids must be byte-equal and
             16 sampled rows must equal the serial oracle.
3. models:   the DAG and adaptive task models through both backends;
             byte-equal across backends, 2 rows each equal to the oracle.

With ``--chips 4`` only the main-path rows run: sharded over the four
devices on ``jax`` and on ``pallas`` (through the service), and through
``sweep.run_rows`` on the default backend, one row chunk a device; each must
be byte-equal to a run on device 0, every device must have run rows, and no
device reads another's copy.

Every service phase asserts that no fallback hid the device: no resilience
fallback or degraded dispatch, no dispatch on the oracle, and the kernel
lowered as a ``tpu_custom_call`` with ``interpret=False``. Lines before the
last are bring-up observations (wall and compile seconds, events/s); the
last line is the JSON verdict. Exits non-zero, printing no verdict, on any
failure or where JAX finds no TPU.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import shutil
import subprocess
import sys
import tempfile
import threading
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent
sys.path.insert(0, str(REPO / "src"))

import numpy as np  # noqa: E402

from repro import obs  # noqa: E402
from repro.core import backend as bk  # noqa: E402
from repro.core import dag_gen  # noqa: E402
from repro.core import sweep as sw  # noqa: E402
from repro.core.topology import one_cluster  # noqa: E402
from repro.service.api import SimulationService  # noqa: E402
from repro.service.client import DaemonClient  # noqa: E402

#: What the run must find: the platform JAX reports, and the backend that
#: dispatches the compiled kernel by default there.
PLATFORM, KERNEL = "tpu", "pallas"
#: One row of the paper's full grid (repro.configs.ws_paper): p=256,
#: W=1e7, four of its latencies, 256 replications.
MAIN = dict(W_list=[10**7], lam_list=[2, 62, 262, 482], reps=256)
MAIN_P = 256
#: The daemon's clients: one latency each plus one they all ask.
DAEMON_LAMS = ([2], [62], [482])
DAEMON_SHARED_LAM = [262]
DAEMON_REPS = 64
N_ORACLE_ROWS = 16
#: The task-model sizes of benchmarks/run.py::model_throughput.
MODEL_P, MODEL_LAM, MODEL_W, MODEL_REPS = 32, 10, 200_000, 8
MODEL_DAG = (20_000, 64)            # dag_gen.merge_sort(n, cutoff)
DAEMON_START_S = 300.0


class SmokeFailure(RuntimeError):
    pass


def check(cond, what: str):
    if not cond:
        raise SmokeFailure(what)


def note(phase: str, **kw):
    print(json.dumps({"phase": phase, **kw}), flush=True)


def counters() -> dict:
    return dict(obs.REGISTRY.snapshot()["counters"])


def delta(before: dict, after: dict, key: str) -> float:
    return after.get(key, 0) - before.get(key, 0)


def grids_equal(a, b) -> bool:
    """Byte equality of two GridResults, extras and dtypes included."""
    if a.p != b.p or sorted(a.extras) != sorted(b.extras):
        return False
    names = [f for f in vars(a) if f not in ("p", "extras")]
    pairs = [(getattr(a, f), getattr(b, f)) for f in names]
    pairs += [(a.extras[k], b.extras[k]) for k in a.extras]
    return all(np.asarray(x).dtype == np.asarray(y).dtype
               and np.asarray(x).shape == np.asarray(y).shape
               and np.asarray(x).tobytes() == np.asarray(y).tobytes()
               for x, y in pairs)


def rows_equal(grid, idx, ref) -> bool:
    """Rows ``idx`` of ``grid`` equal the columns ``ref`` shares with it."""
    cols = ("makespan", "n_requests", "n_success", "n_fail", "total_idle",
            "startup_end", "overflow")
    return all(np.array_equal(np.asarray(getattr(grid, c))[idx],
                              np.asarray(getattr(ref, c))) for c in cols)


def assert_no_hidden_fallback(stats: dict, before: dict, after: dict,
                              where: str):
    deg = stats["degraded"]
    check(not deg["degraded"] and deg["fallbacks"] == 0
          and deg["retries"] == 0 and deg["dispatch_failures"] == 0,
          f"{where}: degraded dispatch {deg}")
    check(delta(before, after, "resilience.fallbacks") == 0,
          f"{where}: resilience fallback")
    check(delta(before, after, "backend.run_rows{backend=oracle}") == 0,
          f"{where}: rows ran on the host oracle")


def assert_kernel(model, G: int, where: str) -> float:
    """The pallas backend's dispatch lowers to a Mosaic kernel
    (``tpu_custom_call``) with ``interpret=False``; returns the seconds
    the kernel took to lower and compile."""
    import jax
    from repro.kernels.ws_sim import ws_sim_pallas
    be = bk.get_backend(KERNEL)
    check(be._interpret is False and not bk.pallas_interpret_default(),
          f"{where}: pallas backend would interpret the kernel")
    scn = sw.scenario_from_rows(sw.grid_rows([1], [1], G))
    spec = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), scn)
    t0 = time.perf_counter()
    lowered = jax.jit(functools.partial(
        ws_sim_pallas, model, interpret=be._interpret,
        grid_chunk=be.grid_chunk)).lower(spec)
    check("tpu_custom_call" in lowered.as_text(),
          f"{where}: the lowered pallas dispatch holds no tpu_custom_call")
    lowered.compile()
    return time.perf_counter() - t0


def timed_query(svc, topo, **kw):
    t0 = time.perf_counter()
    r = svc.query(topo, **kw)
    return r, time.perf_counter() - t0


def events_per_s(grid, wall: float) -> float:
    return float(np.asarray(grid.extras["n_events"]).sum()) / wall


# ---------------------------------------------------------------------------
# Phase 1: the daemon (before this process opens JAX).
# ---------------------------------------------------------------------------

def _daemon_queries():
    topo = one_cluster(MAIN_P, 1)
    common = dict(W_list=MAIN["W_list"], reps=DAEMON_REPS)
    return [[(topo, dict(common, lam_list=lams)),
             (topo, dict(common, lam_list=DAEMON_SHARED_LAM))]
            for lams in DAEMON_LAMS]


def start_daemon(root: Path, sock: Path, log: Path) -> subprocess.Popen:
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    with open(log, "w") as err:
        proc = subprocess.Popen(
            [sys.executable, "-m", "repro.service.daemon", "--root",
             str(root), "--socket", str(sock)],
            stdout=subprocess.PIPE, stderr=err, text=True, env=env,
            cwd=str(REPO))
    ready = []
    reader = threading.Thread(
        target=lambda: ready.append(proc.stdout.readline()), daemon=True)
    reader.start()
    reader.join(DAEMON_START_S)
    if not ready or not ready[0].startswith("READY"):
        proc.kill()
        proc.wait()
        raise SmokeFailure(f"daemon did not start (rc={proc.returncode}): "
                           + log.read_text()[-2000:])
    return proc


def stop_daemon(proc: subprocess.Popen, client: DaemonClient):
    if proc.poll() is None:
        client.shutdown()
        try:
            proc.wait(timeout=60)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()


def phase_daemon(work: Path):
    from jax._src import xla_bridge
    check(not xla_bridge.backends_are_initialized(),
          "this process opened JAX before the daemon took the chip")
    root, sock = work / "daemon_store", work / "d.sock"
    proc = start_daemon(root, sock, work / "daemon.log")
    try:
        probe = DaemonClient(root=root, socket_path=sock, fallback=False)
        check(probe.alive(), "the daemon answers no ping")
        check(probe.daemon_platform == PLATFORM,
              f"the daemon runs on {probe.daemon_platform!r}, not a TPU")
        queries = _daemon_queries()
        answers, errors = [None] * len(queries), []

        def ask(k):
            try:
                c = DaemonClient(root=root, socket_path=sock, fallback=False)
                answers[k] = c.query_many(
                    [c.make_query(t, **kw) for t, kw in queries[k]])
                check(c.n_daemon_answers == 2 and c.n_fallbacks == 0,
                      f"client {k} was not answered by the daemon")
            except BaseException as e:      # noqa: BLE001 — re-raised below
                errors.append(e)

        t0 = time.perf_counter()
        threads = [threading.Thread(target=ask, args=(k,))
                   for k in range(len(queries))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        if errors:
            raise errors[0]
        stats = probe.stats()
    finally:
        stop_daemon(proc, DaemonClient(root=root, socket_path=sock,
                                       fallback=False))
    check(proc.returncode is not None, "the daemon is still running")
    c = stats["metrics"]["counters"]
    check(stats["default_backend"] == KERNEL,
          f"daemon default backend is {stats['default_backend']!r}")
    check(c.get(f"backend.run_rows{{backend={KERNEL}}}", 0) > 0,
          "the daemon dispatched nothing on the pallas kernel")
    assert_no_hidden_fallback(stats, {}, c, "daemon")
    note("daemon", clients=len(queries), queries=sum(map(len, queries)),
         dispatches=stats["n_dispatches"], wall_s=wall)
    return queries, answers, root


def phase_library(queries, answers, daemon_root: Path, work: Path):
    root = work / "library_store"
    svc = SimulationService(root=root)
    before = counters()
    for k, pair in enumerate(queries):
        local = svc.query_many([svc.make_query(t, **kw) for t, kw in pair])
        for d, lib in zip(answers[k], local):
            check(d.key == lib.key and grids_equal(d.grid, lib.grid),
                  f"client {k}: daemon and library answers differ")
            check((daemon_root / f"{d.key}.npz").read_bytes()
                  == (root / f"{lib.key}.npz").read_bytes(),
                  f"client {k}: daemon and library artifacts differ")
    assert_no_hidden_fallback(svc.stats(), before, counters(), "library")
    note("library", queries=sum(map(len, queries)), byte_equal=True)


# ---------------------------------------------------------------------------
# Phases 2-3: the main path and the other task models (this process).
# ---------------------------------------------------------------------------

def phase_service(name: str, work: Path, topo, kw: dict, n_oracle: int):
    """One query on the default backend (the pallas kernel) and on jax,
    each on a fresh store; byte-equal grids, sampled rows equal to the
    oracle."""
    runs = {}
    for backend in (None, "jax"):
        label = backend or KERNEL
        svc = SimulationService(root=work / f"{name}_{label}")
        q = svc.make_query(topo, backend=backend, **kw)
        before = counters()
        r, wall = timed_query(svc, topo, backend=backend, **kw)
        after = counters()
        assert_no_hidden_fallback(svc.stats(), before, after, name)
        used = {d["backend"] for d in svc.broker.dispatch_log}
        check(used == {label}, f"{name}: dispatched on {used}, not {label}")
        check(delta(before, after, f"backend.run_rows{{backend={label}}}")
              > 0, f"{name}: no dispatch counted on {label}")
        check(not np.asarray(r.grid.overflow).any(), f"{name}: overflow")
        runs[label] = (q.model, r)
        note(name, backend=label, rows=len(r.grid), wall_s=wall,
             events_per_s=events_per_s(r.grid, wall))
    (model, rp), (_, rj) = runs[KERNEL], runs["jax"]
    check(rp.key == rj.key and grids_equal(rp.grid, rj.grid),
          f"{name}: pallas and jax grids differ")
    compile_s = assert_kernel(model, bk.get_backend(KERNEL).grid_chunk or 8,
                              name)
    rows = sw.grid_rows(kw["W_list"], kw["lam_list"], kw["reps"])
    check(np.array_equal(rows.seed, rp.grid.seed), f"{name}: row order")
    idx = np.linspace(0, len(rows) - 1, n_oracle).astype(int)
    t0 = time.perf_counter()
    ref = bk.get_backend("oracle").run_rows(model, rows.take(idx))
    check(rows_equal(rp.grid, idx, ref), f"{name}: rows differ from oracle")
    note(name, byte_equal=True, oracle_rows=int(len(idx)),
         oracle_s=time.perf_counter() - t0, kernel_compile_s=compile_s)


def phase_models(work: Path):
    topo = one_cluster(MODEL_P, MODEL_LAM)
    common = dict(lam_list=[MODEL_LAM], reps=MODEL_REPS)
    phase_service("dag", work, topo, dict(
        common, task_model="dag", W_list=[0], max_events=1 << 20,
        dag=dag_gen.merge_sort(*MODEL_DAG)), n_oracle=2)
    phase_service("adaptive", work, topo, dict(
        common, task_model="adaptive", W_list=[MODEL_W], pool_cap=1 << 13),
        n_oracle=2)


def phase_four_chips(work: Path):
    """The main-path rows sharded over four devices (service on jax and
    pallas, and ``run_rows`` on the default backend), each byte-equal to a
    run on device 0."""
    import jax
    devs = jax.devices()
    topo = one_cluster(MAIN_P, 1)
    probe = SimulationService(root=work / "probe")
    model = probe.make_query(topo, **MAIN).model
    rows = sw.grid_rows(MAIN["W_list"], MAIN["lam_list"], MAIN["reps"])
    t0 = time.perf_counter()
    ref = bk.get_backend("jax").run_rows(model, rows, devices=devs[:1])
    note("four_chips", run="jax on device 0", rows=len(rows),
         wall_s=time.perf_counter() - t0)

    def per_device(before, after, backend):
        return {d.id: delta(before, after,
                            f"backend.device_rows{{backend={backend},"
                            f"device={d.id}}}") for d in devs}

    # No chunk may read or run from another device's copy of its inputs.
    with jax.transfer_guard_device_to_device("disallow_explicit"):
        for backend in (KERNEL, "jax"):
            svc = SimulationService(root=work / f"four_{backend}")
            before = counters()
            r, wall = timed_query(svc, topo,
                                  backend=None if backend == KERNEL
                                  else backend, **MAIN)
            after = counters()
            assert_no_hidden_fallback(svc.stats(), before, after, backend)
            used = {d["backend"] for d in svc.broker.dispatch_log}
            check(used == {backend}, f"dispatched on {used}, not {backend}")
            check(grids_equal(r.grid, ref),
                  f"{backend} over {len(devs)} devices differs from device 0")
            rows_by_dev = per_device(before, after, backend)
            check(all(v > 0 for v in rows_by_dev.values()),
                  f"{backend}: a device ran no rows: {rows_by_dev}")
            note("four_chips", run=f"service on {backend}", wall_s=wall,
                 rows_per_device=rows_by_dev, byte_equal=True,
                 events_per_s=events_per_s(r.grid, wall))
        before = counters()
        t0 = time.perf_counter()
        g = sw.run_rows(model, rows)
        wall = time.perf_counter() - t0
        rows_by_dev = per_device(before, counters(), KERNEL)
        check(grids_equal(g, ref),
              "run_rows on the default backend differs from device 0")
        check(all(v > 0 for v in rows_by_dev.values()),
              f"run_rows: a device ran no rows: {rows_by_dev}")
        note("four_chips", run=f"run_rows on {KERNEL}", wall_s=wall,
             rows_per_device=rows_by_dev, byte_equal=True)


def open_chip(n_chips: int):
    import jax
    devs = jax.devices()
    check(devs[0].platform == PLATFORM,
          f"JAX finds no TPU (platform {devs[0].platform!r})")
    check(len(devs) == n_chips, f"expected {n_chips} chips, JAX sees "
          f"{len(devs)}")
    return devs


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    bk.enable_compile_cache()
    work = Path(tempfile.mkdtemp(prefix="ws_smoke_"))
    t0 = time.perf_counter()
    try:
        if args.chips == 1:
            daemon = phase_daemon(work)
            devs = open_chip(1)
            note("device", kind=devs[0].device_kind)
            phase_library(*daemon, work)
            phase_service("main", work, one_cluster(MAIN_P, 1), MAIN,
                          n_oracle=N_ORACLE_ROWS)
            phase_models(work)
        else:
            devs = open_chip(4)
            note("device", kind=devs[0].device_kind, count=len(devs))
            phase_four_chips(work)
    except Exception as e:      # noqa: BLE001 — the verdict is the exit code
        print(f"chip_smoke: FAILED: {type(e).__name__}: {e}",
              file=sys.stderr, flush=True)
        return 1
    finally:
        shutil.rmtree(work, ignore_errors=True)
    note("done", wall_s=time.perf_counter() - t0,
         compile_cache=str(bk.compile_cache_dir()))
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
