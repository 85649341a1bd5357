"""Log engine export paths (paper §3.5): decode_trace, ASCII Gantt, Paje,
JSON, and Chrome-trace/Perfetto events — including the combined document of
a profiler session's wall-time spans and the simulated-time Gantt."""
import json

import numpy as np
import pytest

from repro import obs
from repro.core import divisible as dv
from repro.core import topology as T
from repro.core.gantt import (SIM_PID, ascii_gantt, decode_trace,
                              row_chrome_events, to_chrome_events, to_json,
                              to_paje, write_chrome_trace)


@pytest.fixture(scope="module")
def traced_run():
    """One traced divisible-load simulation (p=6) plus its decoded form."""
    topo = T.one_cluster(6, 7)
    cfg = dv.EngineConfig(topology=topo, log_trace=True, max_trace=4096,
                          max_events=1 << 16)
    W = 3000
    scn = dv.make_scenario(W, seed=11, lam_local=7, lam_remote=7)
    res = dv.simulate(cfg, scn)
    assert not bool(res.overflow)
    dec = decode_trace(np.asarray(res.trace), int(res.n_trace), 6, W,
                       int(res.makespan))
    return res, dec, W


def test_decode_trace_structure(traced_run):
    res, dec, W = traced_run
    makespan = int(res.makespan)
    assert set(dec["runs"]) == set(range(6))
    assert dec["runs"][0], "proc 0 executes the initial load"
    for proc, intervals in dec["runs"].items():
        for t0, t1 in intervals:
            assert 0 <= t0 <= t1 <= makespan
    # work moved: at least one successful steal decoded into an arrow
    assert any("amount" in a for a in dec["arrows"])
    assert any("victim" in a for a in dec["arrows"])
    for a in dec["arrows"]:
        assert 0 <= a["t"] <= makespan
        assert 0 <= a["thief"] < 6


def test_ascii_gantt(traced_run):
    res, dec, W = traced_run
    chart = ascii_gantt(dec["runs"], int(res.makespan), width=60)
    lines = chart.splitlines()
    assert len(lines) == 7                       # 6 processors + time axis
    assert lines[0].startswith("P0")
    assert "#" in lines[0]                       # proc 0 ran
    assert f"t={int(res.makespan)}" in lines[-1]


def test_paje_export(traced_run):
    res, dec, W = traced_run
    paje = to_paje(dec["runs"], int(res.makespan))
    assert "%EventDef PajeDefineContainerType" in paje
    assert '6 0.0 P5 CT_Proc 0 "P5"' in paje     # every container declared
    set_states = [l for l in paje.splitlines() if l.startswith("10 ")]
    n_intervals = sum(len(v) for v in dec["runs"].values())
    assert len(set_states) >= 2 * n_intervals    # RUN+IDLE per interval
    assert any('"RUN"' in l for l in set_states)
    assert any('"IDLE"' in l for l in set_states)
    # state-change events are time-sorted
    times = [float(l.split()[1]) for l in set_states]
    assert times == sorted(times)


def test_json_export(traced_run):
    res, dec, W = traced_run
    doc = json.loads(to_json(res, 6, W, extra={"note": "test"}))
    assert doc["W"] == W and doc["p"] == 6
    assert doc["makespan"] == int(res.makespan)
    assert doc["note"] == "test"
    assert len(doc["executed"]) == 6
    assert sum(doc["executed"]) == W             # all work accounted for


def _pairing(events):
    """Per-(pid, tid) B/E stack pairing; returns matched (name, t0, t1)."""
    stacks, out = {}, []
    for ev in events:
        if ev["ph"] not in ("B", "E"):
            continue
        stack = stacks.setdefault((ev["pid"], ev["tid"]), [])
        if ev["ph"] == "B":
            stack.append(ev)
        else:
            assert stack, "E without matching B"
            b = stack.pop()
            assert b["name"] == ev["name"]
            assert b["ts"] <= ev["ts"]
            out.append((ev["name"], b["ts"], ev["ts"]))
    for stack in stacks.values():
        assert not stack, "unclosed B events"
    return out


def test_chrome_events(traced_run):
    res, dec, W = traced_run
    events = to_chrome_events(dec, int(res.makespan))
    json.dumps(events)                           # JSON-serializable
    meta = [e for e in events if e["ph"] == "M"]
    assert any(e["name"] == "process_name" for e in meta)
    assert sum(e["name"] == "thread_name" for e in meta) == 6
    assert all(e["pid"] == SIM_PID for e in events)
    matched = _pairing(events)
    assert len(matched) == sum(len(v) for v in dec["runs"].values())
    assert all(name == "RUN" for name, _, _ in matched)
    instants = [e for e in events if e["ph"] == "i"]
    assert len(instants) == len(dec["arrows"])
    assert {e["name"] for e in instants} <= {"steal", "steal_req"}


def test_combined_wall_and_sim_timeline(traced_run, tmp_path):
    """One Perfetto document carrying the host's wall-time spans, as the
    profiler's session wrote them, and the engine's simulated-time Gantt
    (``SIM_PID``) as separate track groups."""
    res, dec, W = traced_run
    with obs.trace_to(tmp_path / "trace") as tr:
        with obs.span("service.query", n_queries=1):
            with obs.span("backend.run_rows", backend="jax"):
                pass
    sim = row_chrome_events(np.asarray(res.trace), int(res.n_trace), 6, W,
                            int(res.makespan))
    path = write_chrome_trace(tmp_path / "combined.json",
                              tr.chrome_events(), sim)
    doc = json.loads(path.read_text())
    events = doc["traceEvents"]
    assert doc["displayTimeUnit"] == "ms"
    host = {e["pid"] for e in events if e.get("name") == "service.query"}
    pids = {e["pid"] for e in events}
    assert len(host) == 1 and SIM_PID not in host
    assert {SIM_PID} | host <= pids
    _pairing(events)                             # every B/E matched
    # per-(pid, tid) timestamps are monotonic in the merged document
    last = {}
    for ev in events:
        if "ts" not in ev:
            continue
        key = (ev["pid"], ev["tid"])
        assert ev["ts"] >= last.get(key, 0.0)
        last[key] = ev["ts"]
    spans = {e["name"] for e in events if e["ph"] == "X"}
    assert {"service.query", "backend.run_rows"} <= spans
    assert "RUN" in {e["name"] for e in events if e["ph"] == "B"}

