"""(8, 128) 32-bit vreg tiles that one scenario's state takes in the DAG
kernel's event loop under Mosaic's layout (a scalar one, a vector of m one
sublane a vreg, m/128; a 2-D array whole vregs), as the program records it
when it builds the kernel: the gauge ``ws_sim.state_tiles{task_model=dag}``
of the run's process. Every event selects across this state, so it sets
how much each event passes. None where the program keeps no such gauge."""


def read(run):
    from repro import obs
    found = [g.value for labels, g in obs.REGISTRY.find(
        "gauge", "ws_sim.state_tiles") if labels.get("task_model") == "dag"]
    return found[0] if found and run.answers else None
