"""KiB of state that one scenario carries through the DAG kernel's event
loop (its ``CoreState`` and the DAG model's deques and predecessor counts),
as the program records it when it builds the kernel: the gauge
``ws_sim.state_bytes{task_model=dag}`` of the run's process. None where the
program keeps no such gauge."""


def read(run):
    from repro import obs
    found = [g.value for labels, g in obs.REGISTRY.find(
        "gauge", "ws_sim.state_bytes") if labels.get("task_model") == "dag"]
    return found[0] / 1024 if found and run.answers else None
