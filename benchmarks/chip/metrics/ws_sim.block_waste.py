"""Share of the kernel's row slots that ran no event of their own, in the
window: 100 × (1 − row events / slot events). A grid step runs a block of
scenarios, and the block steps until its last row ends, so each block costs
its size times its largest row's events (counters
``ws_sim.block_row_events`` and ``ws_sim.block_slot_events``, counted from
each dispatch's rows on the host). ``ws_sim.block_waste.ci`` reads it in the
certified cell. None where the program keeps no such counters."""


def read(run):
    slots = run.counter_delta("ws_sim.block_slot_events")
    if not slots:
        return None
    return 100.0 * (1.0 - run.counter_delta("ws_sim.block_row_events") / slots)
