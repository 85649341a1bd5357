"""Broker dispatches in the window per answer: the replication rounds a
certified answer costs."""


def read(run):
    if not run.answers:
        return None
    return run.counter_delta("broker.dispatches") / len(run.answers)
