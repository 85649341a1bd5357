"""Simulated events of every query answered in the window, over the time
from the window's start to the last answer."""


def read(run):
    return run.n_events / run.window_s if run.answers else None
