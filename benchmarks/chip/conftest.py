"""Sizes shared by the benchmark's tests, which run cells whole on the CPU.

A test that runs a cell whole cuts its configuration to a size the CPU runs
in seconds, from a table ``SMALL`` of task model to the keys it changes.
``test_check.py`` keeps that table for the task models it was written with;
``small_sizes`` adds the task models that came after it, so that every
configuration of ``BENCHMARK.json`` is cut, and none runs at its chip size.
"""
from __future__ import annotations

import pytest

#: Each later task model's configuration cut to a size the CPU runs in
#: seconds: BOTS sort on 4 processors, N=2048 and both cutoffs 64.
SMALL = {"bots_sort": dict(p=4, n_elems=2048, merge_cutoff=64,
                           quick_cutoff=64, n_tasks=430)}


@pytest.fixture(autouse=True, scope="module")
def small_sizes(request):
    """``SMALL``, also added to the test module's own table of that name
    for the task models that table lacks."""
    table = getattr(request.module, "SMALL", None)
    if isinstance(table, dict):
        for model, cut in SMALL.items():
            table.setdefault(model, cut)
    return SMALL
