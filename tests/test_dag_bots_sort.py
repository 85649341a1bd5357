"""BOTS sort's task DAG (``dag_gen.bots_sort``) and the deque bound derived
from a DAG's shape (``TaskDag.deque_bound``), which the DAG task model takes
as its default deque capacity: the engine and the Pallas kernel (interpret
mode) against the unbounded serial oracle, bit for bit."""
import itertools

import numpy as np
import pytest

from repro.core import dag as dg
from repro.core import dag_gen as gen
from repro.core import engine as eng
from repro.core import topology as T
from repro.core.oracle import simulate_dag_oracle
from repro.core.sweep import make_model
from repro.kernels.ws_sim import ws_sim_pallas

#: A BOTS DAG small enough for interpret mode, whose merges recurse.
SMALL = dict(n_elems=1 << 11, merge_cutoff=64, quick_cutoff=64)
SEEDS = np.array([1, 2, 3, 2**31 + 7], np.uint32)


def test_bots_sort_shape():
    dag = gen.bots_sort(1 << 21)
    assert (dag.n, dag.child_idx.size, dag.max_children) == (27646, 38225, 4)
    assert list(dag.sources) == [0]
    assert int((np.diff(dag.child_ptr) == 0).sum()) == 1     # one sink
    assert dag.total_work // dag.critical_path() == 759
    assert dag.deque_bound() == 421 < dag.deque_bound(owner_lifo=False)


def test_default_cap_is_the_derived_bound():
    dag = gen.bots_sort(**SMALL)
    topo = T.one_cluster(4, 2)
    for lifo in (True, False):
        cfg = dg.DagEngineConfig(topology=topo, dag=dag, owner_lifo=lifo)
        assert cfg.cap == dag.deque_bound(lifo) < dag.n
    assert dg.DagEngineConfig(topology=topo, dag=dag, deque_cap=9).cap == 9
    model = make_model("dag", topology=topo, dag=dag)
    assert model.cfg.deque_cap is None and model.cfg.cap == dag.deque_bound()


@pytest.mark.parametrize("dag,lifo,fifo", [
    (gen.chain(6), 1, 1),
    (gen.binary_tree(5), 5, 16),          # H = 4; 16 leaves
    (gen.fork_join(4), 7, 8),             # H = 6; 8 fork leaves
])
def test_deque_bound_of_known_shapes(dag, lifo, fifo):
    assert dag.deque_bound() == lifo
    assert dag.deque_bound(owner_lifo=False) == fifo


def test_fifo_bound_is_reached_and_a_smaller_cap_overflows():
    """One processor runs a binary tree breadth first under FIFO and holds
    its whole last level, the FIFO bound; one slot less halts the row."""
    dag = gen.binary_tree(5)
    topo = T.one_cluster(1, 2)
    assert simulate_dag_oracle(topo, dag, 1, owner_lifo=False)[
        "max_deque"] == dag.deque_bound(owner_lifo=False)
    scn = eng.make_scenario(0, 1, lam=2)
    for cap, overflow in ((None, False), (15, True)):
        cfg = dg.DagEngineConfig(topology=topo, dag=dag, owner_lifo=False,
                                 deque_cap=cap, max_events=1 << 12)
        assert bool(dg.simulate_dag(cfg, scn).overflow) is overflow


@pytest.mark.parametrize("lam", [2, 262])
@pytest.mark.parametrize("lifo", [True, False], ids=["lifo", "fifo"])
@pytest.mark.parametrize("backend", ["jax", "pallas_interpret"])
def test_backends_match_oracle_with_derived_cap(backend, lifo, lam):
    dag = gen.bots_sort(**SMALL)
    topo = T.one_cluster(4, lam)
    cfg = dg.DagEngineConfig(topology=topo, dag=dag, owner_lifo=lifo,
                             max_events=1 << 16)
    scn = eng.batch_scenarios(0, SEEDS, lam=lam)
    if backend == "jax":
        got = dg.simulate_dag_batch(cfg, scn)
    else:
        got = ws_sim_pallas(cfg, scn, interpret=True)
    assert not np.asarray(got.overflow).any()
    for k, seed in enumerate(SEEDS):
        o = simulate_dag_oracle(topo, dag, int(seed), owner_lifo=lifo)
        assert o["max_deque"] <= cfg.cap
        for f in ("makespan", "n_events", "n_requests", "n_success",
                  "n_fail", "total_idle", "startup_end", "n_completed"):
            assert int(np.asarray(getattr(got, f))[k]) == o[f], (f, k)
        for f in ("executed", "tasks_run"):
            assert np.array_equal(np.asarray(getattr(got, f))[k],
                                  o[f].astype(np.int32)), (f, k)


def test_ring_deque_equals_dense_deque():
    """Rings of the derived bound, and of the longest deque the rows reach
    (which wraps round many times), give what one slot per task gives."""
    dag = gen.bots_sort(**SMALL)
    topo = T.one_cluster(4, 62)
    scn = eng.batch_scenarios(0, SEEDS, lam=62)
    tight = max(simulate_dag_oracle(topo, dag, int(s))["max_deque"]
                for s in SEEDS)
    assert tight < dag.deque_bound()
    runs = [dg.simulate_dag_batch(dg.DagEngineConfig(
        topology=topo, dag=dag, deque_cap=cap, max_events=1 << 16), scn)
        for cap in (dag.n, None, tight)]
    assert not np.asarray(runs[0].overflow).any()
    for run, field in itertools.product(runs[1:], runs[0]._fields):
        np.testing.assert_array_equal(np.asarray(getattr(run, field)),
                                      np.asarray(getattr(runs[0], field)),
                                      err_msg=field)
