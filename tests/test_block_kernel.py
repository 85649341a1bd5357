"""The divisible kernel's blocks of scenarios (one per sublane of a grid
step) and the cluster hop table the event core reads distances from:
interpret mode at small p, bit-exact with ``engine.simulate_batch`` and the
serial oracle."""
import jax
import numpy as np
import pytest

from repro import obs
from repro.core import backend as be
from repro.core import dag_gen
from repro.core import engine as eng
from repro.core import sweep as sw
from repro.core import topology as T
from repro.kernels import ws_sim
from repro.service import wire

P = 16
TOPOLOGIES = {
    "one_cluster": T.one_cluster(P, 3),
    "two_clusters": T.two_clusters(P, 40, lam_local=2),
    "multi_cluster": T.multi_cluster(4, 4, 9, inter="ring"),
}
STRATEGIES = (T.UNIFORM, T.LOCAL_FIRST, T.INV_DISTANCE, T.ROUND_ROBIN)
FIELDS = ("makespan", "n_requests", "n_success", "n_fail", "total_idle",
          "startup_end", "overflow")


def _divisible(topo, max_events=1 << 14):
    return sw.make_model("divisible", topology=topo, max_events=max_events)


def _counter(name):
    return sum(c.value for _, c in obs.REGISTRY.find("counter", name))


def _assert_rows_exact(model, rows, ev_budget=None, remote_prob=0.3,
                       oracle=True):
    """The blocked kernel's rows (through the pallas_interpret backend)
    equal the engine's and the oracle's, field for field."""
    got = be.get_backend("pallas_interpret").run_rows(
        model, rows, remote_prob, ev_budget)
    res = eng.simulate_batch(model, sw.scenario_from_rows(
        rows, remote_prob=remote_prob, ev_budget=ev_budget))
    refs = [sw.grid_from_result(model.p, rows, res)]
    if oracle:
        refs.append(be.get_backend("oracle").run_rows(
            model, rows, remote_prob, ev_budget))
    for ref in refs:
        for f in FIELDS:
            np.testing.assert_array_equal(getattr(got, f), getattr(ref, f),
                                          err_msg=f)
        for f in ("n_events", "executed"):
            np.testing.assert_array_equal(got.extras[f], ref.extras[f],
                                          err_msg=f)
    return got


@pytest.mark.parametrize("strategy", STRATEGIES,
                         ids=[T.strategy_name(s) for s in STRATEGIES])
@pytest.mark.parametrize("topo", list(TOPOLOGIES))
def test_blocked_kernel_bit_exact(topo, strategy):
    """Every strategy on one, two and several clusters: 12 rows, so the
    second block holds four zero-budget pad rows."""
    model = _divisible(TOPOLOGIES[topo].with_strategy(strategy))
    assert ws_sim.block_rows(model) == ws_sim.BLOCK == 8
    rows = sw.grid_rows([300, 2500], [(1, 4), (2, 30)], 3, seed0=7)
    # On one cluster a LOCAL_FIRST steal that goes remote finds no victim:
    # the engine then takes processor 0 and the oracle i + 1, so there the
    # kernel is held to the engine alone.
    oracle = not (topo == "one_cluster" and strategy == T.LOCAL_FIRST)
    got = _assert_rows_exact(model, rows, oracle=oracle)
    assert not got.overflow.any()


@pytest.mark.parametrize("G,grid_chunk", [(13, None), (21, 8), (11, 4)])
def test_blocked_kernel_grid_not_a_multiple_of_the_block(G, grid_chunk):
    """A grid, or a chunk, that eight does not divide is padded with
    zero-budget rows, which are dropped."""
    model = _divisible(TOPOLOGIES["two_clusters"])
    rows = sw.grid_rows([700], [(1, 3), (2, 20), (1, 90)], 7, seed0=3)
    rows = rows.slice(0, G)
    scn = sw.scenario_from_rows(rows)
    got = ws_sim.ws_sim_pallas(model, scn, interpret=True,
                               grid_chunk=grid_chunk)
    ref = eng.simulate_batch(model, scn)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
        assert a.shape[0] == G
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_one_block_mixes_latencies_budgets_and_an_overflow():
    """Six rows in one block of eight: four latencies, per-row budgets,
    one row stopped by its budget while the others finish, and two
    zero-budget pad rows. Each row runs its own events alone."""
    model = _divisible(TOPOLOGIES["one_cluster"])
    rows = sw.grid_rows([400, 3000], [2, 62, 262], 1, seed0=11)
    budget = np.array([eng.INF32, 40, eng.INF32, 5000, eng.INF32, 3])
    row_ev, slot_ev = (_counter("ws_sim.block_row_events"),
                       _counter("ws_sim.block_slot_events"))
    got = _assert_rows_exact(model, rows, ev_budget=budget)
    assert got.overflow.tolist() == [False, True, False, False, False, True]
    n = got.extras["n_events"]
    assert n[1] == 40 and n[5] == 3
    assert _counter("ws_sim.block_row_events") - row_ev == n.sum()
    assert _counter("ws_sim.block_slot_events") - slot_ev == 8 * n.max()


def test_block_rows_follows_what_a_scenario_carries():
    """Eight for divisible load (scalars and int32[p] vectors); one where a
    model reads shared arrays (DAG), carries a table a row (adaptive's pool
    and deques) or logs a trace."""
    topo = T.one_cluster(8, 2)
    assert ws_sim.block_rows(_divisible(topo)) == 8
    assert ws_sim.block_rows(sw.make_model(
        "divisible", topology=topo, log_trace=True, max_trace=64)) == 1
    assert ws_sim.block_rows(sw.make_model(
        "dag", topology=topo, dag=dag_gen.binary_tree(3))) == 1
    assert ws_sim.block_rows(sw.make_model("adaptive", topology=topo)) == 1


@pytest.mark.parametrize("grid_chunk,n_events,slots", [
    (None, [5, 1, 0, 9, 2, 2, 2, 2, 7], 8 * 9 + 8 * 7),
    (4, [5, 1, 0, 9, 2, 2, 2, 2, 7], 8 * 9 + 8 * 2 + 8 * 7),
])
def test_count_blocks(grid_chunk, n_events, slots):
    """Slot events are B times each block's largest row, blocks laid from
    the start of every grid chunk; a B = 1 model wastes none."""
    before = (_counter("ws_sim.block_row_events"),
              _counter("ws_sim.block_slot_events"))
    ws_sim.count_blocks(_divisible(T.one_cluster(4, 1)), n_events,
                        grid_chunk)
    dag = sw.make_model("dag", topology=T.one_cluster(4, 1),
                        dag=dag_gen.binary_tree(2))
    ws_sim.count_blocks(dag, n_events, grid_chunk)
    assert _counter("ws_sim.block_row_events") - before[0] == \
        2 * sum(n_events)
    assert _counter("ws_sim.block_slot_events") - before[1] == \
        slots + sum(n_events)


def _builders():
    yield T.one_cluster(6, 4)
    yield T.two_clusters(7, 30)
    yield T.two_clusters(8, 30, split=3)
    for inter in ("complete", "ring", "line", "star"):
        yield T.multi_cluster(5, 3, 11, inter=inter)
    yield T.tpu_fleet(3, 4, dcn_delay=50, inter="line")


@pytest.mark.parametrize("topo", list(_builders()), ids=lambda t: t.name)
def test_cluster_hops_carry_every_cross_cluster_hop(topo):
    cid, hops, table = topo.cluster_id, topo.hops, topo.cluster_hops
    k = topo.n_clusters
    assert table.shape == (k, k) and table.dtype == np.int32
    assert (np.diag(table) == 0).all()
    cross = cid[:, None] != cid[None, :]
    assert (hops[cross] == table[cid[:, None], cid[None, :]][cross]).all()
    back = wire.decode_topology(wire.encode_topology(topo))
    assert back == topo
    np.testing.assert_array_equal(back.cluster_hops, table)
    flat = eng.cluster_hop_table(back)
    assert flat.shape == (k * k,)


def test_topology_without_a_cluster_hop_table_is_refused():
    """Hops between two clusters that depend on more than the clusters
    cannot be read from a k×k table: building a model refuses them."""
    good = T.two_clusters(6, 10)
    hops = good.hops.copy()
    hops[0, 4] = hops[4, 0] = 2
    bad = T.Topology(good.cluster_id, hops, name="uneven")
    with pytest.raises(ValueError, match="not a function of the clusters"):
        bad.cluster_hops
    with pytest.raises(ValueError, match="not a function of the clusters"):
        _divisible(bad)
    with pytest.raises(ValueError, match="not a function of the clusters"):
        sw.make_model("dag", topology=bad, dag=dag_gen.binary_tree(2))
