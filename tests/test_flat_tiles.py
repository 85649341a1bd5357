"""Task-indexed arrays as (R, 128) tiles (``engine.flat_tiles``), and the
DAG kernel that reads them, against the oracle across the tile edges.

``read_flat``/``write_flat`` have two forms: XLA's gather and scatter (the
``jax`` backend) and, under ``engine.select_forms()``, a one-hot select over
the flat iota (the Pallas kernel body). Both must agree with numpy on the
flat array at every edge of a row and of a vreg, on a padded slot, and on
an index outside the tile (-1, no task), which reads 0 and writes nothing.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core import dag as dg
from repro.core import dag_gen as gen
from repro.core import engine as eng
from repro.core import topology as T
from repro.core.oracle import simulate_dag_oracle
from repro.kernels.ws_sim import ws_sim_pallas

N = 1500                       # 12 rows of data, 16 rows of tile
VALUES = np.arange(N, dtype=np.int32) * 7 + 3


@pytest.mark.parametrize("n,rows", [(0, 8), (1, 8), (128, 8), (1024, 8),
                                    (1025, 16), (27646, 216), (38225, 304)])
def test_flat_tiles_fill_whole_vregs(n, rows):
    a = np.arange(n, dtype=np.int64) + 1
    tile = eng.flat_tiles(a)
    assert tile.shape == (rows, eng.LANES) and tile.dtype == np.int32
    flat = tile.reshape(-1)
    np.testing.assert_array_equal(flat[:n], a)
    assert not flat[n:].any()


def _forms(fn, *args):
    """``fn`` under XLA's forms and under the kernel body's select forms."""
    xla = fn(*args)
    with eng.select_forms():
        sel = fn(*args)
    return np.asarray(xla), np.asarray(sel)


EDGES = [0, 127, 128, 1023, 1024, N - 1, N, 16 * 128 - 1, -1]


@pytest.mark.parametrize("k", EDGES, ids=lambda k: f"k{k}")
def test_read_flat_agrees_with_numpy(k):
    tile = jnp.asarray(eng.flat_tiles(VALUES))
    want = VALUES[k] if 0 <= k < N else 0
    xla, sel = _forms(eng.read_flat, tile, jnp.int32(k))
    assert int(xla) == int(sel) == want


@pytest.mark.parametrize("k", EDGES, ids=lambda k: f"k{k}")
def test_write_flat_agrees_with_numpy(k):
    tile = jnp.asarray(eng.flat_tiles(VALUES))
    want = eng.flat_tiles(VALUES).reshape(-1)
    if k >= 0:
        want[k] = -5
    xla, sel = _forms(eng.write_flat, tile, jnp.int32(k), -5)
    np.testing.assert_array_equal(xla.reshape(-1), want)
    np.testing.assert_array_equal(sel.reshape(-1), want)


@pytest.mark.parametrize("make,p", [
    (lambda: gen.binary_tree(6), 4),       # n = 63, below one row
    (lambda: gen.chain(128), 2),           # n = 128: one row exactly
    (lambda: gen.chain(129), 2),           # n = 129; E = 128
    (lambda: gen.binary_tree(11), 4),      # n = 2047, past one vreg
], ids=["n63", "n128", "n129", "n2047"])
def test_dag_kernel_bit_exact_across_tile_edges(make, p):
    """The kernel (interpret mode) and the jax backend's engine give the
    oracle's rows bit for bit, whichever tile row and vreg the DAG's last
    task, last pointer and last edge fall in."""
    dagf = make()
    topo = T.one_cluster(p, 3)
    cfg = dg.DagEngineConfig(topology=topo, dag=dagf, max_events=1 << 18)
    seeds = np.arange(3, dtype=np.uint32) + 5
    scn = eng.batch_scenarios(0, seeds, lam=3)
    got = ws_sim_pallas(cfg, scn, interpret=True)
    expect = dg.simulate_dag_batch(cfg, scn)
    for field in got._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(got, field)),
            np.asarray(getattr(expect, field)), err_msg=field)
    assert not np.asarray(got.overflow).any()
    assert (np.asarray(got.n_completed) == dagf.n).all()
    for k, seed in enumerate(seeds):
        o = simulate_dag_oracle(topo, dagf, int(seed))
        for field in ("makespan", "n_requests", "n_success", "total_idle"):
            assert int(getattr(got, field)[k]) == o[field], field
        for field in ("executed", "tasks_run"):
            np.testing.assert_array_equal(np.asarray(getattr(got, field))[k],
                                          o[field].astype(np.int32))
