"""Compile the main path for a described TPU v5e chip, with no chip attached.

The TPU's compiler is installed with JAX, so the programs the chip would run
are compiled here at their real sizes: the ``ws_sim`` Pallas kernel for each
task model at the sizes ``chip_smoke.py`` runs and at the benchmark's BOTS
sort DAG, the divisible kernel's blocks of eight scenarios on one, two and
four clusters, and the ``jax`` backend's segment step at the paper cell. This
catches what interpret mode cannot: a block shape, an op or a layout the
chip's kernel compiler refuses.

The topology is described inside a fixture (never at import), since only
one process at a time may load the TPU library; the persistent compilation
cache is off around these compiles, as an entry written for a described chip
cannot be read back.
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import dag_gen
from repro.core import divisible as dv
from repro.core import engine as eng
from repro.core import sweep as sw
from repro.core.backend import PallasBackend
from repro.core.topology import multi_cluster, one_cluster, two_clusters
from repro.kernels.ws_sim import (_host_consts, block_rows, kernel_call,
                                  ws_sim_pallas)

GRID_CHUNK = PallasBackend.grid_chunk


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _scenario_spec(n, sharding):
    scn = sw.scenario_from_rows(sw.grid_rows([1], [1], n))
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        scn)


def _paper_model():
    """The main path's model: one row of the paper's grid at p=256."""
    return sw.resolve_model(one_cluster(256, 1), "divisible",
                            W_list=[10**7], lam_list=[2, 62, 262, 482],
                            pow2_max_events=True)


def _models():
    p, lam, W = 32, 10, 200_000           # benchmarks' model_throughput
    topo = one_cluster(p, lam)
    return {
        "divisible": _paper_model(),
        "dag": sw.make_model("dag", topology=topo,
                             dag=dag_gen.merge_sort(20_000, 64),
                             max_events=1 << 20),
        "adaptive": sw.make_model("adaptive", topology=topo,
                                  pool_cap=1 << 13,
                                  max_events=dv.default_max_events(W, p, lam)),
        # the benchmark's dag_batch cell, with the derived deque bound
        "bots_sort": sw.make_model("dag", topology=one_cluster(32, 2),
                                   dag=dag_gen.bots_sort(1 << 21),
                                   max_events=1 << 20),
    }


@pytest.mark.parametrize("name", ["divisible", "dag", "adaptive",
                                  "bots_sort"])
def test_ws_sim_kernel_compiles_for_v5e(one_chip, name):
    model = _models()[name]
    fn = jax.jit(functools.partial(ws_sim_pallas, model, interpret=False,
                                   grid_chunk=GRID_CHUNK))
    lowered = fn.lower(_scenario_spec(GRID_CHUNK, one_chip))
    assert "tpu_custom_call" in lowered.as_text()
    compiled = lowered.compile()
    assert compiled.memory_analysis() is not None


@pytest.mark.parametrize("topology", [
    one_cluster(256, 1), two_clusters(256, 40),
    multi_cluster(4, 64, 10, inter="ring")], ids=lambda t: t.name)
def test_blocked_divisible_kernel_compiles_for_v5e(one_chip, topology):
    """The paper cell's divisible kernel runs eight scenarios a grid step
    (16 steps for 128 rows), reading distances from the cluster hop table:
    1×1, 2×2 and 4×4 here."""
    model = sw.resolve_model(topology, "divisible", W_list=[10**7],
                             lam_list=[2, 62, 262, 482],
                             pow2_max_events=True)
    assert block_rows(model) == 8
    fn = jax.jit(functools.partial(ws_sim_pallas, model, interpret=False,
                                   grid_chunk=GRID_CHUNK))
    compiled = fn.lower(_scenario_spec(GRID_CHUNK, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_cached_kernel_call_keeps_the_custom_call_name(one_chip):
    """The object the backend calls eagerly, lowered by itself, compiles to
    an op named ``%tpu_custom_call``: the name the benchmark's kernel
    metric matches. An outer ``jax.jit`` would name it after its function."""
    model = _paper_model()
    leaves, scn_def = jax.tree.flatten(_scenario_spec(GRID_CHUNK, one_chip))
    kc = kernel_call(model, GRID_CHUNK, False, scn_def,
                     tuple(l.dtype for l in leaves))
    consts = [jax.ShapeDtypeStruct(c.shape, c.dtype, sharding=one_chip)
              for c in _host_consts(model)]
    text = kc.call.lower(*consts, *leaves).compile().as_text()
    ops = re.findall(r"^\s*(?:ROOT )?(%\S+) = .*custom_call_target=\"tpu_custom_call\"",
                     text, re.M)
    assert ops and all(op.startswith("%tpu_custom_call") for op in ops), ops


def test_jax_segment_step_compiles_for_v5e(one_chip):
    model = _paper_model()
    n = 256
    scn = _scenario_spec(n, one_chip)
    state = jax.eval_shape(eng._init_fn(model), scn)
    state = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        state)
    step = eng._segment_step(model, eng.default_segment_len(model.max_events))
    compiled = step.lower(scn, state).compile()
    assert "tpu_custom_call" not in compiled.as_text()


def test_jax_compaction_compiles_for_v5e(one_chip):
    model = _paper_model()
    scn = _scenario_spec(256, one_chip)
    state = jax.eval_shape(eng._init_fn(model), scn)
    state = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip),
        state)
    idx = jax.ShapeDtypeStruct((128,), jnp.int32, sharding=one_chip)
    k = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    eng._compact_fn().lower(state, scn, idx, k).compile()
