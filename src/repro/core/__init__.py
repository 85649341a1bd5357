"""Core: the paper's Work-Stealing simulator as composable JAX modules.

Engines (paper §3): unified event+processor engine (``engine``) with
pluggable task engines (``divisible``, ``dag``, ``adaptive`` task models +
``dag_gen``), topology engine (``topology``), log engine (``gantt``),
simulator engine (``sweep``), analysis layer (``analysis``). See DESIGN.md.
"""
from repro.core.topology import (  # noqa: F401
    Topology, one_cluster, two_clusters, multi_cluster, tpu_fleet,
    UNIFORM, LOCAL_FIRST, INV_DISTANCE, ROUND_ROBIN, strategy_name,
)
from repro.core import engine  # noqa: F401
from repro.core.engine import TaskModel  # noqa: F401
from repro.core.divisible import (  # noqa: F401
    DivisibleModel, EngineConfig, Scenario, SimResult, make_scenario,
    simulate, simulate_batch, default_max_events,
)
from repro.core.engine import (  # noqa: F401
    SegmentStats, SegmentedRun, default_segment_len, simulate_segmented,
)
from repro.core.sweep import (  # noqa: F401
    run_grid, run_rows, quick_sim, GridResult, make_model, as_model,
)
from repro.core.backend import (  # noqa: F401
    BackendCapabilities, ExecutionBackend, available_backends, backend_names,
    default_backend_name, enable_compile_cache, get_backend,
    register_backend,
)
from repro.core import analysis  # noqa: F401
