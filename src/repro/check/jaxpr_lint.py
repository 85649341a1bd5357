"""Pass 1 — jaxpr/compile hazard analyzer.

Traces every available registered backend's dispatch program (the exact
``_simulate`` / ``ws_sim_pallas`` entry the broker dispatch path jits) for
each task model on a tiny one-cluster topology, then scans the jaxprs:

``retrace.static_args``
    The jit caches are keyed on the model object (``lru_cache`` over
    ``(model, seg_len)``), so every cfg field must be hashable and exact
    (ints/bools/str/None). A float or unhashable field either breaks the
    cache key outright or weakly retraces per call; floats additionally
    poison store keys (see ``store.canonical_model``).

``retrace.shape_branch``
    The traced program's *structure* (recursive primitive signature,
    shapes stripped) must be identical across batch widths — a structural
    difference means a Python branch on a traced shape, i.e. one compile
    cache entry per batch width instead of per (model, width-bucket).

``host_sync.callback``
    No host callbacks (``pure_callback`` / ``io_callback`` / ``debug_*``)
    inside the dispatch program: each one is a device->host sync point
    that serializes the broker's batched dispatch.

``dtype.f64``
    No float64 anywhere in the program: the engine is integer-time with
    f32 aggregates; an f64 aval means an accidental weak-type promotion
    that silently doubles memory and diverges bitwise from the oracle.

``pallas.grid_chunk``
    Backend grid chunks headed for ``ws_sim_pallas`` must be powers of
    two (see :func:`repro.kernels.ws_sim.grid_shape_hazards`): each
    distinct padded grid shape compiles a distinct Mosaic program.

``pallas.mosaic_op``
    The event core traced as the Pallas kernel body (under
    ``engine.select_forms``) must hold no ``scatter``, ``scatter-add``,
    ``dynamic_slice``, ``dynamic_update_slice`` or integer ``argmin`` /
    ``argmax``: the TPU's kernel compiler has no lowering for them, so the
    kernel would fail only on the chip.

``donation.ungated``
    AST rule over ``core/engine.py``: any literal non-empty
    ``donate_argnums=`` must be behind the ``_donate_ok()`` platform gate
    — CPU XLA ignores donation and warns per dispatch. A runtime
    consistency probe double-checks ``_donate_ok()`` against the actual
    platform.
"""
from __future__ import annotations

import ast
import dataclasses
import functools
from pathlib import Path
from typing import Iterable, List, Optional, Tuple

import jax

from repro.check import Finding, repo_root

PASS = "jaxpr"

CALLBACK_PRIMITIVES = frozenset({
    "pure_callback", "io_callback", "debug_callback", "debug_print",
    "host_callback", "outside_call",
})

#: Primitives the TPU's kernel compiler cannot lower in the event core;
#: ``argmin``/``argmax`` only on integer or bool operands.
MOSAIC_UNLOWERABLE = frozenset({
    "scatter", "scatter-add", "dynamic_slice", "dynamic_update_slice"})
MOSAIC_FLOAT_ONLY = frozenset({"argmin", "argmax"})

#: Batch widths compared by the shape-branch rule. Distinct pow2 widths so
#: a legitimate pow2-padding branch would not fire it.
SIGNATURE_WIDTHS = (4, 8)


def tiny_models() -> List[Tuple[str, object]]:
    """One tiny configured model per registered task-model kind."""
    from repro.core import dag_gen, sweep
    from repro.core.topology import one_cluster

    topo = one_cluster(4, 1)
    return [
        ("divisible", sweep.make_model("divisible", topology=topo,
                                       max_events=256)),
        ("dag", sweep.make_model("dag", topology=topo,
                                 dag=dag_gen.binary_tree(3), max_events=256)),
        ("adaptive", sweep.make_model("adaptive", topology=topo,
                                      max_events=256)),
    ]


def _tiny_scenario(n: int):
    from repro.core import sweep
    rows = sweep.grid_rows([64], [1], n)
    return sweep.scenario_from_rows(rows, remote_prob=0.25, ev_budget=256)


def trace_model(model, n: int):
    """ClosedJaxpr of the vmapped event core at batch width ``n`` — the
    program the jax backend's dispatch path compiles."""
    from repro.core import engine as eng
    fn = jax.vmap(functools.partial(eng._simulate, model))
    return jax.make_jaxpr(fn)(_tiny_scenario(n))


def trace_pallas(model, n: int):
    """ClosedJaxpr of the Pallas kernel dispatch (interpret lowering traces
    the same ``pallas_call`` the TPU path emits)."""
    from repro.kernels import ws_sim
    fn = functools.partial(ws_sim.ws_sim_pallas, model, interpret=True)
    return jax.make_jaxpr(fn)(_tiny_scenario(n))


# ---------------------------------------------------------------------------
# jaxpr scanning primitives
# ---------------------------------------------------------------------------

def _sub_jaxprs(eqn) -> list:
    subs = []
    for v in eqn.params.values():
        for x in (v if isinstance(v, (list, tuple)) else (v,)):
            if hasattr(x, "jaxpr"):        # ClosedJaxpr
                subs.append(x.jaxpr)
            elif hasattr(x, "eqns"):       # raw Jaxpr (e.g. pallas_call)
                subs.append(x)
    return subs


def iter_eqns(jaxpr) -> Iterable:
    """Depth-first over every equation, descending into sub-jaxprs."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn):
            yield from iter_eqns(sub)


def structural_signature(closed) -> Tuple[str, ...]:
    """Primitive-name sequence of the whole program, shapes stripped —
    equal signatures mean equal program *structure*."""
    return tuple(eqn.primitive.name for eqn in iter_eqns(closed.jaxpr))


def scan_jaxpr(closed, where: str, symbol: str) -> List[Finding]:
    """Callback + float64 scan of one ClosedJaxpr."""
    out: List[Finding] = []
    seen_cb, seen_f64 = set(), set()
    for eqn in iter_eqns(closed.jaxpr):
        name = eqn.primitive.name
        if (name in CALLBACK_PRIMITIVES or "callback" in name) \
                and name not in seen_cb:
            seen_cb.add(name)
            out.append(Finding(
                pass_name=PASS, rule="host_sync.callback", where=where,
                symbol=symbol,
                message=f"primitive {name!r} in the dispatch program is a "
                f"host sync point; the broker's batched dispatch "
                f"serializes on it"))
        for var in (*eqn.invars, *eqn.outvars):
            aval = getattr(var, "aval", None)
            dt = getattr(aval, "dtype", None)
            if dt is not None and str(dt) == "float64" and name not in seen_f64:
                seen_f64.add(name)
                out.append(Finding(
                    pass_name=PASS, rule="dtype.f64", where=where,
                    symbol=symbol,
                    message=f"float64 aval reaches primitive {name!r}: "
                    f"unintended x64 promotion diverges bitwise from the "
                    f"f32 oracle"))
    return out


# ---------------------------------------------------------------------------
# Per-rule checks
# ---------------------------------------------------------------------------

def static_arg_findings(name: str, model) -> List[Finding]:
    out: List[Finding] = []
    try:
        hash(model)
    except TypeError:
        out.append(Finding(
            pass_name=PASS, rule="retrace.static_args",
            where="core.engine jit cache", symbol=name,
            message=f"model {name!r} is unhashable; the per-model jit "
            f"caches (lru_cache keyed on the model) cannot hold it"))
        return out
    for field in dataclasses.fields(model.cfg):
        value = getattr(model.cfg, field.name)
        if isinstance(value, float):
            out.append(Finding(
                pass_name=PASS, rule="retrace.static_args",
                where="core.engine jit cache", symbol=name,
                message=f"cfg field {field.name!r} is a float: weak-typed "
                f"static arg (retrace + inexact store keys); encode it as "
                f"a fixed-point int like remote_prob_u32"))
        else:
            try:
                hash(value)
            except TypeError:
                out.append(Finding(
                    pass_name=PASS, rule="retrace.static_args",
                    where="core.engine jit cache", symbol=name,
                    message=f"cfg field {field.name!r} "
                    f"({type(value).__name__}) is unhashable: it breaks "
                    f"the jit cache key"))
    return out


def shape_branch_findings(name: str, model) -> List[Finding]:
    sigs = {n: structural_signature(trace_model(model, n))
            for n in SIGNATURE_WIDTHS}
    a, b = (sigs[n] for n in SIGNATURE_WIDTHS)
    if a == b:
        return []
    return [Finding(
        pass_name=PASS, rule="retrace.shape_branch",
        where="core.engine._simulate", symbol=name,
        message=f"dispatch program structure differs between batch widths "
        f"{SIGNATURE_WIDTHS[0]} and {SIGNATURE_WIDTHS[1]} "
        f"({len(a)} vs {len(b)} primitives): a Python branch on a traced "
        f"shape forces one compile per batch width")]


def kernel_op_findings(closed, symbol: str) -> List[Finding]:
    """Scan the body of every ``pallas_call`` in ``closed`` for primitives
    the TPU's kernel compiler cannot lower."""
    out: List[Finding] = []
    seen = set()
    for eqn in iter_eqns(closed.jaxpr):
        if eqn.primitive.name != "pallas_call":
            continue
        for op in iter_eqns(eqn.params["jaxpr"]):
            name = op.primitive.name
            if name in MOSAIC_FLOAT_ONLY:
                dt = op.invars[0].aval.dtype
                if jax.numpy.issubdtype(dt, jax.numpy.floating):
                    continue
                name = f"{name}[{dt}]"
            elif name not in MOSAIC_UNLOWERABLE:
                continue
            if name in seen:
                continue
            seen.add(name)
            out.append(Finding(
                pass_name=PASS, rule="pallas.mosaic_op",
                where="kernels.ws_sim._kernel", symbol=symbol,
                message=f"primitive {name!r} in the kernel body has no "
                f"TPU kernel lowering; route it through the engine's "
                f"select-form helpers"))
    return out


def pallas_grid_findings() -> List[Finding]:
    from repro.core import backend as be
    from repro.kernels import ws_sim

    out: List[Finding] = []
    for bname in be.backend_names():
        b = be.get_backend(bname)
        chunk = getattr(b, "grid_chunk", None)
        if chunk is None:
            continue
        for hazard in ws_sim.grid_shape_hazards(chunk):
            out.append(Finding(
                pass_name=PASS, rule="pallas.grid_chunk",
                where="kernels.ws_sim.ws_sim_pallas", symbol=bname,
                message=hazard))
    return out


def lint_donation_source(src: str, filename: str) -> List[Finding]:
    """AST scan: literal non-empty ``donate_argnums=`` outside the
    ``_donate_ok()`` gate (testable on synthetic sources)."""
    tree = ast.parse(src, filename=filename)
    out: List[Finding] = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        for kw in node.keywords:
            if kw.arg != "donate_argnums":
                continue
            v = kw.value
            literal_nonempty = (
                isinstance(v, (ast.Tuple, ast.List)) and len(v.elts) > 0) \
                or (isinstance(v, ast.Constant) and isinstance(v.value, int))
            if literal_nonempty:
                out.append(Finding(
                    pass_name=PASS, rule="donation.ungated",
                    where=f"{filename}:{node.lineno}", symbol="jit",
                    message="literal donate_argnums is not gated on "
                    "_donate_ok(): CPU XLA ignores donation and warns on "
                    "every dispatch; donate only on gpu/tpu"))
    return out


def donation_findings(root: Optional[Path] = None) -> List[Finding]:
    from repro.core import engine as eng

    root = root or repo_root()
    engine_py = root / "src" / "repro" / "core" / "engine.py"
    out = lint_donation_source(engine_py.read_text(),
                               str(engine_py.relative_to(root)))
    platform = jax.default_backend()
    if eng._donate_ok() and platform not in ("gpu", "tpu"):
        out.append(Finding(
            pass_name=PASS, rule="donation.ungated",
            where="core.engine._donate_ok", symbol=platform,
            message=f"_donate_ok() returned True on platform "
            f"{platform!r}, which does not honour donation"))
    return out


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------

def run(root: Optional[Path] = None) -> List[Finding]:
    from repro.core import backend as be

    findings: List[Finding] = []
    models = tiny_models()
    for name, model in models:
        findings.extend(static_arg_findings(name, model))
        findings.extend(shape_branch_findings(name, model))
        closed = trace_model(model, SIGNATURE_WIDTHS[0])
        findings.extend(scan_jaxpr(
            closed, where="core.engine._simulate", symbol=name))

    # Pallas lowering: trace once per model through the kernel entry the
    # pallas/pallas_interpret backends dispatch (interpret mode traces the
    # same pallas_call). Oracle is pure numpy — nothing to trace.
    if any(be.get_backend(n).capabilities().available
           for n in be.backend_names() if "pallas" in n):
        for name, model in models:
            closed = trace_pallas(model, SIGNATURE_WIDTHS[0])
            findings.extend(scan_jaxpr(
                closed, where="kernels.ws_sim.ws_sim_pallas", symbol=name))
            findings.extend(kernel_op_findings(closed, name))

    findings.extend(pallas_grid_findings())
    findings.extend(donation_findings(root))
    return findings


__all__ = ["PASS", "CALLBACK_PRIMITIVES", "MOSAIC_UNLOWERABLE",
           "MOSAIC_FLOAT_ONLY", "SIGNATURE_WIDTHS", "tiny_models",
           "trace_model", "trace_pallas", "iter_eqns",
           "structural_signature", "scan_jaxpr", "static_arg_findings",
           "shape_branch_findings", "kernel_op_findings",
           "pallas_grid_findings",
           "lint_donation_source", "donation_findings", "run"]
