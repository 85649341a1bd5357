"""Lowerings of a jaxpr to a module in the window, per dispatch to a
backend, as the program counts them (``compile.lowerings``, from JAX's
``jaxpr_to_mlir_module_duration`` events): 1 a kernel chunk where every
dispatch lowers its kernel afresh, 0 where the lowered kernel is reused.
None where the program keeps no such counter."""


def read(run):
    n = run.counter_delta("backend.run_rows")
    if not n or "compile.lowerings" not in run.counters_after:
        return None
    return run.counter_delta("compile.lowerings") / n
