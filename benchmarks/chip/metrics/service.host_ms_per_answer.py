"""Host milliseconds per answer that the service spends outside the
backend: in the traced window, the union of the program's ``service.query``
spans on the host, less the union of its ``backend.run_rows`` spans, over
the answers. Broker, estimator and store work, and the Python between
them. None where the program's spans are not in the profiler's trace."""
from devtrace import host_lines
from readings import union_s


def _union_ns(trace, name, lo, hi):
    return union_s([(max(start, lo), min(start + dur, hi))
                    for line in host_lines(trace)
                    for n, start, dur in line["events"]
                    if n == name and start + dur > lo and start < hi])


def read(run):
    if run.trace is None or not run.answers:
        return None
    lo, hi = run.trace_window
    served = _union_ns(run.trace, "service.query", lo, hi)
    if not served:
        return None
    backend = _union_ns(run.trace, "backend.run_rows", lo, hi)
    return (served - backend) / 1e6 / len(run.answers)
