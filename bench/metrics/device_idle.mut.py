"""Device: idle share of the traced window, 100 * (1 - busy / window),
from the profiler trace (``bench/trace_reduce.py``)."""
from bench import trace_reduce


def read(run):
    if run.trace is None or not run.trace.ops:
        return None
    lo, hi = run.trace.window
    return 100.0 * (1.0 - trace_reduce.busy_ns(run.trace) / (hi - lo))
