"""Device: the share (%) of device-idle time inside the benchmark's
``bench.pump`` spans that no program span names.

Idle time is where no device operation runs (``run.trace.ops``). A program
span names work unless it is ``ingest.round``, which encloses the whole
pump. The program's spans are placed on the profiler's clock in two steps:
the recorder's ``clock_sync`` event puts them on ``time.perf_counter``,
the clock of the harness's ``run.pump_spans``; those are paired in order
with the trace's ``bench.pump`` spans, and the median start-to-start
offset (as ``trace_reduce.load`` aligns launches) moves them onto it."""
import statistics

from bench import trace_reduce

_ENCLOSING = "ingest.round"


def origin_ns(spans):
    """The recorder's origin on ``perf_counter_ns``, or None."""
    for ev in spans:
        if ev.get("ph") == "M" and ev.get("name") == "clock_sync":
            return ev["args"]["perf_counter_ns"]
    return None


def on_trace_clock(run, names=None):
    """``[(name, start, end)]`` in ns on the profiler's clock of every
    program span (or those named in ``names``); None when the run lacks
    the clock sync, the pumps or their trace spans."""
    if not run.program_spans or run.trace is None:
        return None
    origin = origin_ns(run.program_spans)
    traced = run.trace.spans.get("bench.pump", [])
    pairs = list(zip(run.pump_spans, traced))
    if origin is None or not pairs:
        return None
    shift = statistics.median(s - 1e9 * t0 for (t0, _), (s, _) in pairs)
    out = []
    for ev in run.program_spans:
        if ev.get("ph") != "X" or (names and ev["name"] not in names):
            continue
        s = origin + 1e3 * ev["ts"] + shift
        out.append((ev["name"], int(s), int(s + 1e3 * ev["dur"])))
    return out


def minus(intervals, cut):
    """The parts of disjoint sorted ``intervals`` outside ``cut``."""
    cut = trace_reduce.union(cut)
    out = []
    for lo, hi in intervals:
        t = lo
        for s, e in trace_reduce.clip(cut, lo, hi):
            if s > t:
                out.append((t, s))
            t = max(t, e)
        if hi > t:
            out.append((t, hi))
    return out


def read(run):
    spans = on_trace_clock(run)
    if spans is None:
        return None
    busy = [(s, e) for evs in run.trace.ops.values() for _, s, e in evs]
    idle = minus(trace_reduce.union(run.trace.spans["bench.pump"]), busy)
    total = sum(e - s for s, e in idle)
    if total <= 0:
        return None
    named = [(s, e) for name, s, e in spans if name != _ENCLOSING]
    untraced = minus(idle, named)
    return 100.0 * sum(e - s for s, e in untraced) / total
