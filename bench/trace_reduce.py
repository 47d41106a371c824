"""Reduction of a JAX profiler trace to the benchmark's device numbers.

The traced run writes one ``.xplane.pb``. Device operations come from the
``XLA Ops`` line of each ``/device:`` plane; the benchmark's own host spans
(``jax.profiler.TraceAnnotation`` named ``bench.*``) come from the host
plane, on the same clock. From these:

* busy time: the union of device-op intervals inside the ``bench.window``
  span, averaged over the devices that ran anything;
* device time inside named host spans (the union clipped to each span);
* the breakdown: the device operations that took most time, and the
  longest idle gaps, each labelled with the innermost ``bench.*`` span that
  covers it.

Every PR computes these the same way; nothing here reads the program.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict
from dataclasses import dataclass, field

WINDOW = "bench.window"


@dataclass
class Trace:
    """Device ops per device and ``bench.*`` host spans, in ns."""

    ops: dict = field(default_factory=dict)      # device -> [(name, s, e)]
    spans: dict = field(default_factory=dict)    # name -> [(s, e)]

    @property
    def window(self) -> tuple[int, int]:
        w = self.spans.get(WINDOW)
        if not w:
            raise ValueError("trace holds no bench.window span")
        return min(s for s, _ in w), max(e for _, e in w)


def find_xplane(logdir: str) -> str:
    hits = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                     recursive=True)
    if not hits:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return max(hits, key=os.path.getmtime)


_LAUNCH = "PJRT_LoadedExecutable_Execute"


def _op_name(text: str) -> str:
    """``%fusion.3 = f32[...] fusion(...)`` -> ``fusion.3``."""
    return text.split(" = ", 1)[0].lstrip("%")


def _module_name(text: str) -> str:
    """``jit_apply_ops_fast(123456)`` -> ``jit_apply_ops_fast``."""
    return text.split("(", 1)[0]


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` written by ``jax.profiler``.

    Ops are named ``<program>/<op>``, the program being the ``XLA
    Modules`` event the op starts in. The device timeline comes with an
    offset of its own against the host's (about 1.4 ms on a v5e): each
    program execution is paired in order with a host
    ``PJRT_LoadedExecutable_Execute`` call, and the device events are
    moved by the median gap, so that device work falls inside the host
    span that launched it."""
    import bisect
    import statistics

    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    tr = Trace()
    launches: list[int] = []
    modules: dict = {}
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            mods = []
            for line in plane.lines:
                if line.name == "XLA Modules":
                    mods = sorted((int(ev.start_ns), int(ev.start_ns)
                                   + int(ev.duration_ns),
                                   _module_name(ev.name))
                                  for ev in line.events)
            starts = [m[0] for m in mods]
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                evs = []
                for ev in line.events:
                    s = int(ev.start_ns)
                    i = bisect.bisect_right(starts, s) - 1
                    prog = mods[i][2] if i >= 0 and s < mods[i][1] else "?"
                    evs.append((f"{prog}/{_op_name(ev.name)}", s,
                                s + int(ev.duration_ns)))
                if evs:
                    tr.ops.setdefault(plane.name, []).extend(evs)
                    modules[plane.name] = starts
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        s = int(ev.start_ns)
                        tr.spans.setdefault(ev.name, []).append(
                            (s, s + int(ev.duration_ns)))
                    elif ev.name == _LAUNCH:
                        launches.append(int(ev.start_ns))
    launches.sort()
    for dev, starts in modules.items():
        n = min(len(starts), len(launches))
        if n:
            shift = int(statistics.median(
                h - d for h, d in zip(launches[:n], starts[:n])))
            tr.ops[dev] = [(name, s + shift, e + shift)
                           for name, s, e in tr.ops[dev]]
    return tr


def union(intervals) -> list[tuple[int, int]]:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def covered(merged, spans) -> int:
    """ns of the disjoint ``merged`` intervals that fall inside ``spans``."""
    total = 0
    for s, e in union(spans):
        total += sum(b - a for a, b in clip(merged, s, e))
    return total


def busy_ns(tr: Trace) -> float:
    """Device-busy ns inside the window, averaged over the devices that ran
    an operation in the trace; 0 when no device ran anything."""
    lo, hi = tr.window
    per = [sum(e - s for s, e in clip(union((s, e) for _, s, e in evs),
                                      lo, hi))
           for evs in tr.ops.values()]
    return sum(per) / len(per) if per else 0.0


def device_ns_in(tr: Trace, span: str) -> float:
    """Device-busy ns inside the host spans named ``span`` (averaged over
    devices)."""
    spans = tr.spans.get(span, [])
    per = [covered(union((s, e) for _, s, e in evs), spans)
           for evs in tr.ops.values()]
    return sum(per) / len(per) if per else 0.0


def _label(tr: Trace, t: int) -> str:
    """The innermost (shortest) ``bench.*`` span other than the window
    that covers instant ``t``; ``"host"`` when none does."""
    best, best_len = "host", None
    for name, spans in tr.spans.items():
        if name == WINDOW:
            continue
        for s, e in spans:
            if s <= t < e and (best_len is None or e - s < best_len):
                best, best_len = name, e - s
    return best


def breakdown(tr: Trace, top: int = 10) -> dict:
    """``{"device_ops": [[name, s]...], "idle_gaps": [[label, s]...]}``:
    the ``top`` device operations by summed time inside the window, and the
    ``top`` longest gaps in the window in which no device op ran."""
    lo, hi = tr.window
    by_op: dict = defaultdict(int)
    all_ops = []
    for evs in tr.ops.values():
        for name, s, e in evs:
            for a, b in clip([(s, e)], lo, hi):
                by_op[name] += b - a
                all_ops.append((a, b))
    ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    gaps, t = [], lo
    for s, e in union(all_ops):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "device_ops": [[n, ns / 1e9] for n, ns in ops],
        "idle_gaps": [[_label(tr, (s + e) // 2), (e - s) / 1e9]
                      for s, e in gaps[:top]],
    }
