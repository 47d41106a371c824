"""Hierarchical spans + Perfetto export: the tracing half of the
observability layer (DESIGN.md §14).

One process-global ``TraceRecorder`` collects *spans* — named, timed,
attributed intervals — from every instrumented layer (admission rounds,
fused applies, BFS supersteps, index queries, epoch-ring reconstructions).
The recorder is OFF by default and the disabled path is engineered to be
free in both senses that matter on the hot path:

  * **wall time** — ``span()`` with the recorder disabled performs one
    global load, one attribute check, and returns a shared ``_NullSpan``
    singleton whose ``__enter__``/``__exit__``/``set`` are empty slots
    methods. tests/test_obs.py budgets the full per-workload cost of the
    disabled instrumentation at <5% of a scripted ingest round's wall.
  * **jit behaviour** — instrumentation lives strictly OUTSIDE jit
    boundaries (host timestamps around jitted calls; device timings via
    ``fence`` = ``jax.block_until_ready``), and traced code paths are
    selected by ``enabled()`` checked on the HOST, never inside a traced
    function. With tracing disabled every jitted entry point sees exactly
    the pre-observability call signature: zero extra retraces, pinned by
    the cache-key test in tests/test_obs.py.

Enabling: set ``REPRO_TRACE=1`` in the environment (read once at import),
or call ``enable()``/``capture()`` at runtime. ``save(path)`` writes the
Chrome trace-event JSON (``{"traceEvents": [...]}``) that
https://ui.perfetto.dev and ``chrome://tracing`` load directly;
``tools/trace_view.py`` summarizes the same file offline (DESIGN.md §14).

Span nesting is positional, the way the trace-event format defines it:
complete ("X") events on one thread nest by timestamp containment, so the
recorder never maintains an explicit tree — each layer simply opens its
span around the work, and ``ingest.round`` ends up enclosing
``ingest.fused_apply`` which encloses nothing, while ``bfs.session``
encloses one ``bfs.superstep`` per frontier expansion.

One clock with the device trace: while the recorder is on, every live span
also enters ``jax.profiler.TraceAnnotation(name)``, so a profiler capture
shows the program's spans on its host track, on the same clock as the
device ops. The recorder's own events are stamped from an origin on
``time.perf_counter_ns``; ``events()`` and the export lead with one
metadata ("M") event named ``clock_sync`` whose args give that origin, so
a reader can place the JSON spans on any ``perf_counter`` timeline.
"""
from __future__ import annotations

import contextlib
import json
import os
import threading
import time

_TRUTHY = {"1", "true", "yes", "on"}


class _NullSpan:
    """Shared do-nothing span: the entire disabled-tracer hot path."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs):
        return self


_NULL = _NullSpan()


class _LiveSpan:
    """One open interval; appends a complete ("X") event on exit and
    mirrors itself as a profiler ``TraceAnnotation`` while open."""

    __slots__ = ("_rec", "name", "attrs", "_t0", "_ann")

    def __init__(self, rec: "TraceRecorder", name: str, attrs: dict):
        self._rec = rec
        self.name = name
        self.attrs = attrs
        self._t0 = 0
        self._ann = None

    def set(self, **attrs):
        """Attach/overwrite span attributes mid-flight (e.g. a direction
        tag only known after the superstep ran)."""
        self.attrs.update(attrs)
        return self

    def __enter__(self):
        # jax imported lazily, as ``fence`` does: a disabled recorder never
        # loads it
        from jax.profiler import TraceAnnotation

        self._ann = TraceAnnotation(self.name)
        self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        dur = time.perf_counter_ns() - self._t0
        self._ann.__exit__(None, None, None)
        self._rec._emit(self.name, self._t0, dur, self.attrs)
        return False


class TraceRecorder:
    """Process-global span/counter sink (DESIGN.md §14).

    Thread-safe appends; each event carries the OS thread id so multi-
    client admission shows up as parallel tracks in Perfetto.
    """

    def __init__(self):
        self.enabled = False
        self._events: list[dict] = []
        self._lock = threading.Lock()
        self._epoch_ns = time.perf_counter_ns()

    # -- recording ----------------------------------------------------------
    def _emit(self, name: str, t0_ns: int, dur_ns: int, attrs: dict) -> None:
        ev = {
            "name": name,
            "ph": "X",
            "ts": (t0_ns - self._epoch_ns) / 1e3,   # microseconds
            "dur": dur_ns / 1e3,
            "pid": os.getpid(),
            "tid": threading.get_ident() & 0xFFFF,
        }
        if attrs:
            ev["args"] = attrs
        with self._lock:
            self._events.append(ev)

    def counter(self, name: str, value) -> None:
        """One counter ("C") sample — a stepped time series in Perfetto."""
        if not self.enabled:
            return
        ev = {
            "name": name,
            "ph": "C",
            "ts": (time.perf_counter_ns() - self._epoch_ns) / 1e3,
            "pid": os.getpid(),
            "tid": threading.get_ident() & 0xFFFF,
            "args": {"value": value},
        }
        with self._lock:
            self._events.append(ev)

    # -- lifecycle ----------------------------------------------------------
    def start(self, fresh: bool = False) -> None:
        with self._lock:
            if fresh:
                self._events = []
            self.enabled = True

    def stop(self) -> None:
        with self._lock:
            self.enabled = False

    def clear(self) -> None:
        with self._lock:
            self._events = []

    # -- export -------------------------------------------------------------
    def clock_sync(self) -> dict:
        """The metadata event that ties ``ts`` to ``time.perf_counter_ns``:
        an event at ``ts`` microseconds started at ``perf_counter_ns``
        ``args["perf_counter_ns"] + 1000 * ts``."""
        return {"name": "clock_sync", "ph": "M", "ts": 0.0,
                "pid": os.getpid(), "tid": 0,
                "args": {"perf_counter_ns": self._epoch_ns}}

    def events(self) -> list[dict]:
        """The ``clock_sync`` event, then every recorded event in order."""
        with self._lock:
            return [self.clock_sync()] + self._events

    def export(self) -> dict:
        """Chrome/Perfetto trace-event JSON object (DESIGN.md §14)."""
        return {"traceEvents": self.events(), "displayTimeUnit": "ms"}

    def save(self, path: str) -> str:
        with open(path, "w", encoding="utf-8") as f:
            json.dump(self.export(), f)
        return path


_RECORDER = TraceRecorder()


def recorder() -> TraceRecorder:
    """The process-global recorder."""
    return _RECORDER


def enabled() -> bool:
    """Host-side tracing switch — the ONE check every instrumented layer
    guards its traced path with (DESIGN.md §14)."""
    return _RECORDER.enabled


def span(name: str, **attrs):
    """Open a span. Disabled: returns the shared no-op singleton (no
    allocation beyond the kwargs dict the caller already built — hot paths
    with expensive attrs should guard on ``enabled()`` first)."""
    if not _RECORDER.enabled:
        return _NULL
    return _LiveSpan(_RECORDER, name, attrs)


def counter(name: str, value) -> None:
    """Record a counter sample (no-op when disabled)."""
    _RECORDER.counter(name, value)


def enable(fresh: bool = False) -> None:
    _RECORDER.start(fresh=fresh)


def disable() -> None:
    _RECORDER.stop()


def save(path: str | None = None) -> str:
    """Write the Perfetto-loadable trace JSON (DESIGN.md §14); ``None``
    uses ``REPRO_TRACE_PATH`` (default ``repro_trace.json``)."""
    return _RECORDER.save(path if path is not None else _env_path())


def fence(x):
    """Device-timing fence: block on ``x`` when tracing so the enclosing
    span measures device work, pass through untouched when disabled
    (DESIGN.md §14)."""
    if _RECORDER.enabled:
        import jax

        jax.block_until_ready(x)
    return x


@contextlib.contextmanager
def capture():
    """Enable a FRESH trace for the duration of the block and yield the
    recorder; restores the previous enabled state on exit. The test/bench
    surface: benchmarks capture a traced run to derive obs columns
    (supersteps, direction flips) without leaking global state
    (DESIGN.md §14)."""
    was = _RECORDER.enabled
    _RECORDER.start(fresh=True)
    try:
        yield _RECORDER
    finally:
        if not was:
            _RECORDER.stop()


def _env_path() -> str:
    return os.environ.get("REPRO_TRACE_PATH", "repro_trace.json")


# REPRO_TRACE=1 (or any truthy value) arms the recorder at import — the
# env-var form of enable() the launchers rely on (DESIGN.md §14).
if os.environ.get("REPRO_TRACE", "").strip().lower() in _TRUTHY:
    _RECORDER.start()
