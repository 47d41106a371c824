"""Typed metrics registry + registry-backed stat views (DESIGN.md §14).

The repo grew one ad-hoc stat block per subsystem (``IngestStats``,
``ServeStats``, per-server index/ring counters). This module gives them a
single canonical home:

  * ``MetricsRegistry`` — a typed (counter | gauge | histogram) name ->
    value store. Counters and gauges are plain numbers; histograms keep
    (count, sum, min, max) — enough for latency attribution without
    bucketing policy.
  * ``StatsView`` — a dataclass-shaped VIEW over a registry: subclasses
    declare ``_SPEC`` (field -> (kind, default)) and ``_PREFIX``;
    attribute reads/writes route to the registry under
    ``"<prefix>.<field>"``. ``IngestStats`` and ``ServeStats`` are now
    such views, so every existing call site (``stats.submitted += 1``,
    pinned equality asserts in tests/test_serving_stats.py) keeps working
    unchanged while ``GraphCoServer.get_metrics`` serves the same numbers
    from one registry snapshot.
  * ``GLOBAL`` — the process-global registry the *tracing-only* metrics
    land in (superstep direction counts, ring resolution depths, index
    latencies). These are updated only when ``trace.enabled()`` — the
    disabled hot path never touches them.

``OBS_METRICS`` is the static declaration of every global metric; the
drift check (tools/check_metrics_doc.py, run by the obs-tests CI step)
asserts each declared name — global and view fields alike — appears in
DESIGN.md §14's metric table.
"""
from __future__ import annotations

import threading

_KINDS = ("counter", "gauge", "histogram")


class MetricsRegistry:
    """Typed name -> metric store (DESIGN.md §14). Thread-safe."""

    def __init__(self):
        self._lock = threading.Lock()
        self._kinds: dict[str, str] = {}
        self._values: dict[str, object] = {}

    def declare(self, name: str, kind: str, default=0) -> None:
        if kind not in _KINDS:
            raise ValueError(f"unknown metric kind {kind!r} for {name!r}")
        with self._lock:
            prev = self._kinds.get(name)
            if prev is not None and prev != kind:
                raise ValueError(
                    f"metric {name!r} re-declared as {kind} (was {prev})")
            if name not in self._values:
                self._kinds[name] = kind
                self._values[name] = (
                    {"count": 0, "sum": 0.0, "min": None, "max": None}
                    if kind == "histogram" else default)

    def kind(self, name: str) -> str:
        return self._kinds[name]

    def get(self, name: str):
        with self._lock:
            return self._values[name]

    def set(self, name: str, value) -> None:
        with self._lock:
            if self._kinds.get(name) == "histogram":
                raise TypeError(f"histogram {name!r} takes observe(), not set()")
            self._values[name] = value

    def inc(self, name: str, delta=1) -> None:
        with self._lock:
            self._values[name] = self._values[name] + delta

    def observe(self, name: str, value) -> None:
        with self._lock:
            h = self._values[name]
            h["count"] += 1
            h["sum"] += value
            h["min"] = value if h["min"] is None else min(h["min"], value)
            h["max"] = value if h["max"] is None else max(h["max"], value)

    def names(self) -> list[str]:
        with self._lock:
            return sorted(self._kinds)

    def snapshot(self) -> dict:
        """One flat dict of current values (histograms as sub-dicts) — the
        payload of the ``get_metrics`` serving endpoint (DESIGN.md §14)."""
        with self._lock:
            return {k: (dict(v) if isinstance(v, dict) else v)
                    for k, v in sorted(self._values.items())}


class StatsView:
    """Dataclass-shaped view over a ``MetricsRegistry`` (DESIGN.md §14).

    Subclasses declare ``_PREFIX`` and ``_SPEC``; instances expose each
    spec field as a plain attribute whose storage is the registry entry
    ``"<prefix>.<field>"`` — the pre-existing ``stats.field += n`` call
    sites and pinned test asserts keep their exact semantics while the
    values become registry-servable.
    """

    _PREFIX = ""
    _SPEC: dict[str, tuple] = {}

    def __init__(self, registry: MetricsRegistry | None = None):
        object.__setattr__(self, "registry",
                           registry if registry is not None
                           else MetricsRegistry())
        for name, (kind, default) in self._SPEC.items():
            self.registry.declare(self._qual(name), kind, default)

    @classmethod
    def _qual(cls, name: str) -> str:
        return f"{cls._PREFIX}.{name}" if cls._PREFIX else name

    def __getattr__(self, name: str):
        if name in type(self)._SPEC:
            return self.registry.get(self._qual(name))
        raise AttributeError(
            f"{type(self).__name__} has no field {name!r}")

    def __setattr__(self, name: str, value) -> None:
        if name in type(self)._SPEC:
            self.registry.set(self._qual(name), value)
        else:
            object.__setattr__(self, name, value)

    def snapshot(self) -> dict:
        """field -> current value (unprefixed, view-local)."""
        return {name: getattr(self, name) for name in self._SPEC}

    def __repr__(self) -> str:
        body = ", ".join(f"{k}={getattr(self, k)!r}" for k in self._SPEC)
        return f"{type(self).__name__}({body})"


# Tracing-only global metrics: updated exclusively under ``trace.enabled()``
# so the disabled hot path never pays for them. Every name here must appear
# in DESIGN.md §14's metric table (tools/check_metrics_doc.py enforces).
OBS_METRICS: dict[str, tuple[str, str]] = {
    "bfs.supersteps": ("counter", "traced fused supersteps executed"),
    "bfs.pull_supersteps": ("counter", "traced supersteps that chose pull"),
    "bfs.direction_flips": ("counter",
                            "push<->pull switches across traced supersteps"),
    "bfs.exchange_bytes": ("counter",
                           "sharded frontier-exchange bytes (packed words)"),
    "ingest.round_s": ("histogram", "wall seconds per admission round"),
    "ingest.fused_apply_s": ("histogram",
                             "device wall seconds per fused apply"),
    "ingest.admit_wait_s": ("histogram",
                            "seconds from enqueue to the admitting round, "
                            "per admitted batch"),
    "index.query_s": ("histogram", "wall seconds per index query batch"),
    "index.ring_validate_s": ("histogram",
                              "wall seconds per ring-validated serve"),
    "index.fallback_s": ("histogram",
                         "wall seconds per BFS-fallback session"),
    "ring.occupancy": ("gauge", "delta records currently retained"),
    "ring.evictions": ("counter", "delta records dropped by retention"),
    "ring.resolve_depth": ("histogram",
                           "XOR records replayed per state_at()"),
    "ring.d2h_bytes": ("histogram",
                       "bytes copied device->host per ring push or reset"),
    "ring.full_copies": ("counter",
                         "ring resets that copied the whole state to the "
                         "host"),
    "wal.append_s": ("histogram",
                     "wall seconds per durable WAL append (incl. fsync)"),
    "ckpt.save_s": ("histogram",
                    "wall seconds per published graph checkpoint"),
    "recovery.restore_s": ("histogram",
                           "wall seconds per checkpoint+WAL recovery"),
    "serve.degraded": ("gauge",
                       "1 while the server recovers (pinned reads, "
                       "R_RECOVERING writes)"),
}

GLOBAL = MetricsRegistry()
for _name, (_kind, _doc) in OBS_METRICS.items():
    GLOBAL.declare(_name, _kind)


def global_registry() -> MetricsRegistry:
    """The process-global tracing-metrics registry (DESIGN.md §14)."""
    return GLOBAL
