"""Durability (``runtime/wal.py``): mean ms per WAL append in the window,
from the pool's counters ``ingest.wal_append_s`` / ``ingest.wal_records``."""


def read(run):
    a, b = run.counters_open, run.counters_close
    n = b.get("ingest.wal_records", 0) - a.get("ingest.wal_records", 0)
    if n <= 0:
        return None
    return 1e3 * (b["ingest.wal_append_s"] - a["ingest.wal_append_s"]) / n
