"""Admission round (``IngestPool._admit``): mean ms from a batch's enqueue
to the round that admits it, from the program's tracing-only histogram
``ingest.admit_wait_s`` between the window's open and close."""

_NAME = "ingest.admit_wait_s"


def read(run):
    b = run.counters_close.get(_NAME)
    if not b:
        return None
    a = run.counters_open.get(_NAME) or {"count": 0, "sum": 0.0}
    n = b["count"] - a["count"]
    if n <= 0:
        return None
    return 1e3 * (b["sum"] - a["sum"]) / n
