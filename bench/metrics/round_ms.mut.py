"""Admission round (``runtime/ingest.py`` ``pump``): mean wall ms of the
benchmark's own span around each ``pump()`` call in the window."""


def read(run):
    if not run.pump_spans:
        return None
    return 1e3 * sum(e - s for s, e in run.pump_spans) / len(run.pump_spans)
