"""Epoch publish and ring (``IngestPool._publish``): mean ms of the
program's ``ingest.publish`` span (slot flip, ring push, ``epoch_log``
prune) per publish in the window."""


def read(run):
    if not run.program_spans:
        return None
    durs = [ev["dur"] for ev in run.program_spans
            if ev.get("ph") == "X" and ev["name"] == "ingest.publish"]
    return sum(durs) / len(durs) / 1e3 if durs else None
