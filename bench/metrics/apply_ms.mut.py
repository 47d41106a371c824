"""Mutation engine (``core/ops.py``): mean ms of the program's fenced
``ingest.fused_apply`` span per fused apply in the window."""


def read(run):
    if not run.program_spans:
        return None
    durs = [ev["dur"] for ev in run.program_spans
            if ev.get("ph") == "X" and ev["name"] == "ingest.fused_apply"]
    return sum(durs) / len(durs) / 1e3 if durs else None
