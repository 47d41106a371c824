"""Admission round (``runtime/ingest.py`` ``pump``): mean self time in ms of
the program's ``ingest.round`` span, its duration less the child spans of
the same thread that it encloses and that name the round's work. Nothing
when the run has no ``ingest.publish`` span: a program that does not split
its round into these spans has no self time to read."""

_CHILDREN = ("ingest.admit", "ingest.make_batch", "ingest.fused_apply",
             "wal.append", "ingest.publish", "ingest.ack", "ckpt.save")


def read(run):
    if not run.program_spans:
        return None
    spans = [ev for ev in run.program_spans if ev.get("ph") == "X"]
    rounds = [ev for ev in spans if ev["name"] == "ingest.round"]
    kids = [ev for ev in spans if ev["name"] in _CHILDREN]
    if not rounds or not any(k["name"] == "ingest.publish" for k in kids):
        return None
    total = 0.0
    for r in rounds:
        lo, hi = r["ts"], r["ts"] + r["dur"]
        inner = sum(k["dur"] for k in kids if k["tid"] == r["tid"]
                    and lo <= k["ts"] and k["ts"] + k["dur"] <= hi)
        total += r["dur"] - inner
    return total / len(rounds) / 1e3
