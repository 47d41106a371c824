"""Epoch publish and ring (``core/epochs.py``): mean ms per admission round
spent outside the program's spans ``ingest.admit``, ``ingest.fused_apply``,
``wal.append`` and ``ckpt.save``. Today that is the publish and the epoch
ring push, which have no span of their own, and the ack bookkeeping."""

_PARTS = ("ingest.admit", "ingest.fused_apply", "wal.append", "ckpt.save")


def read(run):
    if not run.program_spans:
        return None
    spans = [ev for ev in run.program_spans if ev.get("ph") == "X"]
    rounds = [ev for ev in spans if ev["name"] == "ingest.round"]
    parts = [ev for ev in spans if ev["name"] in _PARTS]
    if not rounds:
        return None
    rest = 0.0
    for r in rounds:
        lo, hi = r["ts"], r["ts"] + r["dur"]
        inner = sum(p["dur"] for p in parts if p["tid"] == r["tid"]
                    and lo <= p["ts"] and p["ts"] + p["dur"] <= hi)
        rest += r["dur"] - inner
    return rest / len(rounds) / 1e3
