"""Epoch publish and ring (``EpochRing.push``): mean ms of the program's
``ring.delta`` span (row compare, XOR record, eviction) per push in the
window."""


def read(run):
    if not run.program_spans:
        return None
    durs = [ev["dur"] for ev in run.program_spans
            if ev.get("ph") == "X" and ev["name"] == "ring.delta"]
    return sum(durs) / len(durs) / 1e3 if durs else None
