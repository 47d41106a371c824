"""Epoch publish and ring (``EpochRing.push``): mean ms of the program's
``ring.to_host`` span, the device-to-host copy of the patchable fields,
counting only those inside an ``ingest.publish`` span of the same thread."""


def read(run):
    if not run.program_spans:
        return None
    spans = [ev for ev in run.program_spans if ev.get("ph") == "X"]
    pubs = [(ev["tid"], ev["ts"], ev["ts"] + ev["dur"]) for ev in spans
            if ev["name"] == "ingest.publish"]
    durs = [ev["dur"] for ev in spans if ev["name"] == "ring.to_host"
            and any(tid == ev["tid"] and lo <= ev["ts"]
                    and ev["ts"] + ev["dur"] <= hi for tid, lo, hi in pubs)]
    return sum(durs) / len(durs) / 1e3 if durs else None
