"""The trace reduction, on a hand-built trace and on a small profiler trace
recorded on a TPU (``data/tiny.xplane.pb``, made by
``data/make_trace.py``)."""
import pathlib

import pytest

from bench import trace_reduce as tr

DATA = pathlib.Path(__file__).resolve().parent / "data" / "tiny.xplane.pb"


def _hand():
    t = tr.Trace()
    t.spans = {"bench.window": [(0, 100)],
               "bench.pump": [(10, 40), (60, 90)],
               "bench.wait": [(40, 60)]}
    t.ops = {"/device:TPU:0": [("m/a", 12, 20), ("m/b", 18, 30),
                               ("m/a", 62, 70), ("m/a", 95, 120)]}
    return t


def test_busy_inside_spans_and_breakdown_by_hand():
    t = _hand()
    assert tr.union([(5, 9), (1, 3), (2, 4)]) == [(1, 4), (5, 9)]
    assert tr.busy_ns(t) == 18 + 8 + 5
    assert tr.device_ns_in(t, "bench.pump") == 18 + 8
    b = tr.breakdown(t)
    assert b["device_ops"][0] == ["m/a", (8 + 8 + 5) / 1e9]
    gaps = b["idle_gaps"]
    assert gaps[0] == ["bench.wait", 32 / 1e9]       # 30..62
    assert ["bench.pump", 25 / 1e9] in gaps          # 70..95, in a pump
    assert ["host", 12 / 1e9] in gaps                # 0..12
    assert sum(g for _, g in gaps) == pytest.approx((100 - 31) / 1e9)


def test_recorded_tpu_trace():
    t = tr.load(str(DATA))
    lo, hi = t.window
    assert t.ops, "no device plane in the recorded trace"
    busy = tr.busy_ns(t)
    assert 0 < busy < hi - lo
    # aligned to the host clock, all device work falls inside the pumps
    assert tr.device_ns_in(t, "bench.pump") == pytest.approx(busy, rel=0.01)
    assert len(t.spans["bench.pump"]) == 3
    b = tr.breakdown(t)
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    assert b["device_ops"][0][0] == "jit__lambda/convolution_reduce_fusion"
    assert b["idle_gaps"][0][0] == "bench.wait"
    idle = sum(g for _, g in b["idle_gaps"])
    assert idle <= (hi - lo - busy) / 1e9 + 1e-12
