"""The readers of the program's own spans and counters (``publish_ms.mut``,
``ring_copy_ms.mut``, ``ring_delta_ms.mut``, ``round_self_ms.mut``,
``admit_wait_ms.mut``, ``idle_untraced.mut``) on a hand-built run, each
with the run that lacks what it reads; and the clock that places the
program's spans on a profiler trace, on a real trace recorded on the CPU."""
import importlib.util
import pathlib
import time

import pytest

from bench import trace_reduce
from bench.harness import RunData
from bench.registry import Registry

ROOT = pathlib.Path(__file__).resolve().parents[2]
NEW = ("publish_ms.mut", "ring_copy_ms.mut", "ring_delta_ms.mut",
       "round_self_ms.mut", "admit_wait_ms.mut", "idle_untraced.mut")


def _reader_module(name):
    path = ROOT / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "test_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _x(name, ts, dur, tid=1):
    return {"name": name, "ph": "X", "ts": float(ts), "dur": float(dur),
            "pid": 7, "tid": tid}


def _round(t, admit, make, apply, wal, copy, delta, ack, dur):
    """One round at ``t`` us: children laid end to end from its start, the
    publish 10 us longer than its copy + delta + a 10 us lead-in."""
    out, c = [], t
    for name, d in (("ingest.admit", admit), ("ingest.make_batch", make),
                    ("ingest.fused_apply", apply), ("wal.append", wal)):
        out.append(_x(name, c, d))
        c += d
    pub = 10 + copy + delta + 10
    out += [_x("ingest.publish", c, pub), _x("ring.to_host", c + 10, copy),
            _x("ring.delta", c + 10 + copy, delta)]
    c += pub
    out += [_x("ingest.ack", c, ack), _x("ingest.round", t, dur)]
    return out


ORIGIN = 500_000_000                     # clock_sync, perf_counter ns


def _hand(**kw):
    """Two rounds (self time 40 and 20 us), a ring copy outside any publish
    (the ring's reset), and a span of another thread inside round 1."""
    spans = [{"name": "clock_sync", "ph": "M", "ts": 0.0, "pid": 7,
              "tid": 0, "args": {"perf_counter_ns": ORIGIN}},
             _x("ring.to_host", -500, 300)]
    spans += _round(0, 50, 50, 300, 100, 200, 180, 60, 1000)
    spans += _round(2000, 20, 20, 200, 60, 150, 230, 80, 800)
    spans.append(_x("ingest.admit", 100, 100, tid=2))
    tr = trace_reduce.Trace()
    # the profiler's clock runs 499,990,000 ns behind perf_counter here
    tr.spans = {"bench.window": [(0, 3_000_000)],
                "bench.pump": [(10_000, 1_010_000), (2_010_000, 2_830_000)]}
    tr.ops = {"/device:TPU:0": [("m/a", 200_000, 400_000),
                                ("m/b", 2_800_000, 2_820_000)]}
    base = dict(seconds=3.0, t_open=0.499, t_close=0.503, batches=[],
                queries=[], pump_spans=[(0.5, 0.501), (0.502, 0.50282)],
                query_spans=[],
                counters_open={"ingest.admit_wait_s": {"count": 2,
                                                       "sum": 1.0}},
                counters_close={"ingest.admit_wait_s": {"count": 6,
                                                        "sum": 3.4}},
                program_spans=spans, trace=tr)
    base.update(kw)
    return RunData(**base)


# round 1: publish 10 + 200 + 180 + 10 = 400 us, round 2: 10 + 150 + 230
# + 10 = 400 us; self 1000 - (50+50+300+100+400+60) = 40 and 800 -
# (20+20+200+60+400+80) = 20 us
WORKED = {
    "publish_ms.mut": (400 + 400) / 2 / 1e3,
    "ring_copy_ms.mut": (200 + 150) / 2 / 1e3,
    "ring_delta_ms.mut": (180 + 230) / 2 / 1e3,
    "round_self_ms.mut": (40 + 20) / 2 / 1e3,
    "admit_wait_ms.mut": 1e3 * (3.4 - 1.0) / (6 - 2),
    # idle in the pumps: 1,000,000 - 200,000 and 820,000 - 20,000 ns; no
    # named span covers the last 40,000 ns of either, where pump 2 has a
    # 20,000 ns op: 100 * (40,000 + 20,000) / 1,600,000
    "idle_untraced.mut": 100.0 * 60_000 / 1_600_000,
}


@pytest.mark.parametrize("metric", NEW)
def test_reader_by_hand(metric):
    read = Registry(ROOT).reader(metric)
    assert read(_hand()) == pytest.approx(WORKED[metric], rel=1e-9)


# what a program without this instrumentation emits in a traced window
OLD_SPANS = ("ingest.round", "ingest.admit", "ingest.fused_apply",
             "wal.append", "ckpt.save")


@pytest.mark.parametrize("metric", NEW)
def test_reader_finds_nothing_in_a_run_without_it(metric):
    """An untraced run, and a traced run of a program that emits only the
    older spans, no ``clock_sync`` and no admission-wait histogram."""
    read = Registry(ROOT).reader(metric)
    assert read(_hand(program_spans=None, counters_open={},
                      counters_close={})) is None
    old = [ev for ev in _hand().program_spans if ev["name"] in OLD_SPANS]
    assert read(_hand(program_spans=old, counters_open={},
                      counters_close={"ingest.wal_records": 2})) is None
    assert read(_hand(program_spans=old, counters_close={
        "ingest.admit_wait_s": {"count": 2, "sum": 1.0}})) is None


def test_idle_untraced_needs_the_clock_sync_and_the_pumps():
    read = Registry(ROOT).reader("idle_untraced.mut")
    spans = [ev for ev in _hand().program_spans if ev["ph"] == "X"]
    assert read(_hand(program_spans=spans)) is None
    assert read(_hand(pump_spans=[])) is None
    assert read(_hand(trace=None)) is None
    # on the profiler's clock each round starts with its pump
    mod = _reader_module("idle_untraced.mut")
    mapped = mod.on_trace_clock(_hand(), {"ingest.round"})
    assert mapped == [("ingest.round", 10_000, 1_010_000),
                      ("ingest.round", 2_010_000, 2_810_000)]


def test_profiler_round_trip_places_program_spans_in_their_pumps(tmp_path):
    """A traced pool pumped inside ``bench.pump`` annotations under a CPU
    profiler session: the ``.xplane`` host plane holds each round's
    ``ingest.round``, and each JSON ``ingest.round`` mapped by the reader's
    method lies inside its ``bench.pump`` to within 200 us."""
    import jax
    from jax.profiler import ProfileData

    from repro.core import OP_ADD_E, OP_ADD_V
    from repro.obs import trace
    from repro.runtime.serve_loop import GraphCoServer

    srv = GraphCoServer(capacity=32, ingest=True,
                        wal_dir=str(tmp_path / "wal"))
    srv.submit_client("warm", [(OP_ADD_V, 1), (OP_ADD_V, 2)])
    srv.pump()                                  # compile outside the trace
    pumps = []
    with trace.capture() as rec:
        jax.profiler.start_trace(str(tmp_path / "trace"))
        try:
            for k in range(4):
                srv.submit_client("c", [(OP_ADD_V, 10 + k),
                                        (OP_ADD_E, 10 + k, 1)])
                t0 = time.perf_counter()
                with jax.profiler.TraceAnnotation("bench.pump"):
                    assert srv.pump() == 1
                pumps.append((t0, time.perf_counter()))
        finally:
            jax.profiler.stop_trace()
        events = rec.events()
    path = trace_reduce.find_xplane(str(tmp_path / "trace"))
    tr = trace_reduce.load(path)
    assert len(tr.spans["bench.pump"]) == len(pumps)

    host = [ev.name for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events]
    assert host.count("ingest.round") == len(pumps)
    assert host.count("ring.to_host") == len(pumps)

    run = RunData(1.0, pumps[0][0], pumps[-1][1], [], [], pumps, [], {}, {},
                  events, tr)
    mod = _reader_module("idle_untraced.mut")
    mapped = mod.on_trace_clock(run, {"ingest.round"})
    assert len(mapped) == len(pumps)
    for (_, s, e), (ps, pe) in zip(mapped, tr.spans["bench.pump"]):
        assert ps - 200_000 <= s < e <= pe + 200_000
    assert 0.0 <= mod.read(run) < 100.0


def test_traced_tiny_cell_reports_every_program_metric(tiny_root):
    """A traced run of the tiny update-heavy cell on the CPU: each new
    metric is on the result line, and the round's self time is a small
    part of the round."""
    from bench.harness import run_cell

    out = run_cell(tiny_root, "g500-s16.update-heavy", 2 ** 31 + 97, 2.0,
                   True, require_tpu=False)
    assert out["correct"] is True
    m = {k: v["value"] for k, v in out["metrics"].items()}
    assert set(NEW) <= set(m)
    assert 0 < m["round_self_ms.mut"] < 0.2 * m["round_ms.mut"]
    assert m["ring_copy_ms.mut"] + m["ring_delta_ms.mut"] <= m[
        "publish_ms.mut"]
    assert 0 <= m["idle_untraced.mut"] < 100
