"""Observability subsystem contract (DESIGN.md §14).

Three layers of guarantee, tiered by cost:

  * the DISABLED path is free — zero extra jit retraces on the scripted
    3-client ingest round (cache-key pin) and a <5% wall budget for the
    no-op span shells;
  * the ENABLED path is honest — traced ``multi_bfs`` / ``collect_batch``
    are bit-identical to their jitted forms (the spans move the jit
    boundary, never the math), and span nesting follows trace-event
    timestamp containment;
  * the EXPORTS round-trip — recorder -> Perfetto JSON ->
    ``tools/trace_view.py`` summary, ``get_metrics`` is JSON-serializable,
    and the DESIGN.md §14 metric table covers every declared name
    (tools/check_metrics_doc.py, exercised here so the drift check cannot
    itself drift out of CI).
"""
import importlib.util
import json
import pathlib
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    OP_ADD_E, OP_ADD_V, apply_ops, collect_batch, find_slot, make_graph,
    make_op_batch, multi_bfs,
)
# the package re-exports the bfs() FUNCTION under the submodule's name,
# so fetch the modules themselves for the jit-cache pins
bfs_mod = importlib.import_module("repro.core.bfs")
snapshot_mod = importlib.import_module("repro.core.snapshot")
from repro.obs import trace
from repro.obs.metrics import (
    GLOBAL, OBS_METRICS, MetricsRegistry, StatsView, global_registry,
)
from repro.runtime.ingest import IngestStats
from repro.runtime.serve_loop import GraphCoServer

from tests.test_serving_stats import A_OPS, B_OPS, C_OPS, _fake_clock

ROOT = pathlib.Path(__file__).resolve().parent.parent


def _load_tool(name):
    """Import a tools/ script by file path (tools/ is not a package)."""
    spec = importlib.util.spec_from_file_location(
        name, ROOT / "tools" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _build(nv=10, extra_edges=(), cap=32):
    g = make_graph(cap)
    ops = [(OP_ADD_V, k, -1, -1) for k in range(nv)]
    ops += [(OP_ADD_E, k, k + 1, -1) for k in range(nv - 1)]
    ops += [(op, u, v, -1) for (op, u, v) in extra_edges]
    g, _ = apply_ops(g, make_op_batch(ops))
    return g


def _scripted_round(clock=None):
    """The scripted 3-client admission round from tests/test_serving_stats
    plus one GetPath batch — the workload both overhead pins run."""
    srv = GraphCoServer(capacity=32, ingest=True)
    if clock is not None:
        srv.pool.clock = clock
    srv.submit_client("A", A_OPS)
    srv.submit_client("B", B_OPS)
    srv.submit_client("C", C_OPS)
    assert srv.pump() == 2
    assert srv.pump() == 1
    out, _ = srv.get_paths([(1, 12), (5, 5)])
    assert out[0] == (True, [1, 12])
    return srv


# -- export round-trip ------------------------------------------------------

def test_trace_roundtrip_through_trace_view(tmp_path):
    with trace.capture() as rec:
        with trace.span("outer", kind="test"):
            with trace.span("inner", step=0):
                pass
            with trace.span("inner", step=1):
                pass
        trace.counter("ring.occupancy", 3)
        path = rec.save(str(tmp_path / "t.json"))

    doc = json.loads(pathlib.Path(path).read_text())
    assert set(doc) == {"traceEvents", "displayTimeUnit"}
    assert len(doc["traceEvents"]) == 5          # clock_sync + 3 spans + 1 C
    assert doc["traceEvents"][0]["name"] == "clock_sync"

    tv = _load_tool("trace_view")
    events = tv.load(path)
    summ = tv.summarize(events)
    assert "clock_sync" not in summ["spans"]     # metadata is skipped
    assert summ["spans"]["inner"]["count"] == 2
    assert summ["spans"]["outer"]["count"] == 1
    assert summ["spans"]["outer"]["total_us"] > 0
    assert summ["counters"]["ring.occupancy"] == 1
    tv.print_summary(summ)  # must not raise on a real summary


def test_trace_view_accepts_bare_event_list(tmp_path):
    tv = _load_tool("trace_view")
    p = tmp_path / "bare.json"
    p.write_text(json.dumps([{"name": "x", "ph": "X", "ts": 0.0,
                              "dur": 1.0, "pid": 1, "tid": 1}]))
    assert tv.summarize(tv.load(str(p)))["spans"]["x"]["count"] == 1


def test_span_nesting_is_timestamp_containment():
    with trace.capture() as rec:
        with trace.span("parent"):
            with trace.span("child"):
                time.sleep(0.001)
    by_name = {e["name"]: e for e in rec.events()}
    p, c = by_name["parent"], by_name["child"]
    assert p["tid"] == c["tid"]
    assert p["ts"] <= c["ts"]
    assert c["ts"] + c["dur"] <= p["ts"] + p["dur"]


def test_capture_restores_disabled_state_and_isolates_events():
    assert not trace.enabled()
    with trace.capture() as rec:
        assert trace.enabled()
        with trace.span("only"):
            pass
        assert [e["name"] for e in rec.events()] == ["clock_sync", "only"]
    assert not trace.enabled()
    with trace.span("dropped"):   # disabled: the null span records nothing
        pass
    with trace.capture() as rec2:  # fresh capture holds no span
        assert [e["ph"] for e in rec2.events()] == ["M"]


# -- metrics registry + stat views -----------------------------------------

def test_metrics_registry_typed_behaviour():
    reg = MetricsRegistry()
    reg.declare("a.count", "counter")
    reg.declare("a.count", "counter")            # idempotent
    with pytest.raises(ValueError, match="re-declared"):
        reg.declare("a.count", "gauge")
    with pytest.raises(ValueError, match="unknown metric kind"):
        reg.declare("a.bad", "timer")

    reg.declare("a.lat_s", "histogram")
    with pytest.raises(TypeError, match="observe"):
        reg.set("a.lat_s", 1.0)
    reg.observe("a.lat_s", 2.0)
    reg.observe("a.lat_s", 0.5)
    assert reg.get("a.lat_s") == {"count": 2, "sum": 2.5,
                                  "min": 0.5, "max": 2.0}

    reg.inc("a.count", 3)
    assert reg.get("a.count") == 3
    assert reg.names() == ["a.count", "a.lat_s"]
    snap = reg.snapshot()
    snap["a.lat_s"]["count"] = 99                # snapshot is a copy
    assert reg.get("a.lat_s")["count"] == 2


def test_stats_view_routes_fields_to_registry():
    reg = MetricsRegistry()
    s = IngestStats(reg)
    s.submitted += 2
    s.wait_max_s = 3.5
    assert reg.get("ingest.submitted") == 2
    assert reg.get("ingest.wait_max_s") == 3.5
    assert s.snapshot()["submitted"] == 2
    assert set(s.snapshot()) == set(IngestStats._SPEC)
    assert "submitted=2" in repr(s)
    with pytest.raises(AttributeError, match="no field"):
        s.nonexistent_field


def test_global_registry_predeclares_every_obs_metric():
    assert global_registry() is GLOBAL
    for name, (kind, _doc) in OBS_METRICS.items():
        assert GLOBAL.kind(name) == kind


def test_metrics_doc_drift_check_passes_on_this_repo():
    """The CI drift gate, run in-process: every declared metric name is in
    DESIGN.md §14's table, and the §14 extractor actually isolates §14."""
    cmd = _load_tool("check_metrics_doc")
    sec = cmd.section_14((ROOT / "DESIGN.md").read_text(encoding="utf-8"))
    assert sec.startswith("## §14")
    assert "## §13" not in sec
    names = cmd.declared_metrics()
    assert "bfs.supersteps" in names and "serve.wall_s" in names
    assert [n for n in names if n not in sec] == []
    assert cmd.main() == 0


# -- disabled path is free --------------------------------------------------

def test_disabled_tracing_adds_zero_jit_retraces():
    """Cache-key pin: with tracing disabled, re-running the scripted
    ingest round + GetPath batch hits the existing jit caches — the
    instrumentation never perturbs a traced signature (DESIGN.md §14)."""
    assert not trace.enabled()
    _scripted_round(_fake_clock())              # warm every cache
    sizes = {f.__name__: f._cache_size() for f in
             (bfs_mod._multi_bfs_jit, bfs_mod._multi_superstep_jit,
              snapshot_mod._collect_batch_jit,
              snapshot_mod._collect_batch_finish_jit)}
    assert sizes["_collect_batch_jit"] >= 1
    assert sizes["_multi_superstep_jit"] == 0   # traced-only entry point
    _scripted_round(_fake_clock())              # identical second run
    for fn in (bfs_mod._multi_bfs_jit, bfs_mod._multi_superstep_jit,
               snapshot_mod._collect_batch_jit,
               snapshot_mod._collect_batch_finish_jit):
        assert fn._cache_size() == sizes[fn.__name__], fn.__name__


def test_disabled_span_overhead_under_5pct_of_ingest_round():
    """The wall budget: (cost of one disabled span shell) x (number of
    spans the workload would emit) must stay under 5% of the workload's
    measured wall. Span count comes from an enabled capture of the SAME
    scripted workload; the fake pool clock keeps admission deterministic."""
    with trace.capture() as rec:
        _scripted_round(_fake_clock())
        n_spans = len(rec.events())
    assert n_spans >= 10                         # the workload is instrumented

    assert not trace.enabled()
    reps = 20000
    t0 = time.perf_counter()
    for _ in range(reps):
        with trace.span("x", a=1):
            pass
    per_span = (time.perf_counter() - t0) / reps

    t0 = time.perf_counter()
    _scripted_round(_fake_clock())
    wall = time.perf_counter() - t0

    overhead = per_span * n_spans
    assert overhead < 0.05 * wall, (
        f"{n_spans} disabled spans cost {overhead*1e6:.1f}us "
        f"vs round wall {wall*1e3:.1f}ms")


# -- enabled path is honest -------------------------------------------------

@pytest.mark.parametrize("backend", ["jnp", "hybrid", "packed"])
def test_traced_multi_bfs_bit_identical_to_jit(backend):
    g = _build(nv=10, extra_edges=[(OP_ADD_E, 9, 0), (OP_ADD_E, 2, 7)])
    srcs = jnp.asarray([int(find_slot(g, k)) for k in (0, 3, 9, 5)], jnp.int32)
    dsts = jnp.asarray([int(find_slot(g, k)) for k in (9, 3, 1, 0)], jnp.int32)

    base = multi_bfs(g, srcs, dsts, backend=backend)
    with trace.capture() as rec:
        traced = multi_bfs(g, srcs, dsts, backend=backend)
        steps = [e for e in rec.events() if e["name"] == "bfs.superstep"]
        sessions = [e for e in rec.events() if e["name"] == "bfs.session"]

    for f in base._fields:
        np.testing.assert_array_equal(np.asarray(getattr(base, f)),
                                      np.asarray(getattr(traced, f)), f)
    assert len(sessions) == 1
    assert len(steps) == int(base.supersteps)
    assert sessions[0]["args"]["supersteps"] == int(base.supersteps)
    dirs = {e["args"]["direction"] for e in steps}
    if backend != "hybrid":
        assert dirs == {"push"}                  # non-hybrid never pulls
    assert dirs <= {"push", "pull"}


def test_traced_multi_bfs_updates_global_superstep_counters():
    g = _build(nv=8)
    s = jnp.asarray([int(find_slot(g, 0))], jnp.int32)
    d = jnp.asarray([int(find_slot(g, 7))], jnp.int32)
    before = GLOBAL.get("bfs.supersteps")
    with trace.capture():
        res = multi_bfs(g, s, d, backend="jnp")
    assert GLOBAL.get("bfs.supersteps") - before == int(res.supersteps)


def test_traced_collect_batch_bit_identical_to_jit():
    g = _build(nv=10, extra_edges=[(OP_ADD_E, 4, 0)])
    ks = jnp.asarray([0, 5, 9], jnp.int32)
    ls = jnp.asarray([9, 2, 0], jnp.int32)

    base = collect_batch(g, ks, ls, engine="fused")
    with trace.capture() as rec:
        traced = collect_batch(g, ks, ls, engine="fused")
        assert any(e["name"] == "bfs.session" for e in rec.events())

    for f in base._fields:
        np.testing.assert_array_equal(np.asarray(getattr(base, f)),
                                      np.asarray(getattr(traced, f)), f)


def test_traced_get_paths_session_spans_and_answers():
    srv = GraphCoServer(capacity=32, ingest=True)
    srv.submit(A_OPS + B_OPS + C_OPS)
    with trace.capture() as rec:
        out, rounds = srv.get_paths([(1, 12), (11, 12), (12, 1)])
    assert out[0] == (True, [1, 12])
    assert out[1] == (True, [11, 12])
    assert out[2] == (False, [])
    names = [e["name"] for e in rec.events()]
    sess = [e for e in rec.events() if e["name"] == "session.get_paths"]
    assert len(sess) == 1
    assert sess[0]["args"]["pairs"] == 3
    assert sess[0]["args"]["rounds"] == rounds
    assert sess[0]["args"]["resolved"] in ("match", "epoch", "budget")
    assert names.count("collect.round") >= 2     # the double collect


# -- serving endpoint -------------------------------------------------------

def test_get_metrics_endpoint_snapshot():
    srv = GraphCoServer(capacity=32, ingest=True, index=True)
    srv.submit_client("A", A_OPS)
    srv.submit_client("B", B_OPS)
    assert srv.pump() == 2
    assert srv.index_tick() is True
    srv.get_reach([(1, 2), (11, 12)])

    m = srv.get_metrics()
    assert m["server.index_refreshes"] == 1
    assert m["server.index_hits"] == 2
    assert m["ingest.submitted"] == 2
    assert m["ingest.epochs"] == 1
    # epoch 0 (the empty initial state) is retained too
    assert (m["ring.window_lo"], m["ring.window_hi"]) == (0, 1)
    # every global tracing metric rides along, histogram or scalar
    for name in OBS_METRICS:
        assert name in m
    json.dumps(m)                                # plain JSON-serializable


def test_get_metrics_shares_pool_registry_with_stats_view():
    srv = GraphCoServer(capacity=16, ingest=True)
    srv.submit_client("A", A_OPS)
    srv.pump()
    assert srv.get_metrics()["ingest.applied"] == srv.pool.stats.applied == 1


# -- the admission round from inside the program ----------------------------

_ROUND_CHILDREN = ("ingest.admit", "ingest.make_batch", "ingest.fused_apply",
                   "wal.append", "ingest.publish", "ingest.ack", "ckpt.save")


def _inside(inner, outer):
    return (inner["tid"] == outer["tid"] and outer["ts"] <= inner["ts"]
            and inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"])


@pytest.fixture(scope="module")
def traced_rounds(tmp_path_factory):
    """Three traced admission rounds of a durable pool (warmed untraced
    first, so no round compiles): A+B coalesce, then C, then A again."""
    srv = GraphCoServer(capacity=32, ingest=True,
                        wal_dir=str(tmp_path_factory.mktemp("wal")))
    srv.submit_client("W", A_OPS)
    srv.pump()
    with trace.capture() as rec:
        for batch in ((A_OPS, B_OPS, C_OPS), (), (A_OPS,)):
            for i, ops in enumerate(batch):
                srv.submit_client(f"c{i}", ops)
            srv.pump()
        events = rec.events()
    return [e for e in events if e["ph"] == "X"]


def test_traced_pump_spans_enclose_the_round_work(traced_rounds):
    rounds = [e for e in traced_rounds if e["name"] == "ingest.round"]
    assert len(rounds) == 3
    for r in rounds:
        kids = [e for e in traced_rounds
                if e["name"] in _ROUND_CHILDREN and _inside(e, r)]
        names = [e["name"] for e in sorted(kids, key=lambda e: e["ts"])]
        assert names == ["ingest.admit", "ingest.make_batch",
                         "ingest.fused_apply", "wal.append",
                         "ingest.publish", "ingest.ack"]
        pub = next(e for e in kids if e["name"] == "ingest.publish")
        ring = [e for e in traced_rounds
                if e["name"].startswith("ring.") and _inside(e, pub)]
        ring.sort(key=lambda e: e["ts"])
        assert [e["name"] for e in ring] == ["ring.delta", "ring.to_host"]
        assert ring[0]["args"]["rows"] >= 0 and ring[0]["args"]["bytes"] > 0
        assert r["args"]["admitted"] == len(r["args"]["batch_ids"]) >= 1
    # B's and C's inserts change rows; the third round's A is a no-op
    assert [e["args"]["rows"] > 0 for e in traced_rounds
            if e["name"] == "ring.delta"] == [True, True, False]


def test_traced_round_self_time_is_small(traced_rounds):
    """What no child span names (lock release, stats, WAL record build)
    stays under a fifth of the round, even on a CPU."""
    rounds = [e for e in traced_rounds if e["name"] == "ingest.round"]
    kids = [e for e in traced_rounds if e["name"] in _ROUND_CHILDREN]
    total = sum(r["dur"] for r in rounds)
    inner = sum(k["dur"] for r in rounds for k in kids if _inside(k, r))
    assert 0 <= total - inner < 0.2 * total


def test_admit_wait_histogram_counts_each_admitted_batch():
    """Fake clock: submits at 0, 1, 2; round 1 admits A and B at 3 (waits
    3, 2) and reads the clock once more after its apply (4); round 2 admits
    C at 5 (wait 3): three observations summing to 8."""
    before = dict(GLOBAL.get("ingest.admit_wait_s"))
    with trace.capture():
        _scripted_round(_fake_clock())
    after = GLOBAL.get("ingest.admit_wait_s")
    assert after["count"] - before["count"] == 3
    assert after["sum"] - before["sum"] == 8.0


def test_export_holds_one_clock_sync_on_perf_counter():
    t_lo = time.perf_counter_ns()
    with trace.capture() as rec:
        with trace.span("timed"):
            pass
    t_hi = time.perf_counter_ns()
    evs = rec.export()["traceEvents"]
    syncs = [e for e in evs if e["name"] == "clock_sync"]
    assert len(syncs) == 1 and syncs[0]["ph"] == "M"
    origin = syncs[0]["args"]["perf_counter_ns"]
    span = next(e for e in evs if e["name"] == "timed")
    start = origin + 1e3 * span["ts"]
    assert t_lo - 1e3 <= start and start + 1e3 * span["dur"] <= t_hi + 1e3


def test_untraced_round_reads_the_clock_as_before():
    """Untraced: the three submits and the two rounds' post-apply reads,
    exactly (the pinned waits of tests/test_serving_stats depend on it).
    Traced: one more read per admitting round, for the admission wait."""
    calls = []
    fake = _fake_clock()

    def clock():
        calls.append(1)
        return fake()

    assert not trace.enabled()
    _scripted_round(clock)
    assert len(calls) == 5
    calls.clear()
    fake = _fake_clock()
    with trace.capture():
        _scripted_round(clock)
    assert len(calls) == 7
