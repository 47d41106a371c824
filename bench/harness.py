"""One run of one cell: set-up, the measured window, and the check.

Set-up generates the configuration's graph and the mix's traffic from the
seed, builds ``GraphCoServer(ingest=True, wal_dir=...)`` in this process,
loads the graph through the server's own write path (``submit_client`` +
``pump``), and warms every shape the window can use. The window drives the
server from one host thread, one admission round at a time, through its
public calls only: ``submit_client``, ``pump``, ``get_paths`` /
``get_reach`` and ``get_metrics``. After the window every request due in it
is waited for, then three comparisons decide ``correct``:

* ``code_mismatch``: every acknowledged lane's result code against the
  plain reference (``bench/reference.py``) replayed in the server's
  linearization order; ``order_faults``: that order must hold every
  acknowledged batch once, in acknowledgement order;
* ``path_mismatch``: every answered query against the reference at the
  prefix of acknowledged batches it was asked after;
* ``lost_acks``: the server restarts from its WAL and checkpoints, with
  every byte not fsync-durable discarded first, and must read back every
  acknowledged batch and the state every window write left;
* ``unanswered``: requests due in the window that never got an answer.

Each is an exact count with limit 0.
"""
from __future__ import annotations

import json
import math
import os
import pathlib
import shutil
import sys
import time
from dataclasses import dataclass, field

import numpy as np

from bench import datagen, loadgen, trace_reduce
from bench.reference import OPCODES, ReferenceGraph
from bench.registry import Registry

_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_COMPILE = "/jax/core/compile/backend_compile_duration"
DRAIN_S = 60.0
OP_NOP, OP_CON_V, OP_CON_E = OPCODES["NOP"], OPCODES["ConV"], OPCODES["ConE"]
_VERTEX = (OPCODES["AddV"], OPCODES["RemV"], OP_CON_V)


def use_checkout_cache(root) -> None:
    """Keep JAX's persistent compilation cache at ``<root>/.jax_cache``, a
    fixed path inside the checkout, for every program this process
    compiles, the program's own entry points included."""
    import jax

    cache = str(pathlib.Path(root) / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def pow2(n: int, floor: int = 1) -> int:
    p = floor
    while p < n:
        p <<= 1
    return p


# ----------------------------------------------------------------------------
# Durability: which bytes reached the disk
# ----------------------------------------------------------------------------
class FsyncLedger:
    """Records, for every file fsynced in this process, its size at its
    latest fsync. ``crash(dir)`` then cuts every file under ``dir`` back to
    that size (to nothing if it was never fsynced), as a power cut would:
    killing the process keeps the page cache, so the check drops unflushed
    bytes itself."""

    def __init__(self):
        self.sizes: dict = {}
        self._real = None

    def __enter__(self):
        self._real = real = os.fsync

        def fsync(fd):
            real(fd)
            st = os.fstat(fd)
            self.sizes[(st.st_dev, st.st_ino)] = st.st_size

        os.fsync = fsync
        return self

    def __exit__(self, *exc):
        os.fsync = self._real
        return False

    def crash(self, directory: pathlib.Path) -> int:
        """Truncate to the durable prefix; returns bytes dropped."""
        dropped = 0
        for p in sorted(directory.rglob("*")):
            if not p.is_file():
                continue
            st = p.stat()
            keep = self.sizes.get((st.st_dev, st.st_ino), 0)
            if st.st_size > keep:
                dropped += st.st_size - keep
                os.truncate(p, keep)
        return dropped


# ----------------------------------------------------------------------------
# Client-side bookkeeping of one server
# ----------------------------------------------------------------------------
@dataclass
class Batch:
    client: str
    ops: list
    ticket: object
    phase: str
    due: float
    submit_pump: int
    acked: float | None = None
    ack_pump: int | None = None
    failed: str | None = None


@dataclass
class Query:
    pair: tuple
    phase: str
    due: float
    pumps: int = 0
    answered: float | None = None
    found: bool = False
    keys: list | None = None


@dataclass
class Session:
    """Every request sent to one server, with the host times it was due,
    acknowledged or answered, and how many admission rounds had run."""

    srv: object
    query_kind: str = "get_paths"
    max_batch: int = 16
    clock: object = time.perf_counter
    phase: str = "setup"
    pumps: int = 0
    batches: list = field(default_factory=list)
    queries: list = field(default_factory=list)
    open: list = field(default_factory=list)
    pump_spans: list = field(default_factory=list)
    query_spans: list = field(default_factory=list)

    def submit(self, client: str, ops: list, due: float | None = None
               ) -> Batch:
        now = self.clock()
        t = self.srv.submit_client(client, ops)
        b = Batch(client, ops, t, self.phase, now if due is None else due,
                  self.pumps)
        self.batches.append(b)
        if t.status == "queued":
            self.open.append(b)
        else:
            b.failed = t.status
        return b

    def pump(self) -> list[Batch]:
        """One admission round; returns the batches it acknowledged."""
        import jax

        t0 = self.clock()
        with jax.profiler.TraceAnnotation("bench.pump"):
            self.srv.pump()
        t1 = self.clock()
        self.pumps += 1
        self.pump_spans.append((t0, t1))
        acked, still = [], []
        for b in self.open:
            status = b.ticket.status
            if status == "applied":
                b.acked, b.ack_pump = t1, self.pumps
                acked.append(b)
            elif status == "queued":
                still.append(b)
            else:
                b.failed = status
        self.open = still
        return acked

    def settle(self, deadline: float = math.inf) -> None:
        while self.open and self.clock() < deadline:
            self.pump()

    def ask(self, queries: list[Query]) -> None:
        """Answer up to ``max_batch`` queries in one call, padded to a power
        of two (capped at ``max_batch``) by repeating the last pair, so that
        the window uses a few warmed shapes."""
        import jax

        pairs = [q.pair for q in queries]
        size = min(pow2(len(pairs)), self.max_batch)
        padded = pairs + [pairs[-1]] * (size - len(pairs))
        t0 = self.clock()
        with jax.profiler.TraceAnnotation("bench.query"):
            if self.query_kind == "get_paths":
                out, _ = self.srv.get_paths(padded)
                answers = [(bool(f), [int(x) for x in k]) for f, k in out]
            else:
                res = self.srv.get_reach(padded)
                answers = [(bool(f), None) for f in res.found]
        t1 = self.clock()
        self.query_spans.append((t0, t1))
        for q, (found, keys) in zip(queries, answers):
            q.pumps, q.answered, q.found, q.keys = self.pumps, t1, found, keys
            self.queries.append(q)


# ----------------------------------------------------------------------------
# Set-up
# ----------------------------------------------------------------------------
def build_server(cfg: dict, state_dir: pathlib.Path):
    from repro.runtime.serve_loop import GraphCoServer

    s = cfg["server"]
    os.environ["REPRO_BFS_BACKEND"] = s["bfs_backend"]
    return GraphCoServer(
        capacity=cfg["capacity"], ingest=True, index=s["index"],
        max_inflight=s["max_inflight"],
        max_coalesce_lanes=s["max_coalesce_lanes"],
        retain_epochs=s["retain_epochs"], wal_dir=str(state_dir),
        ckpt_every=s["ckpt_every"])


def nop_chunks(ops: list, size: int) -> list[list]:
    """``ops`` cut into ``size``-lane batches, the last NOP-padded, so that
    every batch compiles to one shape."""
    ops = ops + [(OP_NOP,)] * (-len(ops) % size)
    return [ops[i:i + size] for i in range(0, len(ops), size)]


def load_graph(ses: Session, keys, edges, batch: int) -> None:
    """The initial graph through the server's write path, as a bulk loader
    would send it: every vertex, then every edge, in ``batch``-op client
    batches, each acknowledged before the next is sent."""
    add_v, add_e = OPCODES["AddV"], OPCODES["AddE"]
    vops = [(add_v, int(k)) for k in keys]
    eops = [(add_e, int(a), int(b)) for a, b in edges]
    for ops in nop_chunks(vops, batch) + nop_chunks(eops, batch):
        ses.submit("loader", ops)
        ses.settle()


def lane_pads(cfg: dict, mix: dict, stream: loadgen.OpStream) -> list[int]:
    """Every padded lane count an admission round of this mix can take:
    the pool pads a round to a power of two (at least 8) and coalesces at
    most ``max_inflight`` batches within ``max_coalesce_lanes``."""
    s = cfg["server"]
    sizes = [len(t["ops"]) for t in stream.templates]
    if not sizes:
        return []
    lo = min(sizes) * stream.per_batch
    hi = max(sizes) * stream.per_batch
    most = min(s["max_inflight"] * hi, max(hi, s["max_coalesce_lanes"]))
    pads, p = [], pow2(lo, 8)
    while p <= pow2(most, 8):
        pads.append(p)
        p <<= 1
    return pads


def warm_up(ses: Session, cfg: dict, mix: dict, stream, keys, edges
            ) -> None:
    """Compile every shape the window can use, through the same calls:
    one read-only round at each padded lane count, and one query call at
    each padded query batch size."""
    cap = cfg["capacity"]
    for pad in lane_pads(cfg, mix, stream):
        ses.submit("warmup", [(OP_CON_V, i % cap) for i in range(pad)])
        ses.settle()
    q = mix.get("queries")
    if q is None:
        return
    if cfg["server"]["index"]:
        ses.srv.index_tick()
    pair = (int(keys[0]), int(keys[-1]))
    for size in sorted({min(pow2(n), ses.max_batch)
                        for n in range(1, ses.max_batch + 1)}):
        ses.ask([Query(pair, "setup", ses.clock()) for _ in range(size)])
    if ses.query_kind == "get_paths":
        # the server materializes each path at its own length, a program
        # per length: ask one pair at every distance the graph has
        pairs = pairs_by_distance(edges, np.random.default_rng(len(keys)))
        for i in range(0, len(pairs), ses.max_batch):
            ses.ask([Query(p, "setup", ses.clock())
                     for p in pairs[i:i + ses.max_batch]])


def pairs_by_distance(edges, rng, sources: int = 4) -> list[tuple]:
    """One (src, dst) pair at each distance 1..D of the initial graph, D
    the largest eccentricity among a few random sources."""
    indptr, nbr = loadgen.csr(edges)
    best: list[tuple] = []
    for src in rng.choice(np.unique(np.asarray(edges)[:, 0]), sources):
        dist = loadgen.bfs_levels(indptr, nbr, int(src))
        pairs = [(int(src), int(np.flatnonzero(dist == d)[0]))
                 for d in range(1, int(dist.max()) + 1)]
        if len(pairs) > len(best):
            best = pairs
    return best


# ----------------------------------------------------------------------------
# The window
# ----------------------------------------------------------------------------
def closed_window(ses: Session, stream, mix: dict, seconds: float):
    """Closed loop: each client keeps one batch in flight and sends its
    next one as soon as the last is acknowledged, until the window ends."""
    t0 = ses.clock()
    end = t0 + seconds
    for c in range(int(mix["clients"])):
        ses.submit(f"client{c}", stream.next_batch())
    while True:
        if ses.clock() >= end:
            break
        for b in ses.pump():
            if ses.clock() < end:
                ses.submit(b.client, stream.next_batch())
    return t0, ses.clock(), [], []


def open_window(ses: Session, schedule: list, mix: dict, seconds: float):
    """Open loop: each update batch and query is released at its due time,
    whatever the server's backlog; each loop pass runs one admission round
    and one query call of at most ``max_batch`` due queries. Returns the
    window's bounds and every release's lag behind its due time."""
    import jax

    t0 = ses.clock()
    i, waiting, lags, seq = 0, [], [], 0
    while True:
        now = ses.clock() - t0
        if now >= seconds:
            break
        while i < len(schedule) and schedule[i][0] <= now:
            due, kind, payload = schedule[i]
            lags.append(now - due)
            if kind == "update":
                ses.submit(f"u{seq % 64}", payload, due=t0 + due)
                seq += 1
            else:
                waiting.append(Query(payload, ses.phase, t0 + due))
            i += 1
        worked = False
        if ses.open:
            ses.pump()
            worked = True
        if waiting:
            ses.ask(waiting[:ses.max_batch])
            del waiting[:ses.max_batch]
            worked = True
        if not worked:
            nxt = schedule[i][0] if i < len(schedule) else seconds
            with jax.profiler.TraceAnnotation("bench.wait"):
                time.sleep(max(0.0, min(nxt, seconds) - (ses.clock() - t0)))
    return t0, ses.clock(), waiting, lags


# ----------------------------------------------------------------------------
# The check
# ----------------------------------------------------------------------------
def check_order(ses: Session, lin: list) -> int:
    """Faults in the claimed linearization: acknowledged batches missing or
    repeated, unknown ids, and batches ordered against acknowledgement
    order (a batch acknowledged before another was sent must come first)."""
    acked = {b.ticket.batch_id: b for b in ses.batches if b.ack_pump}
    claimed = set(lin)
    faults = len(lin) - len(claimed)
    faults += sum(1 for bid in claimed if bid not in acked)
    faults += sum(1 for bid in acked if bid not in claimed)
    last = 0
    for bid in lin:
        b = acked.get(bid)
        if b is None:
            continue
        if b.ack_pump < last or b.ack_pump <= b.submit_pump:
            faults += 1
        last = max(last, b.ack_pump)
    return faults


def replay(ses: Session, lin: list, notes: list) -> tuple[int, int,
                                                         ReferenceGraph]:
    """Replay the acknowledged batches in linearization order through the
    reference; check every lane's result code and every query answer at
    the prefix it was asked after. Returns (code mismatches, query
    mismatches, final reference)."""
    ref = ReferenceGraph()
    by_id = {b.ticket.batch_id: b for b in ses.batches if b.ack_pump}
    qs = sorted(ses.queries, key=lambda q: q.pumps)
    qi, codes, paths = 0, 0, 0

    def check_queries(upto: float):
        nonlocal qi, paths
        while qi < len(qs) and qs[qi].pumps < upto:
            q = qs[qi]
            k, l = q.pair
            why = (ref.path_fault(k, l, q.found, q.keys)
                   if q.keys is not None or ses.query_kind == "get_paths"
                   else ref.reach_fault(k, l, q.found))
            if why is not None:
                paths += 1
                if len(notes) < 10:
                    notes.append(f"query {k}->{l} after {q.pumps} rounds: "
                                 f"{why}")
            qi += 1

    for bid in lin:
        b = by_id.get(bid)
        if b is None:
            continue
        check_queries(b.ack_pump)
        res = np.asarray(b.ticket.results)
        for j, op in enumerate(b.ops):
            want = ref.apply(op)
            if j >= len(res) or int(res[j]) != want:
                codes += 1
                if len(notes) < 10:
                    got = int(res[j]) if j < len(res) else None
                    notes.append(f"batch {bid} lane {j} {op}: server {got},"
                                 f" reference {want}")
    check_queries(math.inf)
    return codes, paths, ref


def readback_ops(batches: list[Batch]) -> list[tuple]:
    """One read per entity the given batches wrote or read: ConV of each
    vertex key, ConE of each edge."""
    seen, out = set(), []
    for b in batches:
        for op in b.ops:
            if op[0] == OP_NOP:
                continue
            rd = ((OP_CON_V, op[1]) if op[0] in _VERTEX
                  else (OP_CON_E, op[1], op[2]))
            if rd not in seen:
                seen.add(rd)
                out.append(rd)
    return out


def restart_and_read(ses: Session, ledger: FsyncLedger, state_dir, batch: int,
                     notes: list):
    """Make sure the last acknowledged round is in the WAL tail (not only in
    a checkpoint), cut every file back to its fsynced bytes, restart the
    server from disk, and read back. Returns (recovered linearization,
    readback ops, their results)."""
    srv = ses.srv
    window = [b for b in ses.batches if b.phase == "window" and b.ack_pump]
    ses.phase = "probe"
    for _ in range(3):
        saves = srv.get_metrics()["ingest.ckpt_saves"]
        ses.submit("probe", [(OP_CON_V, i) for i in range(batch)])
        ses.settle()
        if srv.get_metrics()["ingest.ckpt_saves"] == saves:
            break
    lin_before = list(srv.pool.linearization)
    dropped = ledger.crash(state_dir)
    notes.append(f"restart: {dropped} bytes were not fsync-durable")
    reads = readback_ops(window)
    srv.enter_degraded()
    try:
        srv.recover_now()
    except Exception as e:  # a refused restart loses every acked write
        notes.append(f"restart failed: {type(e).__name__}: {str(e)[:300]}")
        return lin_before, [], reads, [None] * len(reads)
    lin_after = list(srv.pool.linearization)
    rb = Session(srv)
    results = []
    for ops in nop_chunks(reads, batch):
        b = rb.submit("readback", ops)
        rb.settle()
        results.extend(np.asarray(b.ticket.results).tolist()
                       if b.ack_pump else [None] * len(ops))
    return lin_before, lin_after, reads, results[:len(reads)]


def lost_acks(lin_before, lin_after, reads, results, ref, notes) -> int:
    kept = set(lin_after)
    lost = sum(1 for bid in lin_before if bid not in kept)
    for op, got in zip(reads, results):
        want = ref.apply(op)
        if got != want:
            lost += 1
            if len(notes) < 10:
                notes.append(f"after restart {op}: server {got}, "
                             f"reference {want}")
    return lost


# ----------------------------------------------------------------------------
# A whole run
# ----------------------------------------------------------------------------
@dataclass
class RunData:
    """What the per-layer readers in ``bench/metrics`` read."""

    seconds: float
    t_open: float
    t_close: float
    batches: list
    queries: list
    pump_spans: list
    query_spans: list
    counters_open: dict
    counters_close: dict
    program_spans: list | None = None
    trace: trace_reduce.Trace | None = None


class Compiles:
    """Lowerings and XLA compilations seen while open."""

    def __init__(self):
        self.lowered = 0
        self.compiled = 0
        self.compile_s = 0.0

    def _on(self, event, duration, **_):
        if event == _LOWER:
            self.lowered += 1
        elif event == _COMPILE:
            self.compiled += 1
            self.compile_s += duration

    def __enter__(self):
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(self._on)
        return False


def window_metrics(ses: Session, t0: float, t1: float, closed: bool,
                   unasked: list) -> tuple[dict, int, int]:
    """End-to-end numbers of the window from the client's side, over every
    request due in it: the rate counts the lanes acknowledged inside the
    window, the tails every request from its due time to its answer, and a
    request never answered counts at the drain deadline, so it misses any
    limit. Returns (values, attempted, failed)."""
    wb = [b for b in ses.batches if b.phase == "window"]
    wq = [q for q in ses.queries if q.phase == "window"]
    wq += unasked
    never = t1 + DRAIN_S
    failed = sum(1 for b in wb if not b.ack_pump)
    failed += sum(1 for q in wq if q.answered is None)
    lanes = sum(len(b.ops) for b in wb if b.ack_pump and b.acked <= t1)
    ack_ms = [((b.acked if b.ack_pump else never) - b.due) * 1e3 for b in wb]
    q_ms = [((q.answered if q.answered is not None else never) - q.due) * 1e3
            for q in wq]
    vals = {
        "mut_ops_per_s": lanes / (t1 - t0),
        "mut_ack_p95_ms": loadgen.percentile(ack_ms, 95),
        "query_p95_ms": loadgen.percentile(q_ms, 95),
    }
    log(f"window: {t1 - t0} s, {'closed' if closed else 'open'} loop; "
        f"{len(wb)} batches ({lanes} lanes acked in the window), "
        f"ack samples {len(ack_ms)} p50 {loadgen.percentile(ack_ms, 50)} ms;"
        f" {len(wq)} queries, samples {len(q_ms)} p50 "
        f"{loadgen.percentile(q_ms, 50)} ms; failed {failed}")
    return vals, len(wb) + len(wq), failed


def run_cell(root, workload: str, seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True, t_start: float | None = None,
             on_window=None) -> dict:
    """One run of one cell; returns the result object (the last line of
    output). ``on_window(srv)``, when given, is called as the window opens
    (the controls and the planted faults use it); it may return a callable
    that undoes what it did."""
    import jax

    t_start = time.perf_counter() if t_start is None else t_start
    root = pathlib.Path(root)
    reg = Registry(root)
    cell = reg.workload(workload)
    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu"
                        or len(devices) < cell["chips"]):
        raise NoChip(f"{workload} needs {cell['chips']} TPU chip(s); JAX "
                     f"found {len(devices)} {devices[0].platform} device(s)")
    cfg = reg.config(cell["config"])
    mix = reg.traffic(cell["traffic"])
    run_dir = root / ".bench_run" / workload
    shutil.rmtree(run_dir, ignore_errors=True)
    state_dir = run_dir / "state"
    state_dir.mkdir(parents=True)
    notes: list[str] = []
    undo: list = []
    try:
        with FsyncLedger() as ledger, Compiles() as comp:
            return _run(reg, cell, cfg, mix, seed, seconds, trace, t_start,
                        run_dir, state_dir, ledger, comp, notes,
                        on_window, undo, devices[:cell["chips"]])
    finally:
        for u in undo:
            if u is not None:
                u()
        shutil.rmtree(run_dir, ignore_errors=True)


def _run(reg, cell, cfg, mix, seed, seconds, trace, t_start, run_dir,
         state_dir, ledger, comp, notes, on_window, undo, devices) -> dict:
    import jax

    from repro.obs import trace as obs

    parts = {}
    t = time.perf_counter()
    rng = np.random.default_rng(seed)
    keys, edges = datagen.initial_graph(cfg["generator"], cfg["capacity"],
                                        int(rng.integers(2 ** 63)))
    stream = loadgen.OpStream(mix, cfg["capacity"], keys, edges, rng)
    closed = mix["loop"] == "closed"
    schedule = None if closed else loadgen.open_schedule(stream, seconds, rng)
    parts["generate_s"] = time.perf_counter() - t

    t = time.perf_counter()
    srv = build_server(cfg, state_dir)
    q = mix.get("queries") or {}
    ses = Session(srv, q.get("kind", "get_paths"), int(q.get("max_batch", 16)))
    load_graph(ses, keys, edges, cfg["load_batch"])
    parts["load_s"] = time.perf_counter() - t
    t = time.perf_counter()
    warm_up(ses, cfg, mix, stream, keys, edges)
    parts["warmup_s"] = time.perf_counter() - t
    t = time.perf_counter()
    srv.pool.checkpoint_now()
    parts["checkpoint_s"] = time.perf_counter() - t
    parts["compile_s"] = comp.compile_s
    setup_s = time.perf_counter() - t_start
    log(f"setup: {json.dumps(parts)}; {len(keys)} vertices, {len(edges)} "
        f"edges, {ses.pumps} admission rounds")

    program_spans = None
    tdir = run_dir / "trace"
    if on_window is not None:
        undo.append(on_window(srv))
    lowered0, compiled0 = comp.lowered, comp.compiled
    counters_open = srv.get_metrics()
    ses.phase = "window"
    if trace:
        if q == {}:
            obs.enable(fresh=True)
        jax.profiler.start_trace(str(tdir))
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
        if closed:
            t0, t1, waiting, lags = closed_window(ses, stream, mix, seconds)
        else:
            t0, t1, waiting, lags = open_window(ses, schedule, mix, seconds)
    if trace:
        jax.profiler.stop_trace()
        if obs.enabled():
            program_spans = obs.recorder().events()
            obs.disable()
    counters_close = srv.get_metrics()
    in_window = (comp.lowered - lowered0, comp.compiled - compiled0)

    # wait for every request due in the window: a late answer is late (its
    # latency counts the wait), only one that never comes has failed
    deadline = t1 + DRAIN_S
    ses.settle(deadline)
    while waiting and ses.clock() < deadline:
        ses.ask(waiting[:ses.max_batch])
        del waiting[:ses.max_batch]
    vals, attempted, failed_n = window_metrics(ses, t0, t1, closed, waiting)
    stats = [d.memory_stats() or {} for d in devices]
    peak = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)

    unanswered = sum(1 for b in ses.batches if b.phase == "window"
                     and not b.ack_pump) + len(waiting)
    t = time.perf_counter()
    lin_before, lin_after, reads, results = restart_and_read(
        ses, ledger, state_dir, cfg["load_batch"], notes)
    capacity = int(ses.srv.state.capacity)
    ses.srv = srv = None
    order = check_order(ses, lin_before)
    codes, paths, ref = replay(ses, lin_before, notes)
    lost = lost_acks(lin_before, lin_after, reads, results, ref, notes)
    check_s = time.perf_counter() - t
    if capacity != cfg["capacity"]:
        notes.append(f"capacity grew to {capacity}")

    d = devices[0]
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": peak}
    out = {"correct": None, "attempted": attempted, "failed": failed_n}
    if trace:
        tr = trace_reduce.load(trace_reduce.find_xplane(str(tdir)))
        lo, hi = tr.window
        device["busy_s"] = trace_reduce.busy_ns(tr) / 1e9
        device["window_s"] = (hi - lo) / 1e9
        run = RunData(seconds, t0, t1,
                      [b for b in ses.batches if b.phase == "window"],
                      [x for x in ses.queries if x.phase == "window"],
                      [s for s in ses.pump_spans if t0 <= s[0] < t1],
                      [s for s in ses.query_spans if t0 <= s[0] < t1],
                      counters_open, counters_close, program_spans, tr)
        metrics = {}
        for m in reg.per_layer(cell["name"]):
            v = reg.reader(m["name"])(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        out["metrics"] = metrics
        out["device"] = device
        out["breakdown"] = trace_reduce.breakdown(tr)
    else:
        vals["setup_s"] = setup_s
        out["metrics"] = {m["name"]: {"value": vals[m["name"]],
                                      "unit": m["unit"]}
                          for m in reg.end_to_end(cell["name"])}
        out["device"] = device
    checks = {"code_mismatch": codes, "order_faults": order,
              "path_mismatch": paths, "lost_acks": lost,
              "unanswered": unanswered}
    out["correct"] = all(v == 0 for v in checks.values())
    out["checks"] = {k: {"value": v, "limit": 0} for k, v in checks.items()}
    log(f"compiles in the window: {in_window[0]} lowered, "
        f"{in_window[1]} compiled; check took {check_s} s; batches acked "
        f"{sum(1 for b in ses.batches if b.ack_pump)}, queries answered "
        f"{len(ses.queries)}")
    if lags:
        log(f"open-loop release lag: p50 {loadgen.percentile(lags, 50)} s, "
            f"p95 {loadgen.percentile(lags, 95)} s over {len(lags)} "
            f"releases")
    for n in notes:
        log("note:", n)
    for k, v in checks.items():
        log(f"check {k} {v} limit 0")
    return out
