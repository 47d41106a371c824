"""Batched serving loop co-hosting LM decode and snapshot graph queries.

The serving runtime owns two resources:
  * an LM decode engine (prefill -> iterated decode_step over a KV cache)
  * a live concurrent graph (core/): mutator batches are applied between
    decode steps, and GetPath queries run the paper's double-collect
    protocol against the latest published state — non-blocking co-serving:
    queries never lock out mutations and vice versa (DESIGN.md §5(ii)).
    Query batches go through the fused multi-source BFS engine — Q
    reachability queries per shared double collect (DESIGN.md §7).
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (
    R_RECOVERING,
    R_TABLE_FULL,
    EpochEvictedError,
    GraphState,
    OpBatch,
    PathResult,
    apply_ops_fast,
    get_path_session,
    get_paths_session,
    grow,
    make_graph,
    make_op_batch,
)
from repro.core import partition
from repro.index import (
    build_index,
    index_fresh,
    reach_counts_session,
    reach_session,
    refresh,
)
from repro.obs import trace as _trace
from repro.obs.metrics import StatsView
from repro.obs.metrics import global_registry as _obs_registry
from repro.runtime.fault import SimulatedCrash


class ServeStats(StatsView):
    """Per-``serve()``-call observability (DESIGN.md §12, §13, §14).

    A ``MetricsRegistry``-backed view (fields stored under
    ``serve.<field>``): every field reports THIS call's activity — server-
    lifetime counters are snapshotted at serve start and reported as
    deltas, except the ``*_max`` high-water marks, which stay lifetime
    values (a max has no meaningful delta).
    """

    _PREFIX = "serve"
    _SPEC = {
        "decode_steps": ("counter", 0),
        "decode_tokens": ("counter", 0),
        "graph_ops": ("counter", 0),
        "getpath_calls": ("counter", 0),
        "getpath_rounds": ("counter", 0),
        "getpath_starved": ("gauge", 0),  # sessions whose collects never matched
        "epoch_resolved": ("gauge", 0),   # starved sessions resolved wait-free
        "tt_calls": ("gauge", 0),         # time-travel queries served
        "tt_evicted": ("gauge", 0),       # time-travel past the window
        "epoch_diff_calls": ("gauge", 0),  # epoch-diff audit queries served
        "grow_events": ("gauge", 0),      # auto-grows during THIS serve call
        "index_hits": ("gauge", 0),       # index fast-path answers
        "index_misses": ("gauge", 0),     # fused-BFS fallbacks
        "index_refreshes": ("gauge", 0),  # index builds/refreshes
        # -- multi-tenant admission observability (DESIGN.md §12) -----------
        "ingest_batches": ("gauge", 0),       # client batches applied
        "ingest_fused_calls": ("gauge", 0),   # coalesced device applies
        "ingest_coalesce_max": ("gauge", 0),  # max batches in one fused call
        "ingest_retries": ("gauge", 0),       # rounds lost to conflicts
        # total wait, enqueue -> end of the round's fused apply
        "ingest_wait_s": ("gauge", 0.0),
        "ingest_wait_max_s": ("gauge", 0.0),
        "ingest_queue_depth_max": ("gauge", 0),
        "ingest_epochs": ("gauge", 0),        # snapshot epochs published
        # -- durability / degraded mode (DESIGN.md §16) ---------------------
        "degraded_reads": ("gauge", 0),       # reads served off the pinned epoch
        "rejected_writes": ("gauge", 0),      # R_RECOVERING typed rejections
        "recoveries": ("gauge", 0),           # restart-from-recovery completions
        "wall_s": ("gauge", 0.0),
    }


@dataclass
class TimeTravelResult:
    """Typed answer of the time-travel reachability endpoint (DESIGN.md §13).

    ``evicted=True`` means the requested epoch left the bounded retention
    window (``window`` says what is still addressable) — the typed
    "epoch evicted" outcome, never an exception at the serving surface.
    """

    epoch: int
    evicted: bool
    window: tuple
    found: list = field(default_factory=list)    # [bool] per pair
    paths: list = field(default_factory=list)    # [(found, keys)] per pair


@dataclass
class EpochDiffResult:
    """Typed answer of the epoch-diff endpoint (DESIGN.md §13): which rows
    (and the keys occupying them at each end) changed between two retained
    epochs. ``evicted=True`` when either endpoint left the window."""

    e_from: int
    e_to: int
    evicted: bool
    window: tuple
    rows: list = field(default_factory=list)
    keys_before: list = field(default_factory=list)
    keys_after: list = field(default_factory=list)


class GraphCoServer:
    """Owns the live graph; publishes functional snapshots to queries.

    ``mesh=`` places the state as a ``ShardedGraphState`` (adjacency rows
    partitioned over the 1-D device mesh, DESIGN.md §8): mutation batches go
    through the distributed disjoint-access engine and query batches through
    the distributed fused multi-source BFS — bit-identical results to the
    single-device server, scaled past one chip's HBM.

    ``auto_grow`` (default on) realizes the paper's "unbounded" property at
    the serving surface: any R_TABLE_FULL lane triggers a capacity doubling
    and a replay of the whole batch against the grown pre-batch state, so
    ``submit`` never surfaces slot exhaustion to clients — directly or as
    cascaded VERTEX-NOT-PRESENT failures — and the returned results are
    one clean lane-order linearization.

    ``ingest=True`` attaches the multi-tenant admission pool
    (runtime/ingest.py, DESIGN.md §12): ``submit_client`` enqueues per-
    client batches, ``pump``/``flush`` run conflict-detected admission
    rounds that coalesce non-conflicting batches into fused applies, and
    ``state`` becomes the pool's double-buffered published snapshot epoch —
    readers never block behind admission.

    ``index=True`` maintains a versioned 2-hop reachability index
    (DESIGN.md §9): ``get_reach``/``get_reach_counts`` answer from the
    index whenever its epoch stamp matches the live version metadata (the
    freshness check doubles as the snapshot validation) and fall back to
    the fused BFS double collect otherwise — the index is an accelerator,
    never a consistency dependency, so mutations proceed untouched.
    ``index_tick()`` (called between decode steps by ``serve``) refreshes a
    stale index in the background of the serving loop: refresh runs on a
    functional snapshot and lands as a reference swap, so queries racing
    it simply keep falling back (non-blocking co-serving, DESIGN.md §5(ii)).
    """

    def __init__(self, capacity: int = 256, query_engine: str = "fused",
                 mesh=None, auto_grow: bool = True, index: bool = False,
                 index_landmarks: int | None = None, ingest: bool = False,
                 max_inflight: int = 8, max_coalesce_lanes: int = 256,
                 fault=None, on_conflict: str | None = None,
                 retain_epochs: int = 64, wal_dir: str | None = None,
                 ckpt_every: int = 0, heartbeat=None, failure_policy=None):
        self.mesh = mesh
        self.auto_grow = auto_grow
        self.query_engine = query_engine
        self.grow_events = 0
        self.index_enabled = bool(index)
        self.index_landmarks = index_landmarks
        self.index = None
        self.index_hits = 0
        self.index_misses = 0
        self.index_refreshes = 0
        # wait-free snapshot observability (DESIGN.md §13) — lifetime
        # counters, surfaced as per-serve deltas like the index ones
        self.getpath_starved = 0
        self.epoch_resolved = 0
        self.tt_calls = 0
        self.tt_evicted = 0
        self.epoch_diff_calls = 0
        # durability + degraded mode (DESIGN.md §16): while recovering,
        # reads pin to the last published epoch and writes get typed
        # R_RECOVERING rejections; Heartbeat suspects and SimulatedCrash
        # both funnel into the backoff-budgeted restart-from-recovery path
        self.degraded = False
        self.degraded_reads = 0
        self.rejected_writes = 0
        self.recoveries = 0
        self.heartbeat = heartbeat
        self.failure_policy = failure_policy
        self._pinned = None            # (epoch, state) while degraded
        self._capacity = int(capacity)
        self._retain_epochs = int(retain_epochs)
        self._max_inflight = int(max_inflight)
        self._max_coalesce_lanes = int(max_coalesce_lanes)
        self._fault = fault
        self._wal_dir = wal_dir
        self._ckpt_every = int(ckpt_every)
        self._ckpt = None
        dense = make_graph(capacity)
        self._state = partition.shard_state(mesh, dense) if mesh is not None else dense
        self.pool = None
        if ingest:
            from repro.runtime.ingest import IngestPool

            def bump_grow():
                self.grow_events += 1

            self._bump_grow = bump_grow
            wal = None
            if wal_dir is not None:
                from repro.runtime.recovery import GraphCheckpointer
                from repro.runtime.wal import WriteAheadLog

                wal = WriteAheadLog(f"{wal_dir}/wal.log")
                self._ckpt = GraphCheckpointer(f"{wal_dir}/ckpt")
            self.pool = IngestPool(
                self._state, mesh=mesh, auto_grow=auto_grow,
                max_inflight=max_inflight,
                max_coalesce_lanes=max_coalesce_lanes, fault=fault,
                on_grow=bump_grow, retain_epochs=retain_epochs,
                wal=wal, ckpt=self._ckpt, ckpt_every=ckpt_every)
        # default conflict policy: a pool-backed server resolves starved
        # query sessions wait-free against its published epoch ring
        # (DESIGN.md §13); a bare server keeps the capped-retry deviation
        self.on_conflict = on_conflict if on_conflict is not None else (
            "epoch" if self.pool is not None else "retry")

    @property
    def state(self):
        """Latest published state. With the ingest pool enabled this is the
        double-buffered snapshot epoch — readers never observe (or wait on)
        a round mid-admission (DESIGN.md §12). While DEGRADED, reads pin to
        the epoch published before the failure (DESIGN.md §16)."""
        if self.degraded and self._pinned is not None:
            return self._pinned[1]
        return self.pool.snapshot() if self.pool is not None else self._state

    @state.setter
    def state(self, value):
        if self.pool is not None:
            raise AttributeError(
                "state is pool-owned under multi-tenant ingestion; "
                "mutate through submit()/submit_client() (DESIGN.md §12)")
        self._state = value

    def _apply(self, state, batch: OpBatch):
        if self.mesh is not None:
            return partition.apply_ops_fast(state, batch)
        return apply_ops_fast(state, batch)

    def _grow(self, state, new_capacity: int):
        if self.mesh is not None:
            return partition.grow(state, new_capacity)
        return grow(state, new_capacity)

    def submit(self, ops: list) -> np.ndarray:
        if self.degraded:
            # typed rejection: every lane answers R_RECOVERING; the client
            # retries after recovery instead of blocking on it (DESIGN.md §16)
            self.rejected_writes += 1
            with _trace.span("serve.reject_write", lanes=len(ops)):
                return np.full((len(ops),), R_RECOVERING, np.int32)
        if self.pool is not None:
            # single-tenant surface on the multi-tenant pool: enqueue as one
            # anonymous client and drain — same results, one linearization
            # log shared with every concurrent client (DESIGN.md §12)
            ticket = self.pool.submit("_direct", ops)
            self.pool.flush()
            return np.asarray(ticket.results)
        batch = make_op_batch(ops)
        base = self.state                    # pre-batch snapshot (functional)
        state, res = self._apply(base, batch)
        res = np.asarray(res)
        while self.auto_grow and (res == R_TABLE_FULL).any():
            # Discard the starved application entirely, grow the PRE-batch
            # state, and replay the whole batch: the visible history is one
            # clean lane-order linearization on the grown table (re-applying
            # only the starved lanes would order them after lanes that
            # observed their absence — a history no linearization allows).
            base = self._grow(base, 2 * state.capacity)
            self.grow_events += 1
            state, res = self._apply(base, batch)
            res = np.asarray(res)
        self.state = state
        return res

    # -- multi-tenant admission surface (DESIGN.md §12) ---------------------
    def submit_client(self, client_id: str, ops: list):
        """Enqueue one client's mutation batch; returns its ``Ticket``.

        Requires ``ingest=True``. The batch is admitted by a later
        ``pump()`` once its entity footprint stops colliding with in-flight
        batches; results land on the ticket (DESIGN.md §12)."""
        if self.pool is None:
            raise RuntimeError("GraphCoServer(ingest=True) required for "
                               "multi-tenant submission")
        if self.degraded:
            # typed rejection ticket: never enqueued, resolved immediately
            # with R_RECOVERING lanes (DESIGN.md §16)
            from repro.runtime.ingest import Ticket, batch_footprint

            footprint, exclusive = batch_footprint(ops)
            self.rejected_writes += 1
            with _trace.span("serve.reject_write", lanes=len(ops)):
                return Ticket(-1, str(client_id), list(ops), footprint,
                              exclusive, self.pool.clock(),
                              status="rejected",
                              results=np.full((len(ops),), R_RECOVERING,
                                              np.int32))
        return self.pool.submit(client_id, ops)

    def pump(self) -> int:
        """One admission round of the ingest pool (DESIGN.md §12)."""
        return self.pool.pump() if self.pool is not None else 0

    def flush(self) -> int:
        """Drain the ingest queue (DESIGN.md §12)."""
        return self.pool.flush() if self.pool is not None else 0

    # -- durability / degraded mode (DESIGN.md §16) -------------------------
    def worker_tick(self, worker: str = "ingest", now: float | None = None):
        """Heartbeat tick for an in-process worker (the serve loop ticks
        ``"ingest"`` every decode step)."""
        if self.heartbeat is not None:
            self.heartbeat.tick(worker, now)

    def check_health(self, now: float | None = None) -> list:
        """Suspect scan: a worker past the heartbeat timeout triggers the
        backoff-budgeted restart-from-recovery path. Returns the suspects."""
        if self.heartbeat is None:
            return []
        suspects = self.heartbeat.suspects(now)
        if suspects and not self.degraded:
            self.handle_crash()
            # the restarted worker is live again: reset its heartbeat so one
            # stale timestamp cannot re-trigger recovery every scan
            for w in suspects:
                self.heartbeat.tick(w, now)
        return suspects

    def enter_degraded(self) -> None:
        """Pin the last published epoch and start rejecting writes."""
        if self.pool is not None:
            self._pinned = self.pool.snapshot_epoch()
        else:
            self._pinned = (0, self._state)
        self.degraded = True
        if _trace.enabled():
            _obs_registry().set("serve.degraded", 1)
            _trace.counter("serve.degraded", 1)

    def recover_now(self) -> None:
        """Restart-from-recovery: rebuild the pool from checkpoint + WAL
        replay; reads un-pin, writes are accepted again (DESIGN.md §16)."""
        if self.pool is None or self._wal_dir is None:
            # nothing durable to recover from: just un-pin
            self.degraded = False
            self._pinned = None
            return
        from repro.runtime.recovery import recover, resume_pool
        from repro.runtime.wal import WriteAheadLog

        with _trace.span("serve.recover"):
            old = self.pool
            wal = WriteAheadLog(f"{self._wal_dir}/wal.log")
            rec = recover(self._ckpt, wal, capacity=self._capacity,
                          mesh=self.mesh, auto_grow=self.auto_grow,
                          retain_epochs=self._retain_epochs)
            self.pool = resume_pool(
                rec, mesh=self.mesh, auto_grow=self.auto_grow,
                max_inflight=self._max_inflight,
                max_coalesce_lanes=self._max_coalesce_lanes,
                fault=self._fault, on_grow=self._bump_grow,
                retain_epochs=self._retain_epochs, wal=wal, ckpt=self._ckpt,
                ckpt_every=self._ckpt_every)
            # carry forward what recovery cannot know: tickets already
            # resolved before the crash (clients hold references to them)
            self.pool.tickets.update(old.tickets)
            self.pool.index_stamp = old.index_stamp
        self.degraded = False
        self._pinned = None
        self.recoveries += 1
        if _trace.enabled():
            _obs_registry().set("serve.degraded", 0)
            _trace.counter("serve.degraded", 0)

    def handle_crash(self, exc=None) -> float:
        """One suspect/crash -> degrade -> backoff -> recover cycle.
        Returns the backoff the FailurePolicy budgeted (0.0 without one);
        raises once the restart budget is exhausted — a crash loop must
        page a human, not spin."""
        self.enter_degraded()
        wait = 0.0
        if self.failure_policy is not None:
            wait = self.failure_policy.on_failure()
        self.recover_now()
        return wait

    def _fetch_epoch(self):
        """(epoch, state) pin source for wait-free resolution — the pool's
        published slot when ingesting, None otherwise (DESIGN.md §13).
        While degraded, sessions pin to the frozen pre-failure epoch."""
        if self.degraded and self._pinned is not None:
            return lambda: self._pinned
        return self.pool.snapshot_epoch if self.pool is not None else None

    def _note_session(self, stats: dict):
        if stats.get("starved"):
            self.getpath_starved += 1
        if stats.get("resolved") == "epoch":
            self.epoch_resolved += 1

    def get_path(self, k: int, l: int, max_rounds: int = 64):
        if self.degraded and self.mesh is None:
            self.degraded_reads += 1   # the mesh path counts via get_paths
        if self.mesh is None:
            pr = get_path_session(lambda: self.state, k, l,
                                  max_rounds=max_rounds,
                                  on_conflict=self.on_conflict,
                                  fetch_epoch=self._fetch_epoch())
            if bool(pr.starved):
                self.getpath_starved += 1
                if self.on_conflict == "epoch":
                    self.epoch_resolved += 1
            return pr
        out, rounds = self.get_paths([(k, l)], max_rounds=max_rounds)
        found, keys = out[0]
        pad = np.full((self.state.capacity,), -1, np.int32)
        pad[: len(keys)] = keys
        return PathResult(jnp.asarray(found), jnp.int32(len(keys)),
                          jnp.asarray(pad), jnp.int32(rounds))

    def get_paths(self, pairs: list, max_rounds: int = 64):
        """Batched reachability: Q queries answered under ONE shared double
        collect, traversed by the fused multi-source BFS engine (DESIGN.md
        §7; distributed per-shard form on a mesh, DESIGN.md §8) — the
        serving-side surface a query front-end batches into. A session that
        exhausts its retry budget under sustained mutation follows the
        server's ``on_conflict`` policy — pool-backed servers resolve
        wait-free against the published epoch ring (DESIGN.md §13).
        Returns ([(found, keys)] per pair, rounds)."""
        if self.degraded and self._pinned is not None:
            self.degraded_reads += 1
        st: dict = {}
        out, rounds = get_paths_session(lambda: self.state, pairs,
                                        max_rounds=max_rounds,
                                        engine=self.query_engine,
                                        on_conflict=self.on_conflict,
                                        fetch_epoch=self._fetch_epoch(),
                                        stats=st)
        self._note_session(st)
        return out, rounds

    # -- retained-epoch endpoints (DESIGN.md §13) --------------------------
    def epoch_window(self) -> tuple:
        """(oldest addressable, newest published) epoch of the ring."""
        if self.pool is None:
            raise RuntimeError("GraphCoServer(ingest=True) required for "
                               "epoch-ring endpoints")
        return self.pool.epoch_window()

    def get_reach_at(self, pairs: list, epoch: int) -> TimeTravelResult:
        """Time-travel reachability: "was u→w reachable at epoch e?" —
        answered by a single collect over the ring's bit-identical
        reconstruction of that published epoch (a frozen functional state,
        so one collect is trivially consistent). Epochs past the bounded
        retention window return a typed evicted result (DESIGN.md §13)."""
        if self.pool is None:
            raise RuntimeError("GraphCoServer(ingest=True) required for "
                               "epoch-ring endpoints")
        self.tt_calls += 1
        try:
            state_e = self.pool.state_at(epoch)
        except EpochEvictedError as err:
            self.tt_evicted += 1
            return TimeTravelResult(int(epoch), True, err.window)
        out, _rounds = get_paths_session(lambda: state_e, pairs,
                                         engine=self.query_engine)
        return TimeTravelResult(int(epoch), False, self.pool.epoch_window(),
                                [f for f, _ in out], out)

    def epoch_diff(self, e1: int, e2: int) -> EpochDiffResult:
        """Audit/forensics: which rows (and keys) changed between epochs
        e1 and e2 — read straight off the retained delta records, no
        traversal (DESIGN.md §13). Typed evicted result past the window."""
        if self.pool is None:
            raise RuntimeError("GraphCoServer(ingest=True) required for "
                               "epoch-ring endpoints")
        self.epoch_diff_calls += 1
        try:
            d = self.pool.epoch_diff(e1, e2)
        except EpochEvictedError as err:
            return EpochDiffResult(int(e1), int(e2), True, err.window)
        return EpochDiffResult(d.e_from, d.e_to, False,
                               self.pool.epoch_window(),
                               [int(r) for r in d.rows],
                               [int(k) for k in d.keys_before],
                               [int(k) for k in d.keys_after])

    # -- reachability index surface (DESIGN.md §9) -------------------------
    def index_tick(self) -> bool:
        """Build/refresh the index if enabled and stale; returns True when
        a refresh ran. ``serve`` calls this between decode steps so the
        index converges back to fresh in the gaps of the decode schedule."""
        if not self.index_enabled:
            return False
        if self.index is None:
            self.index = build_index(self.state, self.index_landmarks)
        elif not index_fresh(self.index, self.state):
            self.index, _ = refresh(self.index, self.state)
        else:
            return False
        self.index_refreshes += 1
        if self.pool is not None:
            # freshness stamp rides the next graph checkpoint: after
            # recovery the server knows which epoch the on-disk index
            # labels were built against (DESIGN.md §16)
            self.pool.index_stamp = {"epoch": int(self.pool.epoch),
                                     "refreshes": int(self.index_refreshes)}
        return True

    def get_reach(self, pairs: list, max_rounds: int = 64,
                  join_backend: str = "jnp"):
        """Batched reachability WITHOUT paths — the read-heavy fast path.
        Index-served when fresh (answers linearize at the freshness check);
        stale epochs and undecided pairs transparently fall back to the
        fused BFS double collect. Returns a ``ReachSessionResult`` whose
        ``.paths()`` lazily materializes witness paths on demand.
        ``join_backend`` picks the label intersection: ``"jnp"`` or the
        ``"pallas"`` label_join kernel (bit-identical answers)."""
        res = reach_session(lambda: self.state,
                            self.index if self.index_enabled else None,
                            pairs, engine=self.query_engine,
                            join_backend=join_backend,
                            max_rounds=max_rounds,
                            on_conflict=self.on_conflict,
                            fetch_epoch=self._fetch_epoch(),
                            ring=self.pool.ring if self.pool is not None
                            else None)
        if self.degraded:
            # answered off the pinned pre-failure epoch: flag it so clients
            # can tell a degraded answer from a live one (DESIGN.md §16)
            res.degraded = True
            self.degraded_reads += 1
        if self.index_enabled:   # a server without an index has no misses
            self.index_hits += res.from_index
            self.index_misses += res.fellback
        if res.starved:
            self.getpath_starved += 1
            if self.on_conflict == "epoch":
                self.epoch_resolved += 1
        return res

    def get_reach_counts(self, keys: list) -> np.ndarray:
        """Batched ``core.bfs.reachable_count`` endpoint: |reachable set|
        per source key, answered from the index when fresh (one [Q,L]@[L,V]
        label product) and by one fused multi-BFS otherwise."""
        if self.degraded:
            self.degraded_reads += 1
        counts, from_index = reach_counts_session(
            lambda: self.state, self.index if self.index_enabled else None,
            keys)
        if self.index_enabled:
            if from_index:
                self.index_hits += len(counts)
            else:
                self.index_misses += len(counts)
        return counts

    # -- metrics endpoint (DESIGN.md §14) ----------------------------------
    def get_metrics(self) -> dict:
        """One flat name -> value snapshot of everything the server can
        observe (DESIGN.md §14): its lifetime counters (``server.*``), the
        ingest pool's registry (``ingest.*``) plus ring window, and the
        process-global tracing metrics (``bfs.*``, ``index.*``, ``ring.*``,
        ``ingest.*_s`` histograms). Histograms are {count, sum, min, max}
        sub-dicts; everything is plain JSON-serializable."""
        out = {
            "server.grow_events": self.grow_events,
            "server.index_hits": self.index_hits,
            "server.index_misses": self.index_misses,
            "server.index_refreshes": self.index_refreshes,
            "server.getpath_starved": self.getpath_starved,
            "server.epoch_resolved": self.epoch_resolved,
            "server.tt_calls": self.tt_calls,
            "server.tt_evicted": self.tt_evicted,
            "server.epoch_diff_calls": self.epoch_diff_calls,
            "server.degraded": int(self.degraded),
            "server.degraded_reads": self.degraded_reads,
            "server.rejected_writes": self.rejected_writes,
            "server.recoveries": self.recoveries,
        }
        if self.pool is not None:
            out.update(self.pool.registry.snapshot())
            lo, hi = self.pool.epoch_window()
            out["ring.window_lo"] = int(lo)
            out["ring.window_hi"] = int(hi)
        out.update(_obs_registry().snapshot())
        return out


def serve(model, params, prompts: np.ndarray, *, max_new_tokens: int,
          cache_len: int, graph: GraphCoServer | None = None,
          mutator=None, query_stream=None, clients=None,
          temperature: float = 0.0):
    """Greedy batched decoding with interleaved graph traffic.

    prompts: int32 [B, P]. Returns (generated [B, max_new_tokens], stats).

    ``clients`` (requires ``GraphCoServer(ingest=True)``) is the multi-
    tenant mutation stream: a callable ``step -> [(client_id, ops), ...]``.
    Each step's batches are enqueued and one admission round runs —
    non-conflicting batches coalesce into one fused apply while the read
    stream keeps hitting the last published snapshot epoch (DESIGN.md §12);
    the queue is drained after the last decode step.
    """
    t0 = time.time()
    stats = ServeStats()
    # server counters are lifetime-cumulative; ServeStats reports per-serve
    # deltas, so EVERY lifetime counter gets a start-of-serve snapshot —
    # grow_events included (it used to leak the lifetime total into the
    # second and later serve() calls)
    grow0 = graph.grow_events if graph is not None else 0
    idx0 = ((graph.index_hits, graph.index_misses, graph.index_refreshes)
            if graph is not None else (0, 0, 0))
    ring0 = ((graph.getpath_starved, graph.epoch_resolved, graph.tt_calls,
              graph.tt_evicted, graph.epoch_diff_calls)
             if graph is not None else (0, 0, 0, 0, 0))
    rec0 = ((graph.degraded_reads, graph.rejected_writes, graph.recoveries)
            if graph is not None else (0, 0, 0))
    pool = graph.pool if graph is not None else None
    if clients is not None and pool is None:
        raise RuntimeError("clients= stream requires GraphCoServer(ingest=True)")
    ing0 = ((pool.stats.applied, pool.stats.fused_calls, pool.stats.retries,
             pool.stats.wait_s, pool.stats.epochs)
            if pool is not None else (0, 0, 0, 0.0, 0))
    b, p = prompts.shape
    last, caches = model.prefill(params, {"tokens": jnp.asarray(prompts)})
    caches = model.cache_from_prefill(caches, cache_len)
    jdecode = jax.jit(model.decode_step)

    out = np.zeros((b, max_new_tokens), np.int32)
    tok = jnp.argmax(last, axis=-1).astype(jnp.int32)
    for i in range(max_new_tokens):
        out[:, i] = np.asarray(tok)
        # interleave graph traffic between decode steps (non-blocking co-serving)
        if graph is not None and mutator is not None:
            ops = mutator(i)
            if ops:
                graph.submit(ops)
                stats.graph_ops += len(ops)
        if graph is not None and clients is not None:
            for client_id, ops in clients(i) or ():
                if ops:
                    graph.submit_client(client_id, ops)
                    stats.graph_ops += len(ops)
            # one admission round per decode step: coalesced fused apply of
            # whatever non-conflicting batches are queued (DESIGN.md §12)
            try:
                graph.pump()
            except SimulatedCrash:
                # worker died mid-round: degrade, spend one restart-budget
                # slot, recover from checkpoint + WAL (DESIGN.md §16); the
                # FailurePolicy raises past its budget — that propagates
                graph.handle_crash()
        if graph is not None:
            # heartbeat: the ingest worker ticks every decode step; a
            # missing tick past the timeout trips check_health into the
            # same restart-from-recovery path (DESIGN.md §16)
            graph.worker_tick("ingest")
            graph.check_health()
        if graph is not None:
            # background index refresh between decode steps: co-serving
            # stays non-blocking — queries racing a stale index fall back
            # to BFS and mutations never wait (DESIGN.md §5(ii), §9)
            graph.index_tick()
        if graph is not None and query_stream is not None:
            q = query_stream(i)
            if q is not None and len(q) > 0:
                # a batch is a sequence OF (k, l) pairs (list/tuple/ndarray);
                # a lone pair — any length-2 sequence of scalars — stays on
                # the single-query path. Scalars have no __len__.
                if hasattr(q[0], "__len__"):
                    # one fused multi-query session for the whole batch;
                    # every query in it shares the session's round count, so
                    # rounds-per-call stays comparable with the single path.
                    # With the index enabled, the batch goes through the
                    # reachability fast path instead (DESIGN.md §9) — serve
                    # only consumes found/rounds, so nothing is lost and
                    # fresh-epoch batches skip the BFS entirely.
                    batch_pairs = [(int(p[0]), int(p[1])) for p in q]
                    stats.getpath_calls += len(q)
                    if graph.index_enabled:
                        res = graph.get_reach(batch_pairs)
                        # rounds accounting is PER PAIR, and only the pairs
                        # that actually took the BFS fallback session spent
                        # them — index-served pairs cost 0 rounds. Charging
                        # rounds * len(q) here would bill index hits for a
                        # session they never entered (stale-epoch batches
                        # still charge every pair: fellback == len(q)).
                        stats.getpath_rounds += res.rounds * res.fellback
                    else:
                        _, rounds = graph.get_paths(batch_pairs)
                        # every pair shares the one session's double collect
                        stats.getpath_rounds += rounds * len(q)
                elif graph.index_enabled:
                    res = graph.get_reach([(int(q[0]), int(q[1]))])
                    stats.getpath_calls += 1
                    stats.getpath_rounds += res.rounds
                else:
                    res = graph.get_path(int(q[0]), int(q[1]))
                    stats.getpath_calls += 1
                    stats.getpath_rounds += int(res.rounds)
        tok_logits, caches = jdecode(params, caches, tok, jnp.int32(p + i))
        tok = jnp.argmax(tok_logits, axis=-1).astype(jnp.int32)
        stats.decode_steps += 1
        stats.decode_tokens += b
    if pool is not None:
        try:
            graph.flush()                    # drain whatever is still queued
        except SimulatedCrash:
            graph.handle_crash()
            graph.flush()
        pool = graph.pool                    # recovery may have replaced it
        stats.ingest_batches = pool.stats.applied - ing0[0]
        stats.ingest_fused_calls = pool.stats.fused_calls - ing0[1]
        stats.ingest_retries = pool.stats.retries - ing0[2]
        stats.ingest_wait_s = pool.stats.wait_s - ing0[3]
        stats.ingest_epochs = pool.stats.epochs - ing0[4]
        # high-water marks are lifetime values (a max has no meaningful delta)
        stats.ingest_coalesce_max = pool.stats.coalesce_max
        stats.ingest_wait_max_s = pool.stats.wait_max_s
        stats.ingest_queue_depth_max = pool.stats.queue_depth_max
    if graph is not None:
        stats.grow_events = graph.grow_events - grow0
        stats.index_hits = graph.index_hits - idx0[0]
        stats.index_misses = graph.index_misses - idx0[1]
        stats.index_refreshes = graph.index_refreshes - idx0[2]
        stats.getpath_starved = graph.getpath_starved - ring0[0]
        stats.epoch_resolved = graph.epoch_resolved - ring0[1]
        stats.tt_calls = graph.tt_calls - ring0[2]
        stats.tt_evicted = graph.tt_evicted - ring0[3]
        stats.epoch_diff_calls = graph.epoch_diff_calls - ring0[4]
        stats.degraded_reads = graph.degraded_reads - rec0[0]
        stats.rejected_writes = graph.rejected_writes - rec0[1]
        stats.recoveries = graph.recoveries - rec0[2]
    stats.wall_s = time.time() - t0
    return out, stats
