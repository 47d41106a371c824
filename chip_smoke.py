"""Bring-up smoke run of the graph server on a TPU, through ``GraphCoServer``.

  python chip_smoke.py                # one chip: Graph500 scale 15, 5 phases
  python chip_smoke.py --four-chips   # sharded server on four chips, scale 16

One chip. A Graph500 Kronecker graph (A=0.57, B=C=0.19, edge factor 16,
``--seed``) is served by ``GraphCoServer(capacity=2**scale, ingest=True,
index=True, wal_dir=..., ckpt_every=...)`` with its full guarantees:
linearizable updates, snapshot-consistent reads, and acks only after the
round's WAL record is fsync-durable. Phases:

  1. load the graph through the server's write path, fixed-size batches;
  2. serve mixed-traffic rounds (edge and vertex adds and removes from
     several clients);
  3. answer Q ``get_paths`` and Q ``get_reach`` queries (default ``hybrid``
     engine, reachability index with a landmark budget);
  4. recover in process from checkpoint + WAL and check that epoch,
     linearization and state equal those before;
  5. the same queries on the ``hybrid_pallas`` engine with the index join
     on the ``pallas`` kernel: answers must be bit-identical to phase 3.

Every applied op is replayed in the server's linearization order through
``core.oracle.GraphOracle`` (its result codes must match the server's), and
every answer is checked against a host BFS over the oracle's edge set.

Four chips (``--four-chips``): the row-sharded server
(``GraphCoServer(mesh=make_graph_mesh())``) at scale 16 runs the same load,
rounds and query batch against the same host reference, and prints the
bytes each device holds of the packed adjacency. No index and no WAL there:
the index build gathers the whole graph onto one device, and durability is
the one-chip phase's subject.

Each phase prints one line with its wall time, compile time (lowering and
XLA compilation, from ``jax.monitoring``) and the device's peak bytes in
use. The last line is ``{"ok": true, "device": ...}``; any
failure raises, so the process exits non-zero without it. The run needs a
TPU: on any other platform, or with fewer devices than the phase needs, it
exits with code 3 and prints no result.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import shutil
import sys
import time
from collections import defaultdict

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

# lowering to StableHLO and XLA compilation of each jitted program; tracing
# is left out because nested jits report overlapping trace durations
_COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration")


class SmokeFailure(RuntimeError):
    """An answer, result code or recovered state disagreed with the
    reference."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# ----------------------------------------------------------------------------
# Host reference: oracle replay + numpy BFS over its edge set
# ----------------------------------------------------------------------------
class HostReference:
    """The oracle replayed in the server's linearization order, with a CSR
    BFS over its current edges (the oracle's own BFS is O(V*E))."""

    def __init__(self, capacity: int):
        from repro.core.oracle import GraphOracle

        self.oracle = GraphOracle(capacity)
        self._csr = None

    def replay(self, ops, results) -> None:
        """Apply ``ops`` (client tuples) and compare with the server's
        result codes lane by lane."""
        self._csr = None
        for i, op in enumerate(ops):
            full = tuple(op) + (-1,) * (4 - len(op))
            want = self.oracle.apply(*full)
            check(int(results[i]) == want,
                  f"result code of {op}: server {int(results[i])}, "
                  f"oracle {want}")

    def _graph(self):
        if self._csr is None:
            alive = self.oracle.ecnt
            e = np.array([(u, w) for (u, w) in self.oracle.edges
                          if u in alive and w in alive], np.int64)
            e = e.reshape(-1, 2)
            n = max([0] + [k + 1 for k in alive]) if alive else 0
            order = np.argsort(e[:, 0], kind="stable")
            e = e[order]
            indptr = np.zeros(n + 1, np.int64)
            np.add.at(indptr, e[:, 0] + 1, 1)
            self._csr = (np.cumsum(indptr), e[:, 1], n)
        return self._csr

    def distances(self, src: int) -> np.ndarray:
        """BFS hop count from key ``src`` to every key (-1 unreachable)."""
        indptr, nbr, n = self._graph()
        dist = np.full(max(n, 1), -1, np.int64)
        if src not in self.oracle.ecnt:
            return dist
        dist[src] = 0
        frontier = np.array([src], np.int64)
        d = 0
        while frontier.size:
            d += 1
            starts, ends = indptr[frontier], indptr[frontier + 1]
            cnt = ends - starts
            if cnt.sum() == 0:
                break
            idx = np.repeat(ends - cnt.cumsum(), cnt) + np.arange(cnt.sum())
            cand = np.unique(nbr[idx])
            cand = cand[dist[cand] < 0]
            dist[cand] = d
            frontier = cand
        return dist

    def check_paths(self, pairs, got) -> None:
        edges = self.oracle.edges
        for (k, l), (found, keys) in zip(pairs, got):
            dist = self.distances(k)
            want = l < dist.size and dist[l] >= 0
            check(bool(found) == bool(want),
                  f"get_paths({k},{l}) found={found}, reference {want}")
            if want:
                keys = [int(x) for x in keys]
                check(keys[0] == k and keys[-1] == l,
                      f"path {k}->{l} has endpoints {keys[0]},{keys[-1]}")
                check(all((a, b) in edges for a, b in zip(keys, keys[1:])),
                      f"path {k}->{l} uses an edge the reference lacks")
                check(len(keys) - 1 == dist[l],
                      f"path {k}->{l} has {len(keys) - 1} hops, "
                      f"shortest is {dist[l]}")

    def check_reach(self, pairs, found) -> None:
        for (k, l), f in zip(pairs, found):
            dist = self.distances(k)
            want = l < dist.size and dist[l] >= 0
            check(bool(f) == bool(want),
                  f"get_reach({k},{l})={f}, reference {want}")


# ----------------------------------------------------------------------------
# Phase bookkeeping
# ----------------------------------------------------------------------------
class Phases:
    """Per-phase wall time, compile time and device peak bytes."""

    def __init__(self, devices):
        import jax.monitoring

        self.devices = devices
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_):
        if event in _COMPILE_EVENTS:
            self.compile_s += duration

    def peak_bytes(self) -> list:
        out = []
        for d in self.devices:
            stats = d.memory_stats() or {}
            out.append(int(stats.get("peak_bytes_in_use", -1)))
        return out

    def run(self, name: str, fn):
        c0, t0 = self.compile_s, time.perf_counter()
        info = fn() or {}
        wall = time.perf_counter() - t0
        line = {"phase": name, "wall_s": wall,
                "compile_s": self.compile_s - c0,
                "peak_bytes_in_use": self.peak_bytes()}
        line.update(info)
        print("PHASE " + json.dumps(line), flush=True)
        return info


# ----------------------------------------------------------------------------
# Workload
# ----------------------------------------------------------------------------
def load_ops(edges: np.ndarray, batch: int):
    """Fixed-size load batches: AddVertex for every endpoint (the last
    batch NOP-padded), then AddEdge per generated edge, in order."""
    from repro.core import OP_ADD_E, OP_ADD_V, OP_NOP

    verts = np.unique(edges)
    vops = [(OP_ADD_V, int(k)) for k in verts]
    vops += [(OP_NOP,)] * (-len(vops) % batch)
    eops = [(OP_ADD_E, int(a), int(b)) for a, b in edges]
    eops += [(OP_NOP,)] * (-len(eops) % batch)
    allops = vops + eops
    return verts, [allops[i:i + batch] for i in range(0, len(allops), batch)]


def mixed_round(rng, ref: HostReference, clients: int, lanes: int,
                free_keys: list) -> list:
    """One round of client batches: edge adds among live vertices, removes
    of live edges, adds of new vertices (and edges to them), and the odd
    vertex removal (which makes its batch exclusive)."""
    from repro.core import OP_ADD_E, OP_ADD_V, OP_REM_E, OP_REM_V

    live = np.fromiter(ref.oracle.ecnt, np.int64)
    edges = list(ref.oracle.edges)
    out = []
    for c in range(clients):
        ops = []
        for _ in range(lanes):
            r = rng.random()
            if r < 0.45:
                a, b = rng.choice(live, 2)
                ops.append((OP_ADD_E, int(a), int(b)))
            elif r < 0.85:
                a, b = edges[int(rng.integers(len(edges)))]
                ops.append((OP_REM_E, int(a), int(b)))
            elif free_keys:
                k = free_keys.pop()
                ops.append((OP_ADD_V, k))
                ops.append((OP_ADD_E, int(rng.choice(live)), k))
        ops = ops[:lanes]
        if c == 0 and rng.random() < 0.5:
            ops[-1] = (OP_REM_V, int(rng.choice(live)))
        out.append((f"client{c}", ops))
    return out


def pick_pairs(rng, ref: HostReference, q: int) -> list:
    """Q (src, dst) key pairs: sources with out-edges, destinations half
    drawn from the source's reachable set, half from all live keys."""
    live = np.fromiter(ref.oracle.ecnt, np.int64)
    srcs = np.unique(np.array([u for (u, _) in ref.oracle.edges], np.int64))
    pairs = []
    for i in range(q):
        k = int(rng.choice(srcs))
        dist = ref.distances(k)
        reach = np.nonzero(dist > 0)[0]
        if i % 2 == 0 and reach.size:
            l = int(rng.choice(reach))
        else:
            l = int(rng.choice(live))
        pairs.append((k, l))
    return pairs


def submit_round(srv, batches) -> tuple[list, int]:
    """Submit one round of client batches and drain; returns the applied
    tickets and the number of admission rounds it took."""
    before = srv.pool.epoch
    tickets = [srv.submit_client(cid, ops) for cid, ops in batches]
    srv.flush()
    for t in tickets:
        check(t.status == "applied", f"batch {t.batch_id} is {t.status}")
    return tickets, srv.pool.epoch - before


def replay_in_linearization(srv, ref: HostReference, tickets) -> None:
    """Replay the given tickets through the oracle in the server's claimed
    serial order (the pool's linearization log)."""
    by_id = {t.batch_id: t for t in tickets}
    for bid in srv.pool.linearization:
        t = by_id.pop(bid, None)
        if t is not None:
            ref.replay(t.ops, t.results)
    check(not by_id, f"tickets missing from the linearization: {list(by_id)}")


# ----------------------------------------------------------------------------
# Runs
# ----------------------------------------------------------------------------
def serve_and_check(args, phases: Phases, *, mesh=None, scale: int,
                    state_dir: pathlib.Path | None):
    """Load, mixed rounds and the query batch on one server; with
    ``state_dir`` also the recovery and Pallas-engine phases. Returns the
    server."""
    from repro.data.kronecker import kronecker_edges
    from repro.runtime.serve_loop import GraphCoServer

    rng = np.random.default_rng(args.seed)
    v = 1 << scale
    t0 = time.perf_counter()
    edges = kronecker_edges(scale, args.edge_factor, args.seed)
    verts, batches = load_ops(edges, args.load_batch)
    print(f"data: Graph500 scale {scale} (V={v}), edge factor "
          f"{args.edge_factor}, seed {args.seed}: {len(edges)} edges over "
          f"{len(verts)} non-isolated vertices, {len(batches)} load batches "
          f"of {args.load_batch} ops, generated in "
          f"{time.perf_counter() - t0} s", flush=True)

    durable = state_dir is not None
    kwargs = dict(capacity=v, ingest=True, retain_epochs=args.retain_epochs)
    if mesh is not None:
        kwargs["mesh"] = mesh
    if durable:
        kwargs.update(index=True, index_landmarks=args.landmarks,
                      wal_dir=str(state_dir), ckpt_every=args.ckpt_every)
    srv = GraphCoServer(**kwargs)
    ref = HostReference(v)

    def load():
        tickets = []
        for ops in batches:
            tickets.append(srv.submit_client("loader", ops))
            srv.flush()
        replay_in_linearization(srv, ref, tickets)
        check(srv.state.capacity == v, "capacity grew during the load")
        return {"batches": len(batches), "ops": len(batches) * args.load_batch,
                "epoch": int(srv.pool.epoch),
                "edges_live": len(ref.oracle.edges)}

    phases.run("1 load", load)

    free_keys = sorted(set(range(v)) - set(int(k) for k in verts))

    def rounds():
        tickets, admissions = [], 0
        for _ in range(args.rounds):
            batch = mixed_round(rng, ref, args.clients, args.client_lanes,
                                free_keys)
            got, n = submit_round(srv, batch)
            tickets += got
            admissions += n
            replay_in_linearization(srv, ref, got)
        return {"rounds": args.rounds, "admission_rounds": admissions,
                "batches": len(tickets), "epoch": int(srv.pool.epoch),
                "vertices_live": len(ref.oracle.ecnt),
                "edges_live": len(ref.oracle.edges)}

    phases.run("2 mixed rounds", rounds)

    path_pairs = pick_pairs(rng, ref, args.queries)
    reach_pairs = pick_pairs(rng, ref, args.queries)
    answers = {}

    def queries(tag: str, join_backend: str):
        def run():
            index_s = 0.0
            if durable:
                t = time.perf_counter()
                srv.index_tick()
                index_s = time.perf_counter() - t
            paths, _ = srv.get_paths(path_pairs)
            reach = srv.get_reach(reach_pairs, join_backend=join_backend)
            ref.check_paths(path_pairs, paths)
            ref.check_reach(reach_pairs, reach.found)
            answers[tag] = ([(bool(f), [int(x) for x in k]) for f, k in paths],
                            [bool(f) for f in reach.found])
            return {"engine": os.environ.get("REPRO_BFS_BACKEND", "hybrid"),
                    "join_backend": join_backend, "queries": 2 * args.queries,
                    "found": sum(f for f, _ in paths) + sum(reach.found),
                    "index_hits": reach.from_index,
                    "bfs_fallbacks": reach.fellback, "index_build_s": index_s}
        return run

    phases.run("3 queries", queries("default", "jnp"))
    if not durable:
        return srv

    def recovery():
        epoch, lin = int(srv.pool.epoch), list(srv.pool.linearization)
        before = srv.state
        srv.enter_degraded()
        srv.recover_now()
        check(int(srv.pool.epoch) == epoch,
              f"recovered epoch {srv.pool.epoch}, expected {epoch}")
        check(list(srv.pool.linearization) == lin,
              "recovered linearization differs")
        after = srv.state
        for f in ("vkey", "valive", "vver", "ecnt", "adj_packed",
                  "adj_in_packed"):
            check(np.array_equal(np.asarray(getattr(before, f)),
                                 np.asarray(getattr(after, f))),
                  f"recovered state differs in {f}")
        return {"epoch": epoch, "batches_durable": len(lin)}

    phases.run("4 recovery", recovery)

    os.environ["REPRO_BFS_BACKEND"] = "hybrid_pallas"
    try:
        phases.run("5 pallas queries", queries("pallas", "pallas"))
    finally:
        del os.environ["REPRO_BFS_BACKEND"]
    check(answers["pallas"] == answers["default"],
          "hybrid_pallas / pallas-join answers differ from the default "
          "engine's")
    return srv


def adjacency_bytes_per_device(state) -> dict:
    """Bytes of both packed adjacency mirrors each device holds."""
    out = defaultdict(int)
    for arr in (state.adj_packed, state.adj_in_packed):
        for shard in arr.addressable_shards:
            out[str(shard.device.id)] += int(shard.data.nbytes)
    return dict(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded four-chip phase")
    ap.add_argument("--scale", type=int, default=None,
                    help="Graph500 scale (default 15; 16 with --four-chips)")
    ap.add_argument("--edge-factor", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--load-batch", type=int, default=8192,
                    help="ops per load batch (one compiled apply shape)")
    ap.add_argument("--rounds", type=int, default=8)
    ap.add_argument("--clients", type=int, default=4)
    ap.add_argument("--client-lanes", type=int, default=32)
    ap.add_argument("--queries", type=int, default=16)
    ap.add_argument("--landmarks", type=int, default=16,
                    help="reachability-index landmark budget")
    ap.add_argument("--ckpt-every", type=int, default=16,
                    help="checkpoint cadence in admission rounds")
    ap.add_argument("--retain-epochs", type=int, default=4)
    args = ap.parse_args(argv)
    return run(args, require_tpu=True)


def run(args, *, require_tpu: bool) -> int:
    import jax

    from repro.compile_cache import enable_compile_cache
    from repro.core.partition import make_graph_mesh

    devices = jax.devices()
    need = 4 if args.four_chips else 1
    if require_tpu and (devices[0].platform != "tpu" or len(devices) < need):
        print(f"chip_smoke: needs {need} TPU device(s), JAX found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 3
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    phases = Phases(devices[:need])
    if args.four_chips:
        scale = args.scale if args.scale is not None else 16
        mesh = make_graph_mesh(devices[:4])
        srv = serve_and_check(args, phases, mesh=mesh, scale=scale,
                              state_dir=None)
        per = adjacency_bytes_per_device(srv.state)
        print("adjacency bytes per device: " + json.dumps(per), flush=True)
        check(len(per) == 4 and len(set(per.values())) == 1
              and min(per.values()) > 0,
              f"packed adjacency is not spread evenly over 4 devices: {per}")
    else:
        scale = args.scale if args.scale is not None else 15
        state_dir = ROOT / ".smoke_state"
        shutil.rmtree(state_dir, ignore_errors=True)
        state_dir.mkdir(parents=True)
        try:
            serve_and_check(args, phases, scale=scale, state_dir=state_dir)
        finally:
            shutil.rmtree(state_dir, ignore_errors=True)
    if require_tpu:
        d = devices[0]
        print(json.dumps({"ok": True, "device": {
            "platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
