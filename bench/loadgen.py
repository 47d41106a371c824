"""The one general traffic generator. A traffic mix is a data file
(``bench/traffic/<mix>.json``) of parameters only; this module turns it
into client batches and, for an open loop, a schedule of due times.

Vocabulary of a mix file:

``loop``            ``"closed"`` or ``"open"``.
``clients``         closed loop: clients, each with one batch in flight.
``templates_per_batch``  template instances per client batch (an open
                    loop sends each instance as its own batch: 1).
``templates``       list of ``{"name", "share", "pick", "ops"}``. ``share``
                    is a weight; ``ops`` lists ``[opname, role...]`` with
                    roles ``"a"``/``"b"`` bound by ``pick``:
                    ``key`` / ``key2`` - one / two keys from ``keys``;
                    ``live`` / ``live2`` - one / two distinct live keys;
                    ``unused`` - a key below the capacity never used yet;
                    ``edge`` - a present edge ``(a, b)``.
``keys``            key law over ``[0, capacity)``: ``{"dist": "uniform"}``
                    or ``{"dist": "zipfian", "theta": 0.99,
                    "hot_shift_per_batch": n}`` (YCSB's scrambled zipfian;
                    the hot set moves by ``n`` keys after every batch).
``rate``            open loop: ``{"queries_per_s": r}`` or
                    ``{"knee_queries_per_s": k, "knee_share": s}``; and
                    ``"updates_per_query"``: update ops per query.
``queries``         ``{"kind": "get_paths" | "get_reach", "pairs": ...,
                    "max_batch": 16}``; pairs ``live2`` (two distinct live
                    keys), ``out_src`` (a source with out-edges, any live
                    destination) or ``out_src_reach_half`` (as ``out_src``,
                    every other destination reachable from the source).

Every seed sees the same sizes and arrivals in another order: templates
are dealt from blocks that hold each template in proportion to its share,
and open-loop gaps are the quantiles of an exponential law, shuffled.
"""
from __future__ import annotations

import math

import numpy as np

from bench.reference import OPCODES

_BLOCK = 1000


class KeyModel:
    """The generator's own belief of the graph, updated in generation
    order: live keys, present edges and keys never used. Answers the
    ``live``/``edge``/``unused`` picks in O(1)."""

    def __init__(self, keys, edges, capacity: int, rng):
        self.live: list[int] = [int(k) for k in keys]
        self._live_at = {k: i for i, k in enumerate(self.live)}
        self.edges: list[tuple[int, int]] = []
        self._edge_at: dict[tuple[int, int], int] = {}
        self._nbrs: dict[int, set] = {k: set() for k in self.live}
        for a, b in edges:
            self._add_edge(int(a), int(b))
        self._csr, self._gen = None, 0
        unused = np.setdiff1d(np.arange(capacity), np.asarray(keys))
        self.unused = [int(k) for k in rng.permutation(unused)]

    def _add_edge(self, a: int, b: int) -> None:
        if (a, b) in self._edge_at:
            return
        self._edge_at[(a, b)] = len(self.edges)
        self.edges.append((a, b))
        self._nbrs[a].add(b)
        self._nbrs[b].add(a)

    def _drop(self, lst: list, at: dict, item) -> None:
        i = at.pop(item)
        last = lst.pop()
        if i < len(lst):
            lst[i] = last
            at[last] = i

    def _rem_edge(self, a: int, b: int) -> None:
        if (a, b) not in self._edge_at:
            return
        self._drop(self.edges, self._edge_at, (a, b))
        if (b, a) not in self._edge_at:
            self._nbrs[a].discard(b)
            self._nbrs[b].discard(a)

    def reachable(self, src: int) -> np.ndarray:
        """Keys reachable from ``src`` over the model's directed edges."""
        if self._csr is None or self._csr[0] != self._gen:
            self._csr = (self._gen, *csr(self.edges))
        _, indptr, nbr = self._csr
        return np.flatnonzero(bfs_levels(indptr, nbr, src) > 0)

    def observe(self, op) -> None:
        """Fold one generated op into the model."""
        opc = op[0]
        self._gen += 1
        if opc == OPCODES["AddV"] and op[1] not in self._live_at:
            self._live_at[op[1]] = len(self.live)
            self.live.append(op[1])
            self._nbrs[op[1]] = set()
        elif opc == OPCODES["RemV"] and op[1] in self._live_at:
            k = op[1]
            for n in list(self._nbrs[k]):
                self._rem_edge(k, n)
                self._rem_edge(n, k)
            self._rem_edge(k, k)
            del self._nbrs[k]
            self._drop(self.live, self._live_at, k)
        elif (opc == OPCODES["AddE"] and op[1] in self._live_at
              and op[2] in self._live_at):
            self._add_edge(op[1], op[2])
        elif opc == OPCODES["RemE"]:
            self._rem_edge(op[1], op[2])


def csr(edges) -> tuple[np.ndarray, np.ndarray]:
    """``(indptr, neighbours)`` of directed ``(src, dst)`` edges."""
    e = np.asarray(edges, np.int64).reshape(-1, 2)
    n = int(e.max()) + 1 if e.size else 1
    indptr = np.zeros(n + 1, np.int64)
    np.add.at(indptr, e[:, 0] + 1, 1)
    return np.cumsum(indptr), e[np.argsort(e[:, 0], kind="stable"), 1]


def bfs_levels(indptr, nbr, src: int) -> np.ndarray:
    """Hop counts from ``src`` over a CSR graph (-1 unreachable)."""
    dist = np.full(len(indptr) - 1, -1, np.int64)
    if src >= len(dist):
        return dist
    dist[src] = 0
    frontier, d = np.array([src]), 0
    while frontier.size:
        d += 1
        starts, ends = indptr[frontier], indptr[frontier + 1]
        cnt = ends - starts
        idx = np.repeat(ends - cnt.cumsum(), cnt) + np.arange(cnt.sum())
        cand = np.unique(nbr[idx])
        frontier = cand[dist[cand] < 0]
        dist[frontier] = d
    return dist


class KeyLaw:
    """Keys over ``[0, n)``: uniform, or YCSB's scrambled zipfian with a
    hot set that moves after every batch."""

    def __init__(self, spec: dict, n: int, rng):
        self.n = n
        self.rng = rng
        self.dist = spec.get("dist", "uniform")
        self.shift = 0
        if self.dist == "zipfian":
            theta = float(spec.get("theta", 0.99))
            self.theta = theta
            self.step = int(spec.get("hot_shift_per_batch", 0))
            self.zetan = float(np.sum(1.0 / np.arange(1, n + 1) ** theta))
            zeta2 = 1.0 + 0.5 ** theta
            self.alpha = 1.0 / (1.0 - theta)
            self.eta = ((1 - (2.0 / n) ** (1 - theta))
                        / (1 - zeta2 / self.zetan))
            self.scramble = rng.permutation(n)
        elif self.dist != "uniform":
            raise ValueError(f"unknown key law {self.dist!r}")

    def draw(self) -> int:
        if self.dist == "uniform":
            return int(self.rng.integers(self.n))
        u = self.rng.random()
        uz = u * self.zetan
        if uz < 1.0:
            rank = 0
        elif uz < 1.0 + 0.5 ** self.theta:
            rank = 1
        else:
            rank = int(self.n * (self.eta * u - self.eta + 1) ** self.alpha)
        return int((self.scramble[min(rank, self.n - 1)] + self.shift)
                   % self.n)

    def next_batch(self) -> None:
        if self.dist == "zipfian":
            self.shift = (self.shift + self.step) % self.n


def _deal(shares, rng):
    """Template indices of one block: each template ``round(share * block)``
    times (largest remainder), in seeded order."""
    w = np.asarray(shares, float)
    exact = w / w.sum() * _BLOCK
    counts = np.floor(exact).astype(int)
    rest = _BLOCK - counts.sum()
    counts[np.argsort(counts - exact)[:rest]] += 1
    return list(rng.permutation(np.repeat(np.arange(len(w)), counts)))


class OpStream:
    """Client batches of a mix, in one global deterministic sequence."""

    def __init__(self, mix: dict, capacity: int, keys, edges, rng):
        self.mix = mix
        self.rng = rng
        self.templates = mix["templates"]
        self.per_batch = int(mix.get("templates_per_batch", 1))
        self.keys = KeyLaw(mix.get("keys", {"dist": "uniform"}), capacity,
                           rng)
        picks = {t["pick"] for t in self.templates}
        q = mix.get("queries")
        needs_model = bool(picks - {"key", "key2"}) or q is not None
        self.model = (KeyModel(keys, edges, capacity, rng)
                      if needs_model else None)
        self._deck: list[int] = []
        self._pairs = 0

    @property
    def mean_ops(self) -> float:
        w = np.asarray([t["share"] for t in self.templates], float)
        n = np.asarray([len(t["ops"]) for t in self.templates], float)
        return float((w * n).sum() / w.sum()) if w.size else 0.0

    def _two_live(self) -> tuple[int, int]:
        live, n = self.model.live, len(self.model.live)
        i = int(self.rng.integers(n))
        j = int(self.rng.integers(n - 1))
        return live[i], live[j + (j >= i)]

    def _roles(self, pick: str) -> dict:
        m, rng = self.model, self.rng
        if pick == "key":
            return {"a": self.keys.draw()}
        if pick == "key2":
            return {"a": self.keys.draw(), "b": self.keys.draw()}
        if pick == "live":
            return {"a": m.live[int(rng.integers(len(m.live)))]}
        if pick == "live2":
            a, b = self._two_live()
            return {"a": a, "b": b}
        if pick == "unused":
            return {"a": m.unused.pop()}
        if pick == "edge":
            a, b = m.edges[int(rng.integers(len(m.edges)))]
            return {"a": a, "b": b}
        raise ValueError(f"unknown pick {pick!r}")

    def next_batch(self) -> list[tuple]:
        ops: list[tuple] = []
        for _ in range(self.per_batch):
            if not self._deck:
                self._deck = _deal([t["share"] for t in self.templates],
                                   self.rng)
            t = self.templates[self._deck.pop()]
            roles = self._roles(t["pick"])
            for name, *args in t["ops"]:
                op = (OPCODES[name], *[roles[r] for r in args])
                ops.append(op)
                if self.model is not None:
                    self.model.observe(op)
        self.keys.next_batch()
        return ops

    def query_pair(self) -> tuple[int, int]:
        """One query's (source, destination) keys, by the mix's ``pairs``."""
        m, rng = self.model, self.rng
        how = self.mix["queries"].get("pairs", "live2")
        if how == "live2":
            return self._two_live()
        if how == "out_src":
            a, _ = m.edges[int(rng.integers(len(m.edges)))]
            return a, m.live[int(rng.integers(len(m.live)))]
        if how == "out_src_reach_half":
            # Graph500-style roots: every other destination is drawn from
            # the source's reachable set, the rest from all live keys
            a, _ = m.edges[int(rng.integers(len(m.edges)))]
            self._pairs += 1
            if self._pairs % 2:
                reach = m.reachable(a)
                if reach.size:
                    return a, int(reach[int(rng.integers(reach.size))])
            return a, m.live[int(rng.integers(len(m.live)))]
        raise ValueError(f"unknown pair law {how!r}")


def poisson_due_times(rate: float, seconds: float, rng) -> np.ndarray:
    """Due times in ``[0, seconds)`` of a Poisson stream of ``rate``: the
    gaps are the ``N = rate * seconds`` quantiles of the exponential law,
    in seeded order, so every seed sends the same number of requests."""
    n = int(round(rate * seconds))
    if n <= 0:
        return np.zeros(0)
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    due = np.cumsum(rng.permutation(gaps))
    return due[due < seconds]


def query_rate(mix: dict) -> float:
    rate = mix.get("rate", {})
    if "queries_per_s" in rate:
        return float(rate["queries_per_s"])
    if "knee_queries_per_s" in rate:
        return float(rate["knee_queries_per_s"]) * float(rate["knee_share"])
    return 0.0


def open_schedule(stream: OpStream, seconds: float, rng) -> list[tuple]:
    """The open loop's events, sorted by due time: ``(due_s, "update",
    ops)`` and ``(due_s, "query", (src, dst))``. Generated in due order, so
    the key model sees updates and queries as the server will."""
    mix = stream.mix
    q_rate = query_rate(mix)
    events = [(float(t), 1, "query") for t in
              poisson_due_times(q_rate, seconds, rng)]
    u_ops = q_rate * float(mix.get("updates_per_query", 0))
    if u_ops > 0:
        u_rate = u_ops / (stream.mean_ops * stream.per_batch)
        events += [(float(t), 0, "update") for t in
                   poisson_due_times(u_rate, seconds, rng)]
    events.sort()
    out = []
    for due, _, kind in events:
        payload = stream.next_batch() if kind == "update" \
            else stream.query_pair()
        out.append((due, kind, payload))
    return out


def percentile(values, q: float) -> float:
    """The ``q``-th percentile by numpy's default (linear) rule; NaN for
    an empty sample."""
    if len(values) == 0:
        return math.nan
    return float(np.percentile(np.asarray(values, float), q))
