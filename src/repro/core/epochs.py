"""Wait-free epoch ring: retained snapshot history as packed deltas
(DESIGN.md §13).

The ingest pool (runtime/ingest.py) publishes one immutable functional
snapshot per admission round behind an atomic slot flip — epochs 0, 1, 2,
... in publish order. The successor paper ("Non-blocking Dynamic Unbounded
Graphs with Wait-Free Snapshot", arXiv 2310.02380) makes the collect side
wait-free by letting a reader that keeps losing the double-collect race
resolve against a *retained* consistent epoch instead of retrying forever.
This module reifies that retention: a bounded ring of

    (epoch, version_vector, packed row deltas)

records, one per published epoch, kept host-side as numpy (the device
state stays the single O(V^2/32) packed representation; the ring costs
O(touched_rows * W) per epoch plus one O(V) version vector).

Deltas are XOR patches. For every row whose bytes changed between epoch
e-1 and e the record stores ``row_index`` plus the XOR of the five field
rows (vkey/valive/vver/ecnt scalars and the packed out-adjacency row).
XOR is its own inverse, so the SAME record replays the transition in
either direction: ``state_at(e)`` starts from the newest published state
(the ring's host mirror) and XORs records backward until it lands on e —
bit-identical history reconstruction, proven by tests/test_epochs.py
against the actually published states. The in-adjacency is not stored:
it is re-derived as the packed transpose at reconstruction time (the
DESIGN.md §11 transpose invariant makes that lossless).

The delta is built on the device. The ring keeps a reference to the
previous published state's five fields (no copy: the pool's other
snapshot slot holds the same arrays). A push compares them with the new
state in one jitted pass (``ring.delta``: a row-wise ``any`` over the
packed words, the 64 KiB mask at V = 2^16 fetched and its nonzeros
listed), then gathers the XOR of the changed rows in fixed-size chunks
and copies only those to the host (``ring.to_host``, which also patches
the host mirror in place, builds the record and evicts). A row-sharded
state compares shard by shard under the same jit. Only a ``reset`` (the
pool's start, a grow barrier) copies the whole state to the host; after
``load`` the host mirror is uploaded once as the device predecessor, and
the first push takes the same device path. Counters (under tracing):
``ring.d2h_bytes`` per push or reset, ``ring.full_copies``.

Three query surfaces ride on the ring (DESIGN.md §13):

  * **wait-free resolution** — ``snapshot.get_paths_session(
    on_conflict="epoch")`` pins its answer to one retained epoch after a
    bounded retry budget instead of spinning;
  * **time-travel reachability** — "was u→w reachable at epoch e?" via
    ``state_at(e)`` (a frozen state answers with a single collect);
  * **epoch diff** — "which rows changed between e1 and e2?" via the
    union of the retained records' row sets.

Capacity growth is a retention barrier: a ``grow`` changes every row's
shape, so the ring resets at the grown epoch and earlier epochs report
``EpochEvictedError`` — the same typed signal an epoch past the bounded
retention window produces.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import numpy as np

import jax
import jax.numpy as jnp

from repro.core.graph import GraphState, pack_transpose
from repro.obs import trace as _trace
from repro.obs.metrics import global_registry as _obs_registry

# The five per-row fields a delta record patches, in GraphState order
# (adj_in_packed is derived, never stored; see module docstring).
_ROW_FIELDS = ("vkey", "valive", "vver", "ecnt", "adj_packed")

# Rows per changed-row gather: one compiled shape per capacity whatever a
# push's row count (the last chunk is padded and sliced on the host), so
# no push after the first at a capacity compiles.
_GATHER_ROWS = 256


@jax.jit
def _changed_rows(prev, new):
    """bool[V]: rows where any of the five fields differs (the packed words
    reduced row-wise; row-local, so a row-sharded state compares shard by
    shard)."""
    changed = jnp.any(prev[-1] != new[-1], axis=1)
    for a, b in zip(prev[:-1], new[:-1]):
        changed = changed | (a != b)
    return changed


@jax.jit
def _xor_rows(prev, new, idx):
    """The five fields' XOR at rows ``idx``, in ``_ROW_FIELDS`` order."""
    return tuple(a[idx] ^ b[idx] for a, b in zip(prev, new))


class EpochEvictedError(LookupError):
    """Typed miss for a time-travel/diff query outside the retained window.

    Carries the requested epoch and the window that was available so
    servers can surface a structured "epoch evicted" result instead of a
    bare failure (DESIGN.md §13).
    """

    def __init__(self, epoch: int, window: tuple[int, int]):
        self.epoch = int(epoch)
        self.window = (int(window[0]), int(window[1]))
        super().__init__(
            f"epoch {epoch} outside retained window "
            f"[{window[0]}, {window[1]}]")


@dataclass(frozen=True)
class EpochRecord:
    """One retained epoch: its version vector + the XOR patch from e-1."""

    epoch: int
    capacity: int
    versions: np.ndarray      # int32[V, 2] — (ecnt, vver) AT this epoch
    rows: np.ndarray          # int32[K] — slots whose bytes changed
    vkey_xor: np.ndarray      # int32[K]
    valive_xor: np.ndarray    # bool[K]
    vver_xor: np.ndarray      # int32[K]
    ecnt_xor: np.ndarray      # int32[K]
    adj_xor: np.ndarray       # uint32[K, W] — packed out-adjacency rows


@dataclass(frozen=True)
class EpochDiff:
    """Epoch-diff answer: the rows touched between two retained epochs."""

    e_from: int
    e_to: int
    rows: np.ndarray          # int32[K] — union of touched slots
    keys_before: np.ndarray   # int32[K] — vkey at e_from (-1 = empty slot)
    keys_after: np.ndarray    # int32[K] — vkey at e_to


def _device_fields(state) -> tuple:
    """The five patchable fields of a (dense or sharded) state, in place."""
    return tuple(getattr(state, k) for k in _ROW_FIELDS)


def _count_d2h(nbytes: int) -> None:
    if _trace.enabled():
        _obs_registry().observe("ring.d2h_bytes", nbytes)


class EpochRing:
    """Bounded retention of published epochs as backward-replayable deltas.

    ``retain`` bounds the number of *addressable* epochs (records kept =
    retain - 1 plus the newest full state): after publishing epoch N the
    window is ``[max(reset_epoch, N - retain + 1), N]``. A push patches
    the host mirror in place, so pushes and reads are driven from one
    thread (the ingest pool's rounds and the server that owns it); the
    reconstruction surfaces copy the mirror and touch only immutable
    records, so readers never block writers (DESIGN.md §13).
    """

    def __init__(self, retain: int = 64):
        if retain < 1:
            raise ValueError(f"retain must be >= 1, got {retain}")
        self.retain = int(retain)
        self.evicted = 0              # cumulative records dropped (stats)
        self._records: list[EpochRecord] = []
        # host mirror of the newest state, patched in place by each push
        self._latest: dict[str, np.ndarray] | None = None
        # the newest state's five device fields (None after ``load``, until
        # the first push uploads the mirror)
        self._prev: tuple | None = None
        self._newest = 0

    # -- maintenance (writer side) ------------------------------------------
    def reset(self, epoch: int, state) -> None:
        """Restart retention at ``epoch`` (initial state or a grow barrier:
        a capacity change invalidates every row-shaped delta). Traced as
        ``ring.delta`` (the records dropped) then ``ring.to_host`` (the
        whole state copied to the host mirror)."""
        fields = _device_fields(state)
        nbytes = sum(x.nbytes for x in fields)
        with _trace.span("ring.delta", rows=int(fields[0].shape[0]),
                         bytes=nbytes):
            self.evicted += len(self._records)
            self._records = []
        with _trace.span("ring.to_host"):
            # writable host copies (a sharded state is gathered)
            self._latest = {k: np.array(x) for k, x in zip(_ROW_FIELDS,
                                                            fields)}
        if _trace.enabled():
            _obs_registry().inc("ring.full_copies")
        _count_d2h(nbytes)
        self._prev = fields
        self._newest = int(epoch)

    def push(self, epoch: int, state) -> None:
        """Record the transition newest -> ``epoch`` (consecutive publishes).

        Traced as ``ring.delta`` (device compare with the predecessor, mask
        fetch, row list) then ``ring.to_host`` (gather and copy of the
        changed rows' XOR, mirror patch, record, eviction). After ``load``
        the predecessor is the host mirror, uploaded once under
        ``ring.delta``."""
        fields = _device_fields(state)
        if (self._latest is None
                or fields[0].shape[0] != self._latest["vkey"].shape[0]):
            self.reset(epoch, state)
            return
        if epoch != self._newest + 1:
            raise ValueError(
                f"non-consecutive publish: {self._newest} -> {epoch}")
        with _trace.span("ring.delta") as sp:
            if self._prev is None:
                # read only in this push, before the commit patches the
                # mirror it was uploaded from
                self._prev = tuple(
                    jax.device_put(self._latest[k], x.sharding)
                    for k, x in zip(_ROW_FIELDS, fields))
            mask = np.asarray(_changed_rows(self._prev, fields))
            rows = np.flatnonzero(mask).astype(np.int32)
            chunk = min(_GATHER_ROWS, mask.shape[0])
            n_chunks = -(-rows.size // chunk)
            idx = np.zeros(n_chunks * chunk, dtype=np.int32)
            idx[:rows.size] = rows
            row_nbytes = sum(x.dtype.itemsize * math.prod(x.shape[1:])
                             for x in fields)
            d2h = mask.nbytes + idx.size * row_nbytes
            sp.set(rows=int(rows.size), bytes=d2h)
        with _trace.span("ring.to_host"):
            parts = jax.device_get([
                _xor_rows(self._prev, fields, idx[i:i + chunk])
                for i in range(0, idx.size, chunk)])
            if parts:
                # slice the padded last chunk first, so that a record holds
                # its own rows only
                tail = rows.size - (len(parts) - 1) * chunk
                xors = [np.concatenate([p[j] for p in parts[:-1]]
                                       + [parts[-1][j][:tail]])
                        for j in range(len(_ROW_FIELDS))]
            else:
                xors = [self._latest[k][rows] for k in _ROW_FIELDS]
            self._commit(epoch, rows, xors)
        _count_d2h(d2h)
        self._prev = fields

    def _commit(self, epoch: int, rows: np.ndarray, xors: list) -> None:
        """Patch the host mirror with the XOR rows, append the record of
        ``epoch`` and evict past ``retain``."""
        f = self._latest
        for k, x in zip(_ROW_FIELDS, xors):
            f[k][rows] ^= x
        self._records.append(EpochRecord(
            int(epoch), int(f["vkey"].shape[0]),
            np.stack([f["ecnt"], f["vver"]], axis=-1), rows, *xors))
        self._newest = int(epoch)
        while len(self._records) > self.retain - 1:
            self._records.pop(0)
            self.evicted += 1
            if _trace.enabled():
                _obs_registry().inc("ring.evictions")
        if _trace.enabled():
            _obs_registry().set("ring.occupancy", len(self._records))
            _trace.counter("ring.occupancy", len(self._records))

    # -- read side ----------------------------------------------------------
    def window(self) -> tuple[int, int]:
        """(oldest addressable epoch, newest published epoch), inclusive."""
        return self._newest - len(self._records), self._newest

    def __len__(self) -> int:
        return len(self._records)

    def contains(self, epoch: int) -> bool:
        lo, hi = self.window()
        return lo <= int(epoch) <= hi

    def _fields_at(self, epoch: int) -> dict[str, np.ndarray]:
        lo, hi = self.window()
        if not lo <= int(epoch) <= hi:
            raise EpochEvictedError(epoch, (lo, hi))
        cur = {k: v.copy() for k, v in self._latest.items()}
        for rec in reversed(self._records):
            if rec.epoch <= epoch:
                break
            r = rec.rows
            cur["vkey"][r] ^= rec.vkey_xor
            cur["valive"][r] ^= rec.valive_xor
            cur["vver"][r] ^= rec.vver_xor
            cur["ecnt"][r] ^= rec.ecnt_xor
            cur["adj_packed"][r] ^= rec.adj_xor
        return cur

    def state_at(self, epoch: int) -> GraphState:
        """Reconstruct the published state of ``epoch`` — bit-identical to
        what ``IngestPool.snapshot()`` returned when that epoch was current
        (tests/test_epochs.py pins this against retained real states).
        Always a dense ``GraphState`` (time-travel queries are read-only;
        a sharded pool's history reconstructs to the gathered dense form).
        Raises ``EpochEvictedError`` outside the window."""
        with _trace.span("ring.state_at", epoch=int(epoch)) as sp:
            f = self._fields_at(epoch)
            if _trace.enabled():
                # replay depth: records XORed backward from the newest state
                depth = min(len(self._records),
                            max(0, self._newest - int(epoch)))
                sp.set(depth=depth)
                _obs_registry().observe("ring.resolve_depth", depth)
            adj = jnp.asarray(f["adj_packed"])
            return self._state_from_fields(f, adj)

    def _state_from_fields(self, f, adj) -> GraphState:
        return GraphState(
            vkey=jnp.asarray(f["vkey"]),
            valive=jnp.asarray(f["valive"]),
            vver=jnp.asarray(f["vver"]),
            ecnt=jnp.asarray(f["ecnt"]),
            adj_packed=adj,
            adj_in_packed=pack_transpose(adj, int(f["vkey"].shape[0])),
        )

    def versions_at(self, epoch: int) -> np.ndarray:
        """(ecnt, vver) int32[V, 2] of a retained epoch (cheap: stored for
        every record; reconstructed only for the window's oldest epoch)."""
        lo, hi = self.window()
        if not lo <= int(epoch) <= hi:
            raise EpochEvictedError(epoch, (lo, hi))
        for rec in self._records:
            if rec.epoch == epoch:
                return rec.versions
        if epoch == hi:   # no records yet (fresh ring): newest == latest
            f = self._latest
        else:             # the window's oldest epoch precedes every record
            f = self._fields_at(epoch)
        return np.stack([f["ecnt"], f["vver"]], axis=-1)

    def epoch_of_versions(self, versions, capacity: int) -> int | None:
        """Newest retained epoch whose version vector equals ``versions``
        (the index-stamp lookup of DESIGN.md §13), or None. Equal versions
        imply a byte-identical graph (monotone counters — the §9 freshness
        argument), so an index stamped with these versions answers queries
        pinned to that epoch exactly."""
        if self._latest is None or capacity != self._latest["vkey"].shape[0]:
            return None
        want = np.asarray(versions)
        lo, hi = self.window()
        for e in range(hi, lo - 1, -1):
            if np.array_equal(self.versions_at(e), want):
                return e
        return None

    # -- checkpoint serialization (DESIGN.md §16) ---------------------------
    def dump(self) -> tuple[list[np.ndarray], dict]:
        """Flatten the ring into (leaves, meta) for the graph checkpointer.

        Leaf order: the 5 ``_latest`` fields (in ``_ROW_FIELDS`` order),
        then 7 arrays per retained record (versions, rows, and the five
        XOR patches).  ``meta`` is JSON-safe and records the layout so
        ``load`` can reassemble records of any count — the reason the
        checkpointer grew ``restore_raw`` (template restores assume a
        fixed leaf count). The mirror's leaves are the arrays the next
        push patches in place: write them before it (``GraphCheckpointer
        .save_graph`` blocks until the checkpoint is on disk).
        """
        meta = {"retain": self.retain, "newest": self._newest,
                "evicted": self.evicted, "n_records": len(self._records),
                "has_latest": self._latest is not None,
                "record_epochs": [r.epoch for r in self._records]}
        leaves: list[np.ndarray] = []
        if self._latest is not None:
            leaves += [self._latest[k] for k in _ROW_FIELDS]
        for rec in self._records:
            leaves += [rec.versions, rec.rows, rec.vkey_xor, rec.valive_xor,
                       rec.vver_xor, rec.ecnt_xor, rec.adj_xor]
        return leaves, meta

    @classmethod
    def load(cls, leaves: list[np.ndarray], meta: dict) -> "EpochRing":
        """Rebuild a ring from ``dump`` output, bit-identical: same window,
        same records, same eviction counter. The mirror is copied (a push
        patches it in place); the first push uploads it as the device
        predecessor."""
        ring = cls(retain=int(meta["retain"]))
        ring._newest = int(meta["newest"])
        ring.evicted = int(meta["evicted"])
        i = 0
        if meta.get("has_latest"):
            ring._latest = {k: np.array(leaves[i + j])
                            for j, k in enumerate(_ROW_FIELDS)}
            i += len(_ROW_FIELDS)
        cap = (int(ring._latest["vkey"].shape[0])
               if ring._latest is not None else 0)
        for epoch in meta.get("record_epochs", []):
            versions, rows, vk, va, vv, ec, adj = leaves[i:i + 7]
            i += 7
            ring._records.append(EpochRecord(
                epoch=int(epoch), capacity=cap,
                versions=np.asarray(versions),
                rows=np.asarray(rows, dtype=np.int32),
                vkey_xor=np.asarray(vk), valive_xor=np.asarray(va),
                vver_xor=np.asarray(vv), ecnt_xor=np.asarray(ec),
                adj_xor=np.asarray(adj)))
        return ring

    def diff(self, e1: int, e2: int) -> EpochDiff:
        """Rows (and their keys) that changed between two retained epochs.
        Raises ``EpochEvictedError`` if either endpoint left the window."""
        lo, hi = sorted((int(e1), int(e2)))
        w = self.window()
        for e in (lo, hi):
            if not w[0] <= e <= w[1]:
                raise EpochEvictedError(e, w)
        touched: set[int] = set()
        for rec in self._records:
            if lo < rec.epoch <= hi:
                touched.update(int(r) for r in rec.rows)
        rows = np.asarray(sorted(touched), dtype=np.int32)
        vk_lo = self._fields_at(lo)["vkey"]
        vk_hi = self._fields_at(hi)["vkey"]
        return EpochDiff(lo, hi, rows, vk_lo[rows], vk_hi[rows])
