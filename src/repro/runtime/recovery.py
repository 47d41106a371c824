"""Crash recovery for the serving stack: checkpoint + WAL replay
(DESIGN.md §16).

``GraphCheckpointer`` wraps the generic sharded ``Checkpointer`` with the
graph-specific tree: the six ``GraphState`` device fields (both packed
adjacency mirrors included), every retained ``EpochRing`` record, and the
pool's logical registers (linearization log, epoch->prefix map, ticket id
counter, index freshness stamp) as JSON extra.  The ring makes the leaf
count variable per checkpoint, which is why ``Checkpointer`` grew
``restore_raw``.

``recover`` rebuilds the pre-crash published prefix: load the newest
checkpoint, then replay every WAL record with a newer epoch through the
SAME fused ``apply_ops_fast`` path (same lane padding, same auto-grow
replay discipline) the live pool used — so the recovered state is
bit-identical, not merely equivalent.  Replay is idempotent: records at
or below the checkpointed epoch are skipped (the ``wal-fsync`` crash can
leave a durable record the checkpoint already covers), and each record's
stored result codes are cross-checked against the replayed ones — a
mismatch means log/checkpoint corruption and raises ``RecoveryError``
rather than silently serving wrong state.

``resume_pool`` turns a ``Recovered`` into a live ``IngestPool`` whose
published epoch, linearization log, and epoch ring continue exactly where
the dead process stopped.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field

import jax.numpy as jnp
import numpy as np

from repro.core import R_TABLE_FULL, apply_ops_fast, grow, make_graph, \
    make_op_batch
from repro.core import partition
from repro.core.epochs import EpochRing
from repro.core.graph import GraphState
from repro.checkpoint import Checkpointer
from repro.obs import trace as _trace
from repro.obs.metrics import global_registry as _obs_registry
from repro.runtime.wal import WriteAheadLog

_STATE_FIELDS = ("vkey", "valive", "vver", "ecnt", "adj_packed",
                 "adj_in_packed")


class RecoveryError(RuntimeError):
    """Checkpoint/WAL contents contradict each other — refuse to serve."""


@dataclass
class Recovered:
    """Everything ``recover`` reconstructed from disk."""

    state: GraphState | object        # dense, or sharded when mesh given
    epoch: int
    linearization: list = field(default_factory=list)
    epoch_log: dict = field(default_factory=dict)
    next_batch_id: int = 0
    ring: EpochRing = field(default_factory=EpochRing)
    replayed_rounds: int = 0          # WAL records applied on top of the ckpt
    skipped_records: int = 0          # idempotence: records the ckpt covered
    ckpt_step: int | None = None      # checkpoint epoch loaded (None = fresh)
    index_stamp: dict | None = None
    restore_s: float = 0.0


class GraphCheckpointer:
    """Graph-aware snapshots at a round cadence, truncating the WAL behind
    them (the checkpoint-truncation invariant: every epoch is covered by
    the checkpoint XOR the WAL tail, never neither)."""

    def __init__(self, directory: str, *, keep: int = 3):
        self.inner = Checkpointer(directory, keep=keep)

    def _leaves_manifest(self, *, epoch, state, ring, linearization,
                         epoch_log, next_batch_id, index_stamp):
        dense = partition.unshard(state) if hasattr(state, "mesh") else state
        leaves = [np.asarray(getattr(dense, f)) for f in _STATE_FIELDS]
        ring_leaves, ring_meta = ring.dump()
        extra = {
            "kind": "graph",
            "epoch": int(epoch),
            "capacity": int(dense.capacity),
            "n_state_leaves": len(_STATE_FIELDS),
            "ring_meta": ring_meta,
            "linearization": [int(b) for b in linearization],
            "epoch_log": {str(k): int(v) for k, v in epoch_log.items()},
            "next_batch_id": int(next_batch_id),
            "index_stamp": index_stamp,
        }
        return leaves + ring_leaves, extra

    def save_graph(self, *, epoch, state, ring, linearization, epoch_log,
                   next_batch_id, index_stamp=None) -> None:
        """One durable graph snapshot, published atomically at step=epoch.
        Returns once it is on disk: the ring's leaves are its live host
        mirror, which the next push patches in place."""
        leaves, extra = self._leaves_manifest(
            epoch=epoch, state=state, ring=ring, linearization=linearization,
            epoch_log=epoch_log, next_batch_id=next_batch_id,
            index_stamp=index_stamp)
        with _trace.span("ckpt.save", epoch=int(epoch), leaves=len(leaves)):
            t0 = time.perf_counter()
            self.inner.save(int(epoch), leaves, extra=extra, blocking=True)
            if _trace.enabled():
                _obs_registry().observe("ckpt.save_s",
                                        time.perf_counter() - t0)

    def save_torn(self, *, epoch, state, ring, linearization, epoch_log,
                  next_batch_id, index_stamp=None) -> None:
        """The ``ckpt-mid-write`` crash: the tmp dir is fully written but
        the rename never happens — ``restore`` must load the PREVIOUS
        step (tests pin this on the generic checkpointer too)."""
        self.inner.wait()
        leaves, extra = self._leaves_manifest(
            epoch=epoch, state=state, ring=ring, linearization=linearization,
            epoch_log=epoch_log, next_batch_id=next_batch_id,
            index_stamp=index_stamp)
        manifest = {
            "step": int(epoch),
            "treedef": "torn",
            "n_leaves": len(leaves),
            "shapes": [list(x.shape) for x in leaves],
            "dtypes": [str(x.dtype) for x in leaves],
            "shard_hint": "torn write (crash simulation)",
            "extra": extra,
            "time": time.time(),
        }
        self.inner._write(int(epoch), leaves, manifest, publish=False)

    def latest_step(self) -> int | None:
        return self.inner.latest_step()

    def restore_graph(self, *, step=None):
        """(dense GraphState, EpochRing, extra dict) of a published step."""
        leaves, manifest = self.inner.restore_raw(step=step)
        extra = manifest["extra"]
        if extra.get("kind") != "graph":
            raise RecoveryError(f"checkpoint step {manifest['step']} is not "
                                f"a graph snapshot")
        n = int(extra["n_state_leaves"])
        state = GraphState(*[jnp.asarray(x) for x in leaves[:n]])
        ring = EpochRing.load(leaves[n:], extra["ring_meta"])
        return state, ring, extra


def _replay_apply(base, batch, *, mesh, auto_grow):
    """The pool's fused-apply-with-grow discipline, replicated exactly so
    replayed epochs are bit-identical to the ones the dead pool published."""
    grows = 0
    if mesh is not None:
        state, res = partition.apply_ops_fast(base, batch)
    else:
        state, res = apply_ops_fast(base, batch)
    res = np.asarray(res)
    while auto_grow and (res == R_TABLE_FULL).any():
        if mesh is not None:
            base = partition.grow(base, 2 * base.capacity)
            state, res = partition.apply_ops_fast(base, batch)
        else:
            base = grow(base, 2 * base.capacity)
            state, res = apply_ops_fast(base, batch)
        res = np.asarray(res)
        grows += 1
    return state, res, grows


def recover(ckpt: GraphCheckpointer | str | None, wal: WriteAheadLog | str | None,
            *, capacity: int = 32, mesh=None, auto_grow: bool = True,
            retain_epochs: int = 64, verify_results: bool = True) -> Recovered:
    """Latest checkpoint + WAL replay -> the pre-crash published prefix.

    ``ckpt``/``wal`` accept live objects or paths (or None: recover from
    the other alone; both None yields a fresh empty graph).  ``capacity``
    only seats the fresh-graph case — a checkpoint carries its own.
    """
    t0 = time.perf_counter()
    if isinstance(ckpt, str):
        ckpt = GraphCheckpointer(ckpt)
    if isinstance(wal, str):
        wal = WriteAheadLog(wal)

    with _trace.span("recovery.restore") as sp:
        out = Recovered(state=None, epoch=0, ring=EpochRing(retain_epochs))
        # 1) newest durable checkpoint (a torn tmp dir is invisible: only
        #    renamed step_* dirs are addressable)
        dense = None
        if ckpt is not None and ckpt.latest_step() is not None:
            dense, ring, extra = ckpt.restore_graph()
            out.epoch = int(extra["epoch"])
            out.linearization = list(extra["linearization"])
            out.epoch_log = {int(k): int(v)
                             for k, v in extra["epoch_log"].items()}
            out.next_batch_id = int(extra["next_batch_id"])
            out.index_stamp = extra.get("index_stamp")
            out.ring = ring
            out.ckpt_step = int(extra["epoch"])
        fresh = dense is None
        if fresh:
            dense = make_graph(capacity)
            out.epoch_log = {0: 0}

        state = partition.shard_state(mesh, dense) if mesh is not None \
            else dense
        if fresh:
            # the ring's device predecessor is the state replay starts from
            out.ring.reset(0, state)

        # 2) idempotent WAL replay of every epoch past the checkpoint
        if wal is not None:
            for rec in wal.records():
                if rec.epoch <= out.epoch:
                    out.skipped_records += 1     # ckpt already covers it
                    continue
                if rec.epoch != out.epoch + 1:
                    raise RecoveryError(
                        f"WAL gap: have epoch {out.epoch}, next record is "
                        f"epoch {rec.epoch}")
                batch = make_op_batch(rec.ops, lanes=rec.pad)
                state, res, _ = _replay_apply(state, batch, mesh=mesh,
                                              auto_grow=auto_grow)
                if verify_results and rec.results:
                    got = [int(x) for x in np.asarray(res)[:rec.lanes]]
                    if got != [int(x) for x in rec.results]:
                        raise RecoveryError(
                            f"replay divergence at epoch {rec.epoch}: "
                            f"logged {rec.results} got {got}")
                out.linearization.extend(int(b) for b in rec.batch_ids)
                out.epoch = rec.epoch
                out.epoch_log[rec.epoch] = len(out.linearization)
                out.ring.push(rec.epoch, state)
                if rec.batch_ids:
                    out.next_batch_id = max(out.next_batch_id,
                                            max(rec.batch_ids) + 1)
                out.replayed_rounds += 1

        out.state = state
        out.restore_s = time.perf_counter() - t0
        sp.set(epoch=out.epoch, replayed=out.replayed_rounds,
               skipped=out.skipped_records)
        if _trace.enabled():
            _obs_registry().observe("recovery.restore_s", out.restore_s)
    return out


def resume_pool(recovered: Recovered, **pool_kwargs):
    """Construct an IngestPool that continues from a ``Recovered`` point:
    same published epoch, linearization log, epoch ring, and ticket-id
    counter as the dead process."""
    from repro.runtime.ingest import IngestPool

    pool = IngestPool(recovered.state, **pool_kwargs)
    pool._slots = [(recovered.epoch, recovered.state),
                   (recovered.epoch, recovered.state)]
    pool._cur = 0
    pool._head = recovered.state
    pool.ring = recovered.ring
    pool.linearization = list(recovered.linearization)
    pool.epoch_log = dict(recovered.epoch_log)
    pool._next_id = int(recovered.next_batch_id)
    pool.stats.epochs = recovered.epoch
    pool.stats.epochs_retained = len(pool.ring) + 1
    pool.stats.epochs_evicted = pool.ring.evicted
    return pool
