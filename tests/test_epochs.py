"""Retained epoch ring: bit-identical history, bounded eviction, diffs.

The wait-free snapshot story (DESIGN.md §13) stands on one claim: for any
epoch still inside the retention window, ``EpochRing.state_at(e)`` is BYTE
identical to the state the pool published at epoch e. This suite pins that
claim against the actually-published states (captured as the schedule
runs), plus the boundary behavior that makes the ring safe to lean on:
eviction at exactly ``retain`` epochs, the grow barrier (a capacity change
resets retention), ``epoch_of_versions`` (the index-stamp lookup),
``epoch_diff``, and the ``epoch_log`` prune that fixes the unbounded
per-epoch dict leak.
"""
import numpy as np
import pytest

from repro.core import (
    OP_ADD_E,
    OP_ADD_V,
    OP_CON_V,
    OP_REM_E,
    OP_REM_V,
    EpochEvictedError,
    EpochRing,
    epochs,
    make_graph,
    packed_width,
    partition,
    version_vector,
)
from repro.core.distributed import make_graph_mesh
from repro.obs import trace
from repro.obs.metrics import GLOBAL
from repro.runtime.ingest import IngestPool

FIELDS = ("vkey", "valive", "vver", "ecnt", "adj_packed", "adj_in_packed")


def _assert_states_equal(a, b, msg=""):
    for f in FIELDS:
        np.testing.assert_array_equal(
            np.asarray(getattr(a, f)), np.asarray(getattr(b, f)),
            err_msg=f"{msg} field {f!r}")


def _mutation_stream(n):
    """n single-op batches with adds, removes and edge churn."""
    ops = []
    for i in range(n):
        k = i % 8
        if i % 7 == 6:
            ops.append([(OP_REM_E, k, (k + 1) % 8)])
        elif i % 5 == 4:
            ops.append([(OP_REM_V, k)])
        elif i % 2 == 0:
            ops.append([(OP_ADD_V, k)])
        else:
            ops.append([(OP_ADD_E, k, (k + 1) % 8)])
    return ops


def _pump_stream(pool, ops):
    """Apply each batch as its own publish; return {epoch: published state}."""
    published = {pool.epoch: pool.snapshot()}
    for batch in ops:
        pool.submit("c", batch)
        pool.flush()
        published[pool.epoch] = pool.snapshot()
    return published


def test_reconstruction_bit_identical_to_published_states():
    pool = IngestPool(make_graph(32), retain_epochs=64)
    published = _pump_stream(pool, _mutation_stream(20))
    lo, hi = pool.epoch_window()
    assert (lo, hi) == (0, 20)
    for e in range(lo, hi + 1):
        _assert_states_equal(pool.state_at(e), published[e],
                             f"epoch {e} reconstruction diverges:")


def test_state_at_newest_is_the_published_slot_itself():
    pool = IngestPool(make_graph(16), retain_epochs=8)
    _pump_stream(pool, _mutation_stream(3))
    assert pool.state_at(pool.epoch) is pool.snapshot()


def test_eviction_window_boundaries_retain_4():
    pool = IngestPool(make_graph(32), retain_epochs=4)
    published = _pump_stream(pool, _mutation_stream(10))
    lo, hi = pool.epoch_window()
    assert (lo, hi) == (7, 10)          # exactly retain=4 addressable epochs
    # inside the window: exact; first epoch past it: typed eviction
    for e in range(lo, hi + 1):
        _assert_states_equal(pool.state_at(e), published[e])
    with pytest.raises(EpochEvictedError) as exc:
        pool.state_at(lo - 1)
    assert exc.value.epoch == lo - 1
    assert exc.value.window == (lo, hi)
    assert pool.stats.epochs_retained == 4
    assert pool.stats.epochs_evicted == 7  # epochs 0..6 aged out


def test_retain_one_keeps_only_the_newest_epoch():
    pool = IngestPool(make_graph(16), retain_epochs=1)
    _pump_stream(pool, _mutation_stream(5))
    assert pool.epoch_window() == (5, 5)
    with pytest.raises(EpochEvictedError):
        pool.state_at(4)
    _assert_states_equal(pool.state_at(5), pool.snapshot())


def test_grow_is_a_retention_barrier():
    """A capacity change voids every row-shaped delta: the ring resets at
    the grown epoch and pre-grow epochs report eviction even though they
    were inside the nominal retain count."""
    pool = IngestPool(make_graph(4), retain_epochs=64, auto_grow=True)
    # capacity 4 and 6 distinct keys forces at least one R_TABLE_FULL grow
    for k in range(6):
        pool.submit("c", [(OP_ADD_V, 10 * k)])
        pool.flush()
    assert pool.stats.grow_events >= 1
    lo, hi = pool.epoch_window()
    assert lo > 0                        # pre-grow epochs were dropped
    _assert_states_equal(pool.state_at(hi), pool.snapshot())
    with pytest.raises(EpochEvictedError):
        pool.state_at(lo - 1)


def test_epoch_log_pruned_to_ring_window():
    """Satellite bugfix: epoch_log leaked one entry per published epoch;
    it must now track exactly the addressable window."""
    pool = IngestPool(make_graph(32), retain_epochs=4)
    _pump_stream(pool, _mutation_stream(12))
    lo, hi = pool.epoch_window()
    assert sorted(pool.epoch_log) == list(range(lo, hi + 1))
    # retained epochs answer; evicted epochs raise the typed error
    assert pool.linearization_prefix(hi) == len(pool.linearization)
    with pytest.raises(EpochEvictedError) as exc:
        pool.linearization_prefix(lo - 1)
    assert exc.value.window == (lo, hi)


def test_epoch_diff_reports_touched_rows_and_keys():
    pool = IngestPool(make_graph(32), retain_epochs=64)
    pool.submit("c", [(OP_ADD_V, 1), (OP_ADD_V, 2)])
    pool.flush()                          # epoch 1
    pool.submit("c", [(OP_ADD_E, 1, 2)])
    pool.flush()                          # epoch 2
    pool.submit("c", [(OP_ADD_V, 3)])
    pool.flush()                          # epoch 3
    d = pool.epoch_diff(1, 3)
    # rows touched after epoch 1: vertex 1's row (new out-edge bumps its
    # ecnt/adjacency), vertex 2's row (in-edge bookkeeping), vertex 3's slot
    state = pool.snapshot()
    vkey = np.asarray(state.vkey)
    keys_after = {int(vkey[r]) for r in d.rows}
    assert {1, 3} <= keys_after
    assert d.e_from == 1 and d.e_to == 3
    # endpoints are order-normalized
    d2 = pool.epoch_diff(3, 1)
    np.testing.assert_array_equal(d.rows, d2.rows)
    # identical endpoints: empty diff
    assert pool.epoch_diff(2, 2).rows.size == 0
    # evicted endpoint: typed error
    small = IngestPool(make_graph(32), retain_epochs=2)
    _pump_stream(small, _mutation_stream(6))
    with pytest.raises(EpochEvictedError):
        small.epoch_diff(0, small.epoch)


def test_epoch_of_versions_finds_the_stamped_epoch():
    pool = IngestPool(make_graph(32), retain_epochs=64)
    published = _pump_stream(pool, _mutation_stream(8))
    for e, state in published.items():
        vv = np.asarray(version_vector(state))
        got = pool.ring.epoch_of_versions(vv, state.capacity)
        # the NEWEST matching epoch is returned (a failed/no-op publish can
        # leave versions unchanged, so got may exceed e) — what matters is
        # that equal versions imply a byte-identical graph, so pinning to
        # the returned epoch answers exactly as the stamped one would
        assert got is not None and got >= e
        _assert_states_equal(pool.state_at(got), state,
                             f"versions matched epoch {got} but states differ:")
    # an alien version vector (or capacity) matches nothing
    alien = np.full_like(np.asarray(version_vector(pool.snapshot())), 7)
    assert pool.ring.epoch_of_versions(alien, pool.snapshot().capacity) is None
    assert pool.ring.epoch_of_versions(
        np.asarray(version_vector(pool.snapshot())), 999) is None


def test_ring_push_rejects_epoch_gaps():
    ring = EpochRing(retain=4)
    state = make_graph(8)
    ring.reset(0, state)
    with pytest.raises(ValueError):
        ring.push(2, state)               # 0 -> 2 skips epoch 1


def test_ring_retain_validation():
    with pytest.raises(ValueError):
        EpochRing(retain=0)


def test_sharded_pool_ring_reconstructs_dense_bit_identical():
    """A sharded pool's ring records host gathers; reconstruction is the
    dense form of every published epoch (time-travel is read-only, so the
    gathered dense answer is the contract)."""
    mesh = make_graph_mesh()
    state = partition.shard_state(mesh, make_graph(32))
    pool = IngestPool(state, mesh=mesh, retain_epochs=64)
    published = _pump_stream(pool, _mutation_stream(8))
    lo, hi = pool.epoch_window()
    assert (lo, hi) == (0, 8)
    # np.asarray gathers sharded fields, so one comparison covers both the
    # dense reconstructions (older epochs) and the sharded newest slot
    for e in range(lo, hi + 1):
        _assert_states_equal(pool.state_at(e), published[e],
                             f"epoch {e} (sharded pool):")


# -- the delta built on the device -----------------------------------------
RING_FIELDS = ("vkey", "valive", "vver", "ecnt", "adj_packed")
EDGES_INTO_0 = [(OP_ADD_V, k) for k in range(6)] + [
    (OP_ADD_E, k, 0) for k in (1, 2, 3)] + [(OP_ADD_E, 4, 5)]


def _oracle_record(before, after):
    """The record of ``before`` -> ``after`` by plain numpy: full host
    copies of both published states, row compare, XOR."""
    b = [np.asarray(getattr(before, k)) for k in RING_FIELDS]
    a = [np.asarray(getattr(after, k)) for k in RING_FIELDS]
    changed = np.zeros(a[0].shape[0], dtype=bool)
    for x, y in zip(b, a):
        changed |= (x != y).reshape(x.shape[0], -1).any(axis=1)
    rows = np.flatnonzero(changed).astype(np.int32)
    versions = np.stack([a[3], a[2]], axis=-1)
    return rows, [x[rows] ^ y[rows] for x, y in zip(b, a)], versions


def _slots(state, keys):
    vkey = np.asarray(state.vkey)
    return sorted(int(np.flatnonzero(vkey == k)[0]) for k in keys)


def _bit_flips(state, cells):
    """``state`` then one state per (row, word) cell, each with that packed
    word's low bit flipped in the previous state."""
    out = [state]
    for r, w in cells:
        adj = out[-1].adj_packed
        out.append(out[-1]._replace(adj_packed=adj.at[r, w].set(
            adj[r, w] ^ 1)))
    return out


# each case: capacity, client batches (one publish each), the batch index
# before which the ring is dumped and reloaded, and what its records show;
# or hand-built states pushed to a bare ring
DELTA_CASES = {
    # RemV 0 clears column 0: the rows of 1, 2, 3 change, no lane names them
    "remv_column_clear": dict(
        batches=[EDGES_INTO_0, [(OP_REM_V, 0)]],
        expect=lambda recs, pub: recs[-1].rows.tolist() == _slots(
            pub[1], (0, 1, 2, 3))),
    "add_vertex": dict(
        batches=[[(OP_ADD_V, 7)], [(OP_ADD_V, 8), (OP_ADD_V, 9)]],
        expect=lambda recs, pub: [r.rows.size for r in recs] == [1, 2]),
    "add_edge": dict(
        batches=[EDGES_INTO_0, [(OP_ADD_E, 5, 4)]],
        expect=lambda recs, pub: recs[-1].rows.tolist() == _slots(
            pub[2], (5,))),
    "remove_edge": dict(
        batches=[EDGES_INTO_0, [(OP_REM_E, 4, 5)]],
        expect=lambda recs, pub: recs[-1].rows.tolist() == _slots(
            pub[2], (4,))),
    "no_row_changes": dict(
        batches=[EDGES_INTO_0, [(OP_CON_V, 1)], [(OP_REM_E, 1, 2)]],
        expect=lambda recs, pub: [r.rows.size for r in recs[1:]] == [0, 0]),
    # more changed rows than one gather chunk holds
    "rows_span_chunks": dict(
        capacity=512,
        batches=[[(OP_ADD_V, k) for k in range(300)], [(OP_REM_V, 3)]],
        expect=lambda recs, pub: recs[0].rows.size == 300
        > epochs._GATHER_ROWS),
    # capacity 4 and six keys force a grow: the ring resets at the grown
    # epoch and the pushes after it compare against the grown state
    "grow_barrier": dict(
        capacity=4,
        batches=[[(OP_ADD_V, 10 * k)] for k in range(6)]
        + [[(OP_ADD_E, 0, 10)], [(OP_REM_V, 0)]],
        expect=lambda recs, pub: recs[0].capacity > 4
        and recs[-1].epoch == 8),
    # the ring reloaded from its dump has no device predecessor: its first
    # push uploads the host mirror and compares on the device
    "first_push_after_load": dict(
        batches=[EDGES_INTO_0, [(OP_ADD_E, 5, 1)], [(OP_REM_V, 1)],
                 [(OP_ADD_V, 9)]],
        reload_at=2,
        expect=lambda recs, pub: [r.epoch for r in recs] == [1, 2, 3, 4]),
    # a row whose packed words change while its counters do not (no op
    # does that today; the compare must not lean on ecnt/vver)
    "adjacency_bits_only": dict(
        states=lambda: _bit_flips(make_graph(32), [(3, 0), (3, 0), (9, 0)]),
        expect=lambda recs, pub: [r.rows.tolist() for r in recs]
        == [[3], [3], [9]]),
    "sharded_pool": dict(
        sharded=True,
        batches=[EDGES_INTO_0, [(OP_REM_V, 0)], [(OP_ADD_E, 4, 1)]],
        expect=lambda recs, pub: recs[1].rows.size == 4),
}


@pytest.mark.parametrize("case", sorted(DELTA_CASES))
def test_device_delta_records_match_a_host_oracle(case):
    """Every retained record, and the host mirror, equal byte for byte
    what a plain numpy diff of the published states gives."""
    spec = DELTA_CASES[case]
    if "states" in spec:
        published = dict(enumerate(spec["states"]()))
        ring = EpochRing(retain=64)
        ring.reset(0, published[0])
        for e in range(1, len(published)):
            ring.push(e, published[e])
    else:
        state = make_graph(spec.get("capacity", 32))
        mesh = None
        if spec.get("sharded"):
            mesh = make_graph_mesh()
            state = partition.shard_state(mesh, state)
        pool = IngestPool(state, mesh=mesh, retain_epochs=64)
        published = {0: pool.snapshot()}
        for i, batch in enumerate(spec["batches"]):
            if i == spec.get("reload_at"):
                pool.ring = EpochRing.load(*pool.ring.dump())
            pool.submit("c", batch)
            pool.flush()
            published[pool.epoch] = pool.snapshot()
        ring = pool.ring
    recs = list(ring._records)
    assert recs and spec["expect"](recs, published), [
        r.rows.tolist() for r in recs]
    for rec in recs:
        rows, xors, versions = _oracle_record(published[rec.epoch - 1],
                                              published[rec.epoch])
        got = [rec.rows, rec.vkey_xor, rec.valive_xor, rec.vver_xor,
               rec.ecnt_xor, rec.adj_xor, rec.versions]
        for g, w in zip(got, [rows, *xors, versions]):
            assert (g.dtype, g.shape) == (w.dtype, w.shape), rec.epoch
            assert g.tobytes() == w.tobytes(), rec.epoch
    newest = published[max(published)]
    for k in RING_FIELDS:
        assert ring._latest[k].tobytes() == np.asarray(
            getattr(newest, k)).tobytes(), k


def test_device_delta_compiles_once_and_counts_what_crosses():
    """Pushes of 300, 0, 1 and 2 changed rows at one capacity: no compile
    after the first push, no whole-state copy after the pool's reset, and
    each push copies the mask plus its padded row chunks."""
    cap, chunk = 512, epochs._GATHER_ROWS
    row_bytes = 4 + 1 + 4 + 4 + 4 * packed_width(cap)
    with trace.capture():
        full0 = GLOBAL.get("ring.full_copies")
        pool = IngestPool(make_graph(cap), retain_epochs=8)
        assert GLOBAL.get("ring.full_copies") == full0 + 1
        pool.submit("c", [(OP_ADD_V, 1)])
        pool.flush()
        compiled = (epochs._changed_rows._cache_size(),
                    epochs._xor_rows._cache_size())
        for batch, k in (([(OP_ADD_V, j) for j in range(2, 302)], 300),
                         ([(OP_CON_V, 1)], 0), ([(OP_ADD_E, 1, 2)], 1),
                         ([(OP_REM_V, 2)], 2)):
            before = dict(GLOBAL.get("ring.d2h_bytes"))
            pool.submit("c", batch)
            pool.flush()
            assert pool.ring._records[-1].rows.size == k
            after = GLOBAL.get("ring.d2h_bytes")
            assert after["count"] == before["count"] + 1
            assert after["sum"] - before["sum"] == (
                cap + -(-k // chunk) * chunk * row_bytes)
        assert (epochs._changed_rows._cache_size(),
                epochs._xor_rows._cache_size()) == compiled
        assert GLOBAL.get("ring.full_copies") == full0 + 1
