"""Batched, linearizable graph mutations — the lock-free update engine.

Concurrency model (DESIGN.md §3): a batch of B ops from B logical actors is
applied in one device step. Lane order is the linearization order. Two engines:

``apply_ops``       exact reference engine: a ``lax.fori_loop`` over lanes where
                    each lane's op is itself fully vectorized. This is the
                    executable *sequential specification* of the batch
                    semantics (paper §2.2) and the ground truth for tests.

``apply_ops_fast``  disjoint-access-parallel engine: lanes whose referenced
                    keys collide with no other lane are applied in ONE
                    vectorized step (they commute with every other lane, so
                    any interleaving is linearizable); colliding lanes are
                    then applied in lane order by a masked correction loop.
                    This mirrors the paper's performance model exactly —
                    lock-free threads only serialize on CAS contention, i.e.
                    on same-location conflicts — and is where the 5-7x-style
                    scaling over a serialized engine comes from (Fig. 9/10
                    analogues in benchmarks/).

Strong equivalence contract: ``apply_ops_fast`` is BIT-identical to
``apply_ops`` — same result codes AND the same concrete arrays (slot
placement, ecnt, vver), not merely the same abstract graph. Three mechanisms
buy this (tests/test_linearizability_prop.py is the enforcing suite, and the
sharded engine in core/partition.py inherits the contract by mirroring the
same decisions, DESIGN.md §8):

  * ``_alloc_schedule`` precomputes, for every AddVertex lane, whether it
    allocates under lane-order serial execution (per-key liveness is decided
    by the LAST prior AddVertex/RemoveVertex lane on the same key — an
    AddVertex always leaves the key alive, a RemoveVertex always dead) and
    which free slot it takes (allocating lanes consume free slots in
    increasing slot order, exactly what repeated argmax-free does). Clean
    lanes allocate at their scheduled slot, leaving holes that the serial
    correction pass's argmax-free naturally lands in.
  * RemoveVertex lanes are always routed to the serial pass: their in-edge
    source ``ecnt`` bumps read the whole adjacency, so they depend on lanes
    they share no key with. Symmetrically, CAS edge lanes (expect >= 0) go
    serial whenever the batch contains any RemoveVertex — the in-edge bump
    is the one cross-key ``ecnt`` write a CAS read could miss.
  * If the scheduled allocations would exhaust free slots (R_TABLE_FULL
    territory), the whole batch falls back to the serial reference engine —
    capacity exhaustion couples every AddVertex lane, and the host is about
    to ``grow()`` anyway.

CAS semantics: ``OpBatch.expect >= 0`` makes an edge op conditional on the
source vertex's ``ecnt`` equalling ``expect`` (else R_CAS_FAIL) — the direct
analogue of the paper's CAS-with-retry protocol, surfaced to clients.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.graph import (
    EMPTY_KEY,
    OP_ADD_E,
    OP_ADD_V,
    OP_CON_E,
    OP_CON_V,
    OP_NOP,
    OP_REM_E,
    OP_REM_V,
    R_CAS_FAIL,
    R_EDGE_ADDED,
    R_EDGE_NOT_PRESENT,
    R_EDGE_PRESENT,
    R_EDGE_REMOVED,
    R_FALSE,
    R_TABLE_FULL,
    R_TRUE,
    R_VERTEX_NOT_PRESENT,
    GraphState,
    OpBatch,
    bit_mask,
    bit_word,
    find_slot,
    get_bit,
    pack_bits,
    popcount,
    traversable,
    traversable_packed,
    unpack_bits,
)


# ----------------------------------------------------------------------------
# Packed-word adjacency primitives (DESIGN.md §10): every edge mutation is a
# masked bit set/clear on one uint32 word instead of a dense row/cell write.
# ----------------------------------------------------------------------------
def _clear_row_col(adj_packed, slot):
    """Clear adjacency row ``slot`` and column bit ``slot`` in every row
    (the stale-adjacency scrub a slot reuse needs).

    The scrubbed bit set {(slot, *)} ∪ {(*, slot)} is its own transpose, so
    the SAME helper scrubs the in-adjacency (DESIGN.md §11) — every caller
    applies it to both packed matrices."""
    w, m = bit_word(slot), bit_mask(slot)
    cleared = adj_packed.at[slot, :].set(jnp.uint32(0))
    return cleared.at[:, w].set(cleared[:, w] & ~m)


def _set_edge_bit(adj_packed, row, col, present, do):
    """Masked single-bit write: bit (row, col) := present when ``do``."""
    w, m = bit_word(col), bit_mask(col)
    cur = adj_packed[row, w]
    new = jnp.where(do, jnp.where(present, cur | m, cur & ~m), cur)
    return adj_packed.at[row, w].set(new)


# ----------------------------------------------------------------------------
# Single-op primitives (each fully vectorized over the slot table)
# ----------------------------------------------------------------------------
def _free_slot(state: GraphState) -> jax.Array:
    """First truly-free slot (never-used or physically removed). -1 if full."""
    free = state.vkey == EMPTY_KEY
    idx = jnp.argmax(free)
    return jnp.where(jnp.any(free), idx.astype(jnp.int32), jnp.int32(-1))


def _apply_one(state: GraphState, opcode, k1, k2, expect):
    """Apply a single op; returns (state', result).

    Branch-free: every op kind's decision is computed from ``state`` and its
    writes are masked by the opcode, so the serial lane loop rewrites a
    few words of the packed matrices in place. Keep it so:
    XLA copies both packed matrices out of every branch of a
    ``lax.switch``/``lax.cond`` that returns the state — O(V^2/32) bytes
    per serial op. The one heavy write, the AddVertex scrub, runs as a
    0/1-trip loop for the same reason.

    AddVertex takes the first truly-free slot and scrubs the slot's stale
    adjacency (a reused slot may carry a dead predecessor's edges);
    RemoveVertex marks the vertex and bumps the ``ecnt`` of every live
    in-edge source, read off ONE maintained in-adjacency row (DESIGN.md
    §11) — the paper's adversary argument needs those rows' versions to
    move; edge ops are the masked single-bit RMW on both mirrors plus the
    paper's FAA on the source row's ``ecnt``.
    """
    s1 = find_slot(state, k1)
    s2 = find_slot(state, k2)
    is_addv = opcode == OP_ADD_V
    is_remv = opcode == OP_REM_V
    is_adde = opcode == OP_ADD_E
    is_reme = opcode == OP_REM_E
    v = state.capacity

    new = _free_slot(state)
    exists = s1 >= 0
    do_av = is_addv & ~exists & (new >= 0)
    r_addv = jnp.where(exists, R_FALSE,
                       jnp.where(new < 0, R_TABLE_FULL, R_TRUE))
    do_rv = is_remv & exists
    r_remv = jnp.where(exists, R_TRUE, R_FALSE)
    both = (s1 >= 0) & (s2 >= 0)
    rk, rl = jnp.maximum(s1, 0), jnp.maximum(s2, 0)
    cas_ok = (expect < 0) | (state.ecnt[rk] == expect)
    present = get_bit(state.adj_packed, rk, rl)
    do_add = is_adde & both & cas_ok & ~present
    do_rem = is_reme & both & cas_ok & present
    r_adde = jnp.where(both, jnp.where(cas_ok, jnp.where(
        present, R_EDGE_PRESENT, R_EDGE_ADDED), R_CAS_FAIL),
        R_VERTEX_NOT_PRESENT)
    r_reme = jnp.where(both, jnp.where(cas_ok, jnp.where(
        present, R_EDGE_REMOVED, R_EDGE_NOT_PRESENT), R_CAS_FAIL),
        R_VERTEX_NOT_PRESENT)
    r_cone = jnp.where(both, jnp.where(present, R_EDGE_PRESENT,
                                       R_EDGE_NOT_PRESENT),
                       R_VERTEX_NOT_PRESENT)
    r_conv = jnp.where(exists, R_TRUE, R_FALSE)

    # metadata writes ("drop" parks a masked-off write past the table)
    at = jnp.where(do_av, new, v)
    rt = jnp.where(do_rv, s1, v)
    et = jnp.where(do_add | do_rem, rk, v)
    vkey = state.vkey.at[at].set(k1, mode="drop")
    valive = state.valive.at[at].set(True, mode="drop")
    valive = valive.at[rt].set(False, mode="drop")
    vver = state.vver.at[at].add(1, mode="drop").at[rt].add(1, mode="drop")
    in_src = unpack_bits(state.adj_in_packed[jnp.maximum(s1, 0)], v) \
        & state.valive & do_rv
    ecnt = (state.ecnt.at[at].set(0, mode="drop").at[rt].add(1, mode="drop")
            .at[et].add(1, mode="drop") + in_src.astype(jnp.int32))

    # adjacency: the mirrored single-bit RMW of an edge op (DESIGN.md §11),
    # then the reused-slot scrub of an AddVertex, run as a 0/1-trip loop so
    # the column pass costs nothing on the lanes that do not allocate
    adj = _set_edge_bit(state.adj_packed, rk, rl, do_add, do_add | do_rem)
    adj_in = _set_edge_bit(state.adj_in_packed, rl, rk, do_add,
                           do_add | do_rem)
    tgt = jnp.maximum(new, 0)
    adj, adj_in = jax.lax.fori_loop(
        0, do_av.astype(jnp.int32),
        lambda _, m: (_clear_row_col(m[0], tgt), _clear_row_col(m[1], tgt)),
        (adj, adj_in))

    res = jnp.select(
        [opcode == OP_ADD_V, is_remv, opcode == OP_CON_V, is_adde, is_reme,
         opcode == OP_CON_E],
        [r_addv, r_remv, r_conv, r_adde, r_reme, r_cone], R_FALSE)
    return (GraphState(vkey, valive, vver, ecnt, adj, adj_in),
            res.astype(jnp.int32))


# ----------------------------------------------------------------------------
# Reference engine: exact lane-order linearization
# ----------------------------------------------------------------------------
def _serial_masked(state: GraphState, ops: OpBatch, mask: jax.Array,
                   res0: jax.Array):
    """Apply the ``mask``-selected lanes in lane order via ``_apply_one``.

    Unselected lanes keep their ``res0`` entry. This is both the reference
    engine (mask = all lanes) and the fast engine's correction pass
    (mask = conflicting lanes). The loop visits the selected lanes only,
    so its trip count is theirs, not the batch's.
    """
    lanes = jnp.nonzero(mask, size=ops.lanes, fill_value=0)[0]

    def body(j, carry):
        st, res = carry
        i = lanes[j]
        st, r = _apply_one(st, ops.opcode[i], ops.key1[i], ops.key2[i],
                           ops.expect[i])
        return st, res.at[i].set(r)

    n = jnp.sum(mask.astype(jnp.int32))
    return jax.lax.fori_loop(0, n, body, (state, res0))


@jax.jit
def apply_ops(state: GraphState, ops: OpBatch):
    """Apply a batch with exact lane-order linearization (reference engine)."""
    res0 = jnp.full((ops.lanes,), R_FALSE, jnp.int32)
    return _serial_masked(state, ops, jnp.ones((ops.lanes,), jnp.bool_), res0)


# ----------------------------------------------------------------------------
# Fast engine: disjoint-access parallelism
# ----------------------------------------------------------------------------
def _lane_conflicts(ops: OpBatch) -> jax.Array:
    """True for lanes that must take the serial correction pass.

    Key collisions are detected sort-based O(B log B): flatten the (up to)
    two keys per lane, sort, mark duplicates, scatter the mark back to
    lanes. Read-only lanes (contains) still count as conflicting when they
    share a key with a writer — conservative and simple (reads that conflict
    only with reads are still routed to the serial pass; rare in
    benchmarks). On top of key collisions, two lane classes are serial
    unconditionally (the bit-identity contract, module docstring):

      * RemoveVertex — its in-edge-source ecnt bumps depend on adjacency
        and liveness of vertices it shares no key with;
      * CAS edge lanes (expect >= 0) whenever the batch contains any
        RemoveVertex — the CAS reads its source row's ecnt, which an
        earlier RemoveVertex lane may bump through an in-edge without
        sharing a key (the only cross-key ecnt writer);
      * any lane naming a negative key — negative keys alias EMPTY_KEY
        slot-table sentinels, so only the exact reference semantics of
        ``_apply_one`` are trusted with them.
    """
    b = ops.lanes
    is_edge = (ops.opcode == OP_ADD_E) | (ops.opcode == OP_REM_E) | (ops.opcode == OP_CON_E)
    is_vert = (ops.opcode == OP_ADD_V) | (ops.opcode == OP_REM_V) | (ops.opcode == OP_CON_V)
    k1 = jnp.where(is_edge | is_vert, ops.key1, -1)
    k2 = jnp.where(is_edge, ops.key2, -1)
    keys = jnp.concatenate([k1, k2])  # [2B]
    lane = jnp.concatenate([jnp.arange(b), jnp.arange(b)])
    order = jnp.argsort(keys)
    sk, sl = keys[order], lane[order]
    same_prev = jnp.concatenate([jnp.array([False]), (sk[1:] == sk[:-1]) & (sk[1:] >= 0)])
    same_next = jnp.concatenate([(sk[:-1] == sk[1:]) & (sk[:-1] >= 0), jnp.array([False])])
    dup = same_prev | same_next
    conflict = jnp.zeros((b,), jnp.bool_)
    conflict = conflict.at[sl].max(dup)
    conflict = conflict | (ops.opcode == OP_REM_V)
    has_remv = jnp.any(ops.opcode == OP_REM_V)
    is_cas_edge = ((ops.opcode == OP_ADD_E) | (ops.opcode == OP_REM_E)) & (ops.expect >= 0)
    conflict = conflict | (is_cas_edge & has_remv)
    conflict = conflict | (is_vert & (ops.key1 < 0))
    conflict = conflict | (is_edge & ((ops.key1 < 0) | (ops.key2 < 0)))
    return conflict


def _alive_now(state: GraphState, keys: jax.Array) -> jax.Array:
    """Alive-slot existence per key [B], WITHOUT the key >= 0 guard (a
    degenerate negative key can name a live slot; `_find_slots_masked`
    deliberately hides those from scatter targets)."""
    hit = (state.vkey[None, :] == keys[:, None]) & state.valive[None, :]
    return jnp.any(hit, axis=1)


def _alloc_schedule(state: GraphState, ops: OpBatch):
    """Lane-order-faithful AddVertex allocation schedule (module docstring).

    Returns (wants bool[B], slot int32[B], overflow bool):
      wants[i]  — lane i is an AddVertex that allocates under lane-order
                  serial execution (key not alive at its turn);
      slot[i]   — the free slot it takes (capacity-parked when ~wants);
      overflow  — the schedule needs more slots than are free, so the caller
                  must fall back to the serial reference engine (capacity
                  exhaustion couples lanes across keys).
    """
    b = ops.lanes
    is_addv = ops.opcode == OP_ADD_V
    is_vmut = is_addv | (ops.opcode == OP_REM_V)
    alive0 = _alive_now(state, ops.key1)
    lane = jnp.arange(b, dtype=jnp.int32)
    prior = (
        (ops.key1[:, None] == ops.key1[None, :])
        & is_vmut[None, :]
        & (lane[None, :] < lane[:, None])
    )
    has_prior = jnp.any(prior, axis=1)
    last_j = jnp.argmax(jnp.where(prior, lane[None, :], -1), axis=1)
    # liveness after the last prior vertex-mutating lane on the same key:
    # AddVertex always leaves the key alive, RemoveVertex always dead —
    # regardless of whether that op itself reported success.
    alive_at_turn = jnp.where(has_prior, is_addv[last_j], alive0)
    wants = is_addv & ~alive_at_turn
    rank = jnp.cumsum(wants.astype(jnp.int32)) - 1              # 0-based rank
    free = state.vkey == EMPTY_KEY
    free_cum = jnp.cumsum(free.astype(jnp.int32))               # 1-based counts
    n_free = free_cum[-1]
    # slot for rank r = first index where free_cum == r+1 and free; serial
    # argmax-free consumes free slots in exactly this increasing order.
    slot = jnp.searchsorted(free_cum, rank + 1, side="left").astype(jnp.int32)
    slot = jnp.where(wants, slot, state.capacity)               # park inactive
    overflow = jnp.sum(wants.astype(jnp.int32)) > n_free
    return wants, slot, overflow


def _apply_clean_vectorized(state: GraphState, ops: OpBatch, active: jax.Array,
                            wants: jax.Array, slot: jax.Array):
    """One vectorized pass applying all ``active`` lanes.

    Preconditions: active lanes reference pairwise-disjoint key sets (so all
    scatters below are conflict-free and the pass equals any interleaving),
    RemoveVertex lanes are never active (always serial), and AddVertex
    allocation follows the precomputed non-overflowing ``_alloc_schedule``
    (so placement is bit-identical to the lane-order serial engine).
    """
    b = ops.lanes
    cap = state.capacity
    s1 = _find_slots_masked(state, ops.key1)
    s2 = _find_slots_masked(state, ops.key2)

    is_addv = active & (ops.opcode == OP_ADD_V)
    is_conv = active & (ops.opcode == OP_CON_V)
    is_adde = active & (ops.opcode == OP_ADD_E)
    is_reme = active & (ops.opcode == OP_REM_E)
    is_cone = active & (ops.opcode == OP_CON_E)

    res = jnp.full((b,), R_FALSE, jnp.int32)

    # --- AddVertex: scheduled free-slot allocation ---------------------------
    # A clean AddVertex has no other lane on its key, so the schedule's
    # alive-at-turn is simply alive-now and ``wants`` == "will allocate"
    # (the overflow fallback guarantees a slot exists).
    alloc = jnp.where(is_addv & wants, slot, cap)               # park inactive
    vkey = state.vkey.at[alloc].set(ops.key1, mode="drop")
    valive = state.valive.at[alloc].set(True, mode="drop")
    vver = state.vver.at[alloc].add(1, mode="drop")
    ecnt = state.ecnt.at[alloc].set(0, mode="drop")
    # stale-adjacency scrub on reused slots: rows by scatter, columns by ONE
    # packed AND-NOT mask (several lanes may land in the same word). The
    # scrub set is transpose-symmetric, so the in-adjacency takes the
    # identical row scatter + column mask (DESIGN.md §11).
    adj = state.adj_packed.at[alloc, :].set(jnp.uint32(0), mode="drop")
    adj_in = state.adj_in_packed.at[alloc, :].set(jnp.uint32(0), mode="drop")
    clear_cols = jnp.zeros((cap,), jnp.bool_).at[alloc].set(True, mode="drop")
    clear_mask = ~pack_bits(clear_cols)[None, :]
    adj = adj & clear_mask
    adj_in = adj_in & clear_mask
    res = jnp.where(is_addv, jnp.where(wants, R_TRUE, R_FALSE), res)

    # --- ContainsVertex -------------------------------------------------------
    res = jnp.where(is_conv, jnp.where(s1 >= 0, R_TRUE, R_FALSE), res)

    # --- Edge ops -------------------------------------------------------------
    both = (s1 >= 0) & (s2 >= 0)
    r1, r2 = jnp.maximum(s1, 0), jnp.maximum(s2, 0)
    cur = get_bit(state.adj_packed, r1, r2)
    cas_ok = (ops.expect < 0) | (state.ecnt[r1] == ops.expect)

    do_add = is_adde & both & cas_ok & ~cur
    do_rem = is_reme & both & cas_ok & cur
    # masked bit set/clear: clean lanes own pairwise-distinct source rows, so
    # the word read-modify-writes below are scatter-conflict-free (the word is
    # re-read AFTER the AddVertex scrub so unrelated bits survive)
    fire = do_add | do_rem
    tgt_r = jnp.where(fire, r1, cap)
    wcol, mbit = bit_word(r2), bit_mask(r2)
    curw = adj[jnp.minimum(tgt_r, cap - 1), wcol]
    neww = jnp.where(do_add, curw | mbit, curw & ~mbit)
    adj = adj.at[tgt_r, wcol].set(neww, mode="drop")
    # mirrored in-adjacency RMW: firing clean lanes own pairwise-distinct
    # DESTINATION rows too (disjoint key sets), so the in-row word
    # read-modify-writes are just as conflict-free (DESIGN.md §11)
    tgt_ri = jnp.where(fire, r2, cap)
    wcol_i, mbit_i = bit_word(r1), bit_mask(r1)
    curw_i = adj_in[jnp.minimum(tgt_ri, cap - 1), wcol_i]
    neww_i = jnp.where(do_add, curw_i | mbit_i, curw_i & ~mbit_i)
    adj_in = adj_in.at[tgt_ri, wcol_i].set(neww_i, mode="drop")
    ecnt = ecnt.at[tgt_r].add(1, mode="drop")

    res = jnp.where(
        is_adde,
        jnp.where(both, jnp.where(cas_ok, jnp.where(cur, R_EDGE_PRESENT, R_EDGE_ADDED), R_CAS_FAIL), R_VERTEX_NOT_PRESENT),
        res,
    )
    res = jnp.where(
        is_reme,
        jnp.where(both, jnp.where(cas_ok, jnp.where(cur, R_EDGE_REMOVED, R_EDGE_NOT_PRESENT), R_CAS_FAIL), R_VERTEX_NOT_PRESENT),
        res,
    )
    res = jnp.where(
        is_cone,
        jnp.where(both, jnp.where(cur, R_EDGE_PRESENT, R_EDGE_NOT_PRESENT), R_VERTEX_NOT_PRESENT),
        res,
    )
    return GraphState(vkey, valive, vver, ecnt, adj, adj_in), res


def _find_slots_masked(state: GraphState, keys: jax.Array) -> jax.Array:
    hit = (state.vkey[None, :] == keys[:, None]) & state.valive[None, :] & (keys[:, None] >= 0)
    idx = jnp.argmax(hit, axis=1)
    return jnp.where(jnp.any(hit, axis=1), idx.astype(jnp.int32), jnp.int32(-1))


@jax.jit
def apply_ops_fast(state: GraphState, ops: OpBatch):
    """Disjoint-access-parallel batch application (linearizable; see module doc).

    Linearization order: all conflict-free lanes (which commute with every
    lane) at the batch start in lane order, then conflicting lanes in lane
    order via the masked correction loop. Bit-identical to ``apply_ops``
    (module docstring; tests/test_linearizability_prop.py).
    """
    conflict = _lane_conflicts(ops)
    clean = ~conflict & (ops.opcode != OP_NOP)
    wants, slot, overflow = _alloc_schedule(state, ops)
    res0 = jnp.full((ops.lanes,), R_FALSE, jnp.int32)

    def fallback(st):
        # Allocation would exhaust the slot table: capacity failures couple
        # lanes across keys, so only full serial replay is bit-exact.
        return _serial_masked(st, ops, jnp.ones((ops.lanes,), jnp.bool_), res0)

    def fast(st):
        st, res = _apply_clean_vectorized(st, ops, clean, wants, slot)
        return jax.lax.cond(
            jnp.any(conflict),
            lambda a: _serial_masked(a[0], ops, conflict, a[1]),
            lambda a: a,
            (st, res),
        )

    return jax.lax.cond(overflow, fallback, fast, state)


# ----------------------------------------------------------------------------
# Undirected extension (paper footnote a: "directly extended")
# ----------------------------------------------------------------------------
def _edge_op_undirected(state: GraphState, k, l, expect, *, add: bool):
    """Both directions mutate atomically at one linearization point; both
    endpoint rows take the FAA (so double collects through either endpoint
    observe the mutation)."""
    sk = find_slot(state, k)
    sl = find_slot(state, l)
    both = (sk >= 0) & (sl >= 0)
    rk, rl = jnp.maximum(sk, 0), jnp.maximum(sl, 0)
    cas_ok = (expect < 0) | (state.ecnt[rk] == expect)
    present = get_bit(state.adj_packed, rk, rl)
    if add:
        do = both & cas_ok & ~present
        ok_res = jnp.where(present, R_EDGE_PRESENT, R_EDGE_ADDED)
    else:
        do = both & cas_ok & present
        ok_res = jnp.where(present, R_EDGE_REMOVED, R_EDGE_NOT_PRESENT)
    adj = _set_edge_bit(state.adj_packed, rk, rl, jnp.asarray(add), do)
    adj = _set_edge_bit(adj, rl, rk, jnp.asarray(add), do)
    # an undirected edge is its own transpose: the in-adjacency takes the
    # same symmetric pair of bit writes (DESIGN.md §11)
    adj_in = _set_edge_bit(state.adj_in_packed, rl, rk, jnp.asarray(add), do)
    adj_in = _set_edge_bit(adj_in, rk, rl, jnp.asarray(add), do)
    ecnt = state.ecnt.at[rk].add(jnp.where(do, 1, 0))
    ecnt = ecnt.at[rl].add(jnp.where(do & (rk != rl), 1, 0))
    res = jnp.where(
        both,
        jnp.where(cas_ok, ok_res, R_CAS_FAIL),
        R_VERTEX_NOT_PRESENT,
    )
    return GraphState(state.vkey, state.valive, state.vver, ecnt, adj,
                      adj_in), res.astype(jnp.int32)


@jax.jit
def add_edge_undirected(state: GraphState, k, l):
    return _edge_op_undirected(state, jnp.asarray(k, jnp.int32),
                               jnp.asarray(l, jnp.int32), jnp.int32(-1), add=True)


@jax.jit
def remove_edge_undirected(state: GraphState, k, l):
    return _edge_op_undirected(state, jnp.asarray(k, jnp.int32),
                               jnp.asarray(l, jnp.int32), jnp.int32(-1), add=False)


# ----------------------------------------------------------------------------
# Wait-free neighborhood queries (the traversal-return the paper's related
# work, Kallimanis & Kanellou 2015, could not provide)
# ----------------------------------------------------------------------------
@jax.jit
def neighbors(state: GraphState, k):
    """Out-neighbor keys of v(k): (count, keys int32[V] padded with -1).

    Single bounded vectorized pass over the slot table — wait-free in the
    same sense as ContainsVertex (paper Thm 4.2(i))."""
    slot = find_slot(state, jnp.asarray(k, jnp.int32))
    ok = slot >= 0
    row = unpack_bits(state.adj_packed[jnp.maximum(slot, 0)], state.capacity)
    live = row & state.valive & ok
    n = jnp.sum(live.astype(jnp.int32))
    order = jnp.argsort(~live)  # live slots first (stable)
    keys = jnp.where(live[order], state.vkey[order], -1)
    return n, keys


@jax.jit
def degree(state: GraphState, k):
    """(out_degree, in_degree) of v(k); (-1, -1) if absent. BOTH degrees are
    one popcount over the slot's traversable row words — out over
    ``adj_packed``, in over the maintained ``adj_in_packed`` row
    (DESIGN.md §10, §11) — no strided column gather."""
    slot = find_slot(state, jnp.asarray(k, jnp.int32))
    ok = slot >= 0
    s = jnp.maximum(slot, 0)
    out_d = jnp.sum(popcount(state.adj_packed[s] & state.alive_words))
    in_d = jnp.where(
        state.valive[s],
        jnp.sum(popcount(state.adj_in_packed[s] & state.alive_words)), 0)
    return (jnp.where(ok, out_d, -1), jnp.where(ok, in_d, -1))


# ----------------------------------------------------------------------------
# Physical removal — the helping / compaction analogue
# ----------------------------------------------------------------------------
@jax.jit
def compact(state: GraphState) -> GraphState:
    """Physically remove logically-deleted vertices (paper: the deferred
    physical unlink any helping thread may perform). Frees slots and clears
    their adjacency rows/columns; versions are retained so outstanding
    double-collects still detect the change (vver moved at logical removal).
    """
    dead = (~state.valive) & (state.vkey != EMPTY_KEY)
    keep = ~dead
    vkey = jnp.where(dead, EMPTY_KEY, state.vkey)
    keep_words = pack_bits(keep)[None, :]
    # the scrub (dead rows zeroed, dead columns masked) is transpose-
    # symmetric: the in-adjacency takes the identical form (DESIGN.md §11)
    adj = jnp.where(keep[:, None],
                    state.adj_packed & keep_words, jnp.uint32(0))
    adj_in = jnp.where(keep[:, None],
                       state.adj_in_packed & keep_words, jnp.uint32(0))
    return GraphState(vkey, state.valive, state.vver, state.ecnt, adj, adj_in)


# ----------------------------------------------------------------------------
# Convenience single-op API (host-facing, used by examples/benchmarks)
# ----------------------------------------------------------------------------
def _single(state: GraphState, opcode: int, k, l=-1):
    return _apply_one(state, jnp.int32(opcode), jnp.asarray(k, jnp.int32),
                      jnp.asarray(l, jnp.int32), jnp.int32(-1))


@jax.jit
def add_vertex(state: GraphState, k):
    return _single(state, OP_ADD_V, k)


@jax.jit
def remove_vertex(state: GraphState, k):
    return _single(state, OP_REM_V, k)


@jax.jit
def add_edge(state: GraphState, k, l):
    return _single(state, OP_ADD_E, k, l)


@jax.jit
def remove_edge(state: GraphState, k, l):
    return _single(state, OP_REM_E, k, l)
