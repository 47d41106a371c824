"""Mesh-partitioned graph state — the scale-out form of both engines
(DESIGN.md §8).

``ShardedGraphState`` holds the same logical state as ``GraphState`` with a
split placement over a 1-D device mesh (axis ``"rows"``, shared with
core/distributed.py):

  * ``adj_packed`` and ``adj_in_packed`` — the only O(V^2/32) arrays
    (word-packed out-/in-adjacency, DESIGN.md §10, §11) — are
    ROW-SHARDED: every device owns V/S contiguous packed out-edge rows
    (the edge-lists of its vertices) AND the in-edge rows of the same
    slot block (= the out-adjacency's columns — the column-sharded
    in-row layout the hybrid pull phase runs shard-local over);
  * ``vkey``/``valive``/``vver``/``ecnt`` — the O(V) version metadata — are
    REPLICATED, so lookups (LocV/LocC), the double-collect validation
    vector, and the lane-order mutation schedule are shard-local replicated
    compute with zero communication.

The placement rules live in ``parallel.sharding.graph_state_specs``; the
inside-shard_map helpers (row-block arithmetic, ``_pvary``) are shared
with ``core.distributed``.

Engines (each bit-identical to its dense counterpart — the property suite
tests/test_linearizability_prop.py enforces it):

``apply_ops_fast``  distributed disjoint-access-parallel mutation: every
                    shard applies the conflict-free lanes whose source rows
                    it owns in one vectorized step, while the masked serial
                    correction pass runs on the replicated metadata with
                    only per-lane scalar exchanges (edge-presence pmax,
                    in-edge-bump all_gather) touching the wire. Lane-order
                    linearization survives sharding because every decision
                    (conflict mask, allocation schedule, result codes) is a
                    deterministic function of the replicated metadata —
                    shards can only disagree about adjacency bits, and those
                    are exchanged at the exact program points the dense
                    engine reads them (DESIGN.md §8).

``multi_bfs``       distributed fused multi-source BFS: each superstep does
                    a LOCAL [Q, V/S] @ [V/S, V] frontier-matrix product per
                    shard (``backend="pallas"`` reuses the bfs_multi_step
                    kernel on the shard's row slice) followed by ONE psum
                    frontier exchange + pmin parent combine. The packed
                    backends ("packed", "packed_pallas", DESIGN.md §10)
                    expand over the shard's packed WORDS and exchange the
                    partial next frontiers as packed uint32 bitsets —
                    [Q, V/32] words on the wire instead of [Q, V] int32, a
                    32x cut in frontier-exchange volume. Per-query early
                    exit and the double-collect version check carry over
                    unchanged because the validation vector is replicated.

``grow``/``compact`` preserve the sharding (grow re-rounds capacity up to a
                    multiple of the mesh axis so row blocks stay equal).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import graph as ggraph
from repro.core import ops as gops
from repro.core.bfs import (
    DEFAULT_ALPHA,
    DEFAULT_BETA,
    HYBRID_BACKENDS,
    PACKED_BACKENDS,
    MultiBFSResult,
    _resolve_backend,
    ctz32,
    pick_direction,
)
from repro.core.distributed import (
    AXIS,
    _pvary,
    _row_block_info,
    make_graph_mesh,
)
from repro.core.graph import (
    EMPTY_KEY,
    OP_ADD_E,
    OP_ADD_V,
    OP_CON_E,
    OP_CON_V,
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
    or_reduce,
    pack_bits,
    unpack_bits,
)
from repro.parallel.sharding import graph_state_shardings

INT32_MAX = jnp.int32(2**31 - 1)


@jax.tree_util.register_pytree_node_class
class ShardedGraphState:
    """Row-partitioned graph state (DESIGN.md §8).

    Same six logical fields as ``GraphState`` (duck-type compatible for
    lookups/version_vector/_materialize), plus the owning ``mesh`` carried
    as static pytree aux data so jitted engines can build shard_maps from
    the state alone. ``adj_in_packed`` shares ``adj_packed``'s row sharding:
    shard s owns the in-rows of ITS slot block — the column-sharded in-row
    layout the hybrid pull phase runs shard-local over (DESIGN.md §11).
    """

    def __init__(self, mesh, vkey, valive, vver, ecnt, adj_packed,
                 adj_in_packed):
        self.mesh = mesh
        self.vkey = vkey
        self.valive = valive
        self.vver = vver
        self.ecnt = ecnt
        self.adj_packed = adj_packed
        self.adj_in_packed = adj_in_packed

    def tree_flatten(self):
        return (self.vkey, self.valive, self.vver, self.ecnt,
                self.adj_packed, self.adj_in_packed), self.mesh

    @classmethod
    def tree_unflatten(cls, mesh, children):
        return cls(mesh, *children)

    @property
    def capacity(self) -> int:
        return self.vkey.shape[0]

    @property
    def num_shards(self) -> int:
        return int(self.mesh.shape[AXIS])

    def as_dense(self) -> GraphState:
        """View as a GraphState pytree (arrays keep their placement)."""
        return GraphState(self.vkey, self.valive, self.vver, self.ecnt,
                          self.adj_packed, self.adj_in_packed)

    @property
    def adj(self) -> jax.Array:
        """Dense uint8[V, V] adjacency view (unpacked on demand)."""
        return self.as_dense().adj

    def __repr__(self):  # pragma: no cover - debugging aid
        return (f"ShardedGraphState(capacity={self.capacity}, "
                f"shards={self.num_shards})")


# ----------------------------------------------------------------------------
# Placement / conversion
# ----------------------------------------------------------------------------
def shard_state(mesh, dense: GraphState) -> ShardedGraphState:
    """Place a dense GraphState onto the mesh (DESIGN.md §8 layout)."""
    size = int(mesh.shape[AXIS])
    if dense.capacity % size != 0:
        raise ValueError(
            f"capacity {dense.capacity} not divisible by mesh axis {size}")
    sh = graph_state_shardings(mesh, AXIS)
    return ShardedGraphState(
        mesh,
        jax.device_put(dense.vkey, sh["vkey"]),
        jax.device_put(dense.valive, sh["valive"]),
        jax.device_put(dense.vver, sh["vver"]),
        jax.device_put(dense.ecnt, sh["ecnt"]),
        jax.device_put(dense.adj_packed, sh["adj_packed"]),
        jax.device_put(dense.adj_in_packed, sh["adj_in_packed"]),
    )


def unshard(state: ShardedGraphState) -> GraphState:
    """Gather back to a fully-replicated dense GraphState (tests/host use)."""
    rep = NamedSharding(state.mesh, P())
    return GraphState(*(jax.device_put(x, rep) for x in state.as_dense()))


def grow(state: ShardedGraphState, new_capacity: int) -> ShardedGraphState:
    """Functionally grow capacity, preserving the sharding (DESIGN.md §8).

    Capacity is rounded up to a multiple of the mesh axis so row blocks stay
    equal-sized. Row blocks are redistributed (device k owns a different
    contiguous range after growth), so this is a gather + re-place — the
    same amortized O(V^2) a dense ``grow`` pays, plus one resharding.
    """
    size = int(state.mesh.shape[AXIS])
    new_capacity = -(-int(new_capacity) // size) * size
    if new_capacity <= state.capacity:
        return state
    return shard_state(state.mesh, ggraph.grow(unshard(state), new_capacity))


@jax.jit
def compact(state: ShardedGraphState) -> ShardedGraphState:
    """Physical removal of logically-deleted vertices, shard-local scrub.

    Mirrors ``ops.compact``: frees slots, clears their adjacency rows and
    columns. Each shard scrubs only its own row block; the keep mask is
    replicated metadata (DESIGN.md §8).
    """
    mesh = state.mesh
    v = state.capacity
    size = int(mesh.shape[AXIS])
    dead = (~state.valive) & (state.vkey != EMPTY_KEY)
    keep = ~dead
    vkey = jnp.where(dead, EMPTY_KEY, state.vkey)

    @functools.partial(
        shard_map, mesh=mesh, in_specs=(P(AXIS, None), P()),
        out_specs=P(AXIS, None), check_vma=False,
    )
    def scrub(adjw_l, keep_g):
        _, _, per, row0 = _row_block_info(v, size)
        keep_l = jax.lax.dynamic_slice(keep_g, (row0,), (per,))
        return jnp.where(keep_l[:, None],
                         adjw_l & pack_bits(keep_g)[None, :], jnp.uint32(0))

    # the scrub is transpose-symmetric (dead rows zeroed, dead column bits
    # masked), so the SAME shard-local pass compacts the column-sharded
    # in-rows (DESIGN.md §11)
    return ShardedGraphState(mesh, vkey, state.valive, state.vver,
                             state.ecnt, scrub(state.adj_packed, keep),
                             scrub(state.adj_in_packed, keep))


# ----------------------------------------------------------------------------
# Distributed mutation engine
# ----------------------------------------------------------------------------
def _find_one(vkey, valive, key):
    """find_slot on the replicated metadata (no GraphState wrapper)."""
    hit = (vkey == key) & valive
    idx = jnp.argmax(hit)
    return jnp.where(jnp.any(hit), idx.astype(jnp.int32), jnp.int32(-1))


@jax.jit
def apply_ops_fast(state: ShardedGraphState, ops: OpBatch):
    """Distributed disjoint-access-parallel batch application.

    Bit-identical to the dense ``ops.apply_ops_fast`` (hence to the
    sequential spec ``ops.apply_ops``): the conflict mask, the AddVertex
    allocation schedule and the overflow fallback are the SAME dense-helper
    computations run on the replicated metadata, so every shard takes the
    same decisions; only adjacency bits differ per shard and they are
    exchanged (edge-presence pmax, in-edge-bump all_gather) at the exact
    points the dense engine reads them. See DESIGN.md §8 for why lane-order
    linearization survives the partitioning.
    """
    mesh = state.mesh
    v = state.capacity
    b = ops.lanes
    size = int(mesh.shape[AXIS])

    meta = state.as_dense()  # replicated metadata view for the dense helpers
    conflict = gops._lane_conflicts(ops)
    wants, slot, overflow = gops._alloc_schedule(meta, ops)
    clean = ~conflict & (ops.opcode != gops.OP_NOP) & ~overflow
    serial = jnp.where(overflow, jnp.ones((b,), jnp.bool_), conflict)

    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(), P(), P(), P(), P(AXIS, None), P(AXIS, None),
                  P(), P(), P(), P(), P(), P(), P(), P()),
        out_specs=(P(), P(), P(), P(), P(AXIS, None), P(AXIS, None), P()),
        # Metadata outputs are value-replicated: every shard computes the
        # same result from replicated inputs + deterministic collectives.
        check_vma=False,
    )
    def run(vkey, valive, vver, ecnt, adj_l, adjin_l,
            opc, k1, k2, expect, cleanv, serialv, wantsv, slotv):
        _, _, per, row0 = _row_block_info(v, size)
        vkey0, valive0, ecnt0, adj0_l = vkey, valive, ecnt, adj_l

        # ------------------------------------------------------------------
        # Clean vectorized pass (mirror of ops._apply_clean_vectorized)
        # ------------------------------------------------------------------
        hit1 = (vkey0[None, :] == k1[:, None]) & valive0[None, :] & (k1[:, None] >= 0)
        hit2 = (vkey0[None, :] == k2[:, None]) & valive0[None, :] & (k2[:, None] >= 0)
        s1 = jnp.where(jnp.any(hit1, axis=1), jnp.argmax(hit1, axis=1).astype(jnp.int32), -1)
        s2 = jnp.where(jnp.any(hit2, axis=1), jnp.argmax(hit2, axis=1).astype(jnp.int32), -1)

        is_addv = cleanv & (opc == OP_ADD_V)
        is_conv = cleanv & (opc == OP_CON_V)
        is_adde = cleanv & (opc == OP_ADD_E)
        is_reme = cleanv & (opc == OP_REM_E)
        is_cone = cleanv & (opc == OP_CON_E)
        res = jnp.full((b,), R_FALSE, jnp.int32)

        # AddVertex via the precomputed schedule
        alloc = jnp.where(is_addv & wantsv, slotv, v)
        vkey = vkey.at[alloc].set(k1, mode="drop")
        valive = valive.at[alloc].set(True, mode="drop")
        vver = vver.at[alloc].add(1, mode="drop")
        ecnt = ecnt.at[alloc].set(0, mode="drop")
        lr = alloc - row0
        lr = jnp.where((lr >= 0) & (lr < per), lr, per)
        adj_l = adj_l.at[lr, :].set(jnp.uint32(0), mode="drop")
        # the scrub is transpose-symmetric: the shard's column-sharded
        # in-rows take the identical row scatter + column mask (§11)
        adjin_l = adjin_l.at[lr, :].set(jnp.uint32(0), mode="drop")
        # column-bit scrub: one packed AND-NOT mask over the local rows
        clear_cols = jnp.zeros((v,), jnp.bool_).at[alloc].set(True, mode="drop")
        clear_mask = ~pack_bits(clear_cols)[None, :]
        adj_l = adj_l & clear_mask
        adjin_l = adjin_l & clear_mask
        res = jnp.where(is_addv, jnp.where(wantsv, R_TRUE, R_FALSE), res)

        # ContainsVertex
        res = jnp.where(is_conv, jnp.where(s1 >= 0, R_TRUE, R_FALSE), res)

        # Edge ops: presence lives on the owner shard -> masked bit read + pmax
        both = (s1 >= 0) & (s2 >= 0)
        r1, r2 = jnp.maximum(s1, 0), jnp.maximum(s2, 0)
        l1 = r1 - row0
        mine1 = (l1 >= 0) & (l1 < per)
        cur_loc = (adj0_l[jnp.clip(l1, 0, per - 1), bit_word(r2)]
                   & bit_mask(r2)) > 0
        cur = jax.lax.pmax(
            jnp.where(mine1, cur_loc.astype(jnp.int32), 0), AXIS) > 0
        cas_ok = (expect < 0) | (ecnt0[r1] == expect)

        do_add = is_adde & both & cas_ok & ~cur
        do_rem = is_reme & both & cas_ok & cur
        # masked bit set/clear on the owner's word (clean lanes own
        # pairwise-distinct rows, so the word RMWs are conflict-free)
        el = jnp.where((do_add | do_rem) & mine1, l1, per)
        wc, mb = bit_word(r2), bit_mask(r2)
        curw = adj_l[jnp.clip(el, 0, per - 1), wc]
        neww = jnp.where(do_add, curw | mb, curw & ~mb)
        adj_l = adj_l.at[el, wc].set(neww, mode="drop")
        # mirrored in-row RMW on the DESTINATION owner's shard (§11):
        # clean lanes' key sets are disjoint, so destination rows are
        # pairwise-distinct too and the scatter stays conflict-free
        l2 = r2 - row0
        mine2 = (l2 >= 0) & (l2 < per)
        el2 = jnp.where((do_add | do_rem) & mine2, l2, per)
        wc2, mb2 = bit_word(r1), bit_mask(r1)
        curw2 = adjin_l[jnp.clip(el2, 0, per - 1), wc2]
        neww2 = jnp.where(do_add, curw2 | mb2, curw2 & ~mb2)
        adjin_l = adjin_l.at[el2, wc2].set(neww2, mode="drop")
        ecnt = ecnt.at[jnp.where(do_add | do_rem, r1, v)].add(1, mode="drop")

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

        # ------------------------------------------------------------------
        # Serial correction pass (mirror of ops._apply_one, lane order).
        # Runs every lane unconditionally (uniform collectives across
        # shards); non-serial lanes are masked out of all writes.
        # ------------------------------------------------------------------
        def body(i, carry):
            vkey, valive, vver, ecnt, adj_l, adjin_l, res = carry
            m = serialv[i]
            op, a, bk, exp = opc[i], k1[i], k2[i], expect[i]
            sa = _find_one(vkey, valive, a)
            sb = _find_one(vkey, valive, bk)

            # AddVertex
            free = vkey == EMPTY_KEY
            have = jnp.any(free)
            new = jnp.argmax(free).astype(jnp.int32)
            exists = sa >= 0
            do_av = m & (op == OP_ADD_V) & ~exists & have
            tgt = jnp.where(do_av, new, v)
            vkey = vkey.at[tgt].set(a, mode="drop")
            valive = valive.at[tgt].set(True, mode="drop")
            vver = vver.at[tgt].add(1, mode="drop")
            ecnt = ecnt.at[tgt].set(0, mode="drop")
            ltgt = tgt - row0
            ltgt = jnp.where((ltgt >= 0) & (ltgt < per), ltgt, per)
            adj_l = adj_l.at[ltgt, :].set(jnp.uint32(0), mode="drop")
            adjin_l = adjin_l.at[ltgt, :].set(jnp.uint32(0), mode="drop")
            # column-bit scrub (transpose-symmetric, so the in-rows take the
            # identical mask, §11). A strided pass over every local row: it
            # runs as a 0/1-trip loop, so only an allocating lane pays it
            # (do_av is replicated, so every shard takes the same trip count)
            tw, tm = bit_word(tgt), bit_mask(tgt)
            adj_l, adjin_l = jax.lax.fori_loop(
                0, do_av.astype(jnp.int32),
                lambda _, mats: tuple(x.at[:, tw].set(x[:, tw] & ~tm)
                                      for x in mats),
                (adj_l, adjin_l))
            r_addv = jnp.where(exists, R_FALSE, jnp.where(have, R_TRUE, R_TABLE_FULL))

            # RemoveVertex (in-edge-source bumps read the pre-lane liveness)
            valive_in = valive
            do_rv = m & (op == OP_REM_V) & (sa >= 0)
            t = jnp.where(do_rv, sa, v)
            valive = valive.at[t].set(False, mode="drop")
            vver = vver.at[t].add(1, mode="drop")
            ecnt = ecnt.at[t].add(1, mode="drop")
            col = jnp.maximum(sa, 0)
            valive_l = jax.lax.dynamic_slice(valive_in, (row0,), (per,))

            def bump_in_sources(_, e):
                # a strided column pass + all_gather: a 0/1-trip loop, so
                # only a removing lane pays it (do_rv is replicated)
                bump_l = ((adj_l[:, bit_word(col)] & bit_mask(col)) > 0) \
                    & valive_l
                bump = jax.lax.all_gather(bump_l, AXIS, tiled=True)
                return e + bump.astype(jnp.int32)

            ecnt = jax.lax.fori_loop(0, do_rv.astype(jnp.int32),
                                     bump_in_sources, ecnt)
            r_remv = jnp.where(sa >= 0, R_TRUE, R_FALSE)

            # ContainsVertex
            r_conv = jnp.where(sa >= 0, R_TRUE, R_FALSE)

            # Edge ops
            eboth = (sa >= 0) & (sb >= 0)
            ra, rb = jnp.maximum(sa, 0), jnp.maximum(sb, 0)
            la = ra - row0
            amine = (la >= 0) & (la < per)
            cur = jax.lax.pmax(
                jnp.where(amine,
                          ((adj_l[jnp.clip(la, 0, per - 1), bit_word(rb)]
                            & bit_mask(rb)) > 0).astype(jnp.int32), 0),
                AXIS) > 0
            ecas = (exp < 0) | (ecnt[ra] == exp)
            do_ea = m & (op == OP_ADD_E) & eboth & ecas & ~cur
            do_er = m & (op == OP_REM_E) & eboth & ecas & cur
            ela = jnp.where((do_ea | do_er) & amine, la, per)
            ecurw = adj_l[jnp.clip(ela, 0, per - 1), bit_word(rb)]
            enew = jnp.where(do_ea, ecurw | bit_mask(rb), ecurw & ~bit_mask(rb))
            adj_l = adj_l.at[ela, bit_word(rb)].set(enew, mode="drop")
            # mirrored in-row RMW on the destination owner's shard (§11)
            lb = rb - row0
            bmine = (lb >= 0) & (lb < per)
            elb = jnp.where((do_ea | do_er) & bmine, lb, per)
            ecurw_in = adjin_l[jnp.clip(elb, 0, per - 1), bit_word(ra)]
            enew_in = jnp.where(do_ea, ecurw_in | bit_mask(ra),
                                ecurw_in & ~bit_mask(ra))
            adjin_l = adjin_l.at[elb, bit_word(ra)].set(enew_in, mode="drop")
            ecnt = ecnt.at[jnp.where(do_ea | do_er, ra, v)].add(1, mode="drop")
            r_adde = jnp.where(eboth, jnp.where(ecas, jnp.where(cur, R_EDGE_PRESENT, R_EDGE_ADDED), R_CAS_FAIL), R_VERTEX_NOT_PRESENT)
            r_reme = jnp.where(eboth, jnp.where(ecas, jnp.where(cur, R_EDGE_REMOVED, R_EDGE_NOT_PRESENT), R_CAS_FAIL), R_VERTEX_NOT_PRESENT)
            r_cone = jnp.where(eboth, jnp.where(cur, R_EDGE_PRESENT, R_EDGE_NOT_PRESENT), R_VERTEX_NOT_PRESENT)

            r = jax.lax.switch(
                jnp.clip(op, 0, 6),
                [lambda: jnp.int32(R_FALSE),
                 lambda: r_addv.astype(jnp.int32),
                 lambda: r_remv.astype(jnp.int32),
                 lambda: r_conv.astype(jnp.int32),
                 lambda: r_adde.astype(jnp.int32),
                 lambda: r_reme.astype(jnp.int32),
                 lambda: r_cone.astype(jnp.int32)],
            )
            res = res.at[i].set(jnp.where(m, r, res[i]))
            return vkey, valive, vver, ecnt, adj_l, adjin_l, res

        vkey, valive, vver, ecnt, adj_l, adjin_l, res = jax.lax.fori_loop(
            0, b, body, (vkey, valive, vver, ecnt, adj_l, adjin_l, res))
        return vkey, valive, vver, ecnt, adj_l, adjin_l, res

    vkey, valive, vver, ecnt, adj, adj_in, res = run(
        state.vkey, state.valive, state.vver, state.ecnt, state.adj_packed,
        state.adj_in_packed,
        ops.opcode, ops.key1, ops.key2, ops.expect,
        clean, serial, wants, slot,
    )
    return ShardedGraphState(mesh, vkey, valive, vver, ecnt, adj,
                             adj_in), res


# ----------------------------------------------------------------------------
# Distributed fused multi-source BFS
# ----------------------------------------------------------------------------
def multi_bfs(state: ShardedGraphState, src_slots, dst_slots,
              backend: str | None = None, alpha: int = DEFAULT_ALPHA,
              beta: int = DEFAULT_BETA) -> MultiBFSResult:
    """Fused BFS from Q sources over the row-sharded adjacency.

    Each superstep: every shard expands the slice of all Q frontiers it owns
    with ONE local [Q, V/S] @ [V/S, V] product (``backend="pallas"`` runs
    the bfs_multi_step kernel on the row slice), then the partial next
    frontiers are OR-combined with a single psum and parents min-combined
    with a pmin — the row-partitioned frontier exchange of DESIGN.md §8.
    Per-query early exit is the dense engine's: finished queries expose an
    all-empty frontier on every shard. Results are bit-identical to
    ``core.bfs.multi_bfs`` on the gathered state.

    The hybrid backends (DESIGN.md §11) add the direction-optimizing
    superstep: the push phase is the packed local expansion above; the pull
    phase runs SHARD-LOCAL over the column-sharded in-rows — each shard
    scans only the in-adjacency rows of the V/S destinations it owns
    against the replicated packed frontier bitsets, producing a disjoint
    [Q, V/S] partial. Either phase feeds the SAME packed uint32 frontier
    exchange (all_gather + OR-fold) and pmin parent combine, so the
    direction switch (replicated popcounts → identical on every shard,
    chosen inside the superstep with no collective in either branch) never
    changes the communication pattern. ``backend=None`` resolves via
    ``core.bfs.default_backend()`` HERE, outside the jit boundary, so the
    resolved name (not None) is the static cache key and a changed
    ``REPRO_BFS_BACKEND`` takes effect on the next call.

    Under tracing (DESIGN.md §14) the call is wrapped in one
    ``bfs.session.sharded`` span recording supersteps and the estimated
    packed frontier-exchange volume (the per-superstep psum/all_gather of
    [Q, ceil(V/32)] uint32 words across all shards). The while_loop stays
    inside shard_map, so there are no per-superstep child spans here —
    superstep-level attribution is the dense engine's traced path.
    """
    backend = _resolve_backend(backend)
    from repro.obs import trace as _trace
    if _trace.enabled() and not isinstance(state.valive, jax.core.Tracer):
        from repro.obs.metrics import global_registry as _obs_registry

        q = int(jnp.asarray(src_slots).shape[0])
        v = int(state.capacity)
        size = int(state.mesh.shape[AXIS])
        with _trace.span("bfs.session.sharded", queries=q, capacity=v,
                         shards=size, backend=backend) as sp:
            res = _multi_bfs_jit(state, src_slots, dst_slots,
                                 backend=backend, alpha=alpha, beta=beta)
            _trace.fence(res)
            steps = int(res.supersteps)
            words = (v + 31) // 32
            xbytes = steps * q * words * 4 * size
            sp.set(supersteps=steps, exchange_bytes=xbytes)
            reg = _obs_registry()
            reg.inc("bfs.supersteps", steps)
            reg.inc("bfs.exchange_bytes", xbytes)
        return res
    return _multi_bfs_jit(state, src_slots, dst_slots,
                          backend=backend, alpha=alpha,
                          beta=beta)


@functools.partial(jax.jit, static_argnames=("backend", "alpha", "beta"))
def _multi_bfs_jit(state: ShardedGraphState, src_slots, dst_slots,
                   backend: str, alpha: int,
                   beta: int) -> MultiBFSResult:
    mesh = state.mesh
    v = state.capacity
    size = int(mesh.shape[AXIS])
    src_slots = jnp.asarray(src_slots, jnp.int32)
    dst_slots = jnp.asarray(dst_slots, jnp.int32)
    q = src_slots.shape[0]

    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(), P(AXIS, None), P(AXIS, None), P(), P()),
        out_specs=(P(), P(), P(), P(), P(), P()),
        # Outputs are value-replicated: combined via psum/pmin every
        # superstep.
        check_vma=False,
    )
    def run(alive, adjw_l, adjw_in_l, srcs, dsts):
        _, _, per, row0 = _row_block_info(v, size)
        hybrid = backend in HYBRID_BACKENDS
        packed = backend in PACKED_BACKENDS or hybrid
        alive_l = jax.lax.dynamic_slice(alive, (row0,), (per,))
        # the jnp-level edge views derive from the ONE traversable
        # predicate (row-slice form, DESIGN.md §10) — the Pallas branches
        # stream raw tiles and apply the same mask in their epilogue, per
        # the kernel contract. Loop-invariant, so hoisted out of the body.
        t_l = tw_l = None
        if backend in ("packed", "hybrid"):
            tw_l = ggraph.traversable_packed(adjw_l, alive_l,
                                             pack_bits(alive))
            # parent candidates still need per-bit rows, unpacked ONCE
            t_l = unpack_bits(tw_l, v)
        elif backend == "jnp":
            t_l = ggraph.traversable(unpack_bits(adjw_l, v), alive_l, alive)
        elif backend == "pallas":
            adj_l = unpack_bits(adjw_l, v).astype(jnp.uint8)
        src_ok = (srcs >= 0) & alive[jnp.maximum(srcs, 0)]
        s = jnp.maximum(srcs, 0)
        frontier0 = jnp.zeros((q, v), jnp.bool_).at[jnp.arange(q), s].set(src_ok)
        visited0 = frontier0
        parent0 = jnp.full((q, v), -1, jnp.int32)
        dist0 = jnp.where(frontier0, 0, -1).astype(jnp.int32)
        expanded0 = jnp.zeros((q, v), jnp.bool_)
        steps0 = jnp.zeros((q,), jnp.int32)
        frontier0, visited0, parent0, dist0, expanded0, steps0 = jax.tree.map(
            _pvary, (frontier0, visited0, parent0, dist0, expanded0, steps0))

        def _active(frontiers, visited, step):
            hit = (dsts >= 0) & visited[jnp.arange(q), jnp.maximum(dsts, 0)]
            return jnp.any(frontiers, axis=1) & ~hit & (step < v)

        def cond(c):
            frontiers, visited = c[:2]
            step = c[6]
            return jnp.any(_active(frontiers, visited, step))

        def _push_local(f, f_l, visited):
            """Local top-down partial: (reach_part [Q, V], cand [Q, V])."""
            if backend == "pallas":
                from repro.kernels.bfs_multi_step.ops import multi_bfs_step

                new_p, par_p = multi_bfs_step(f_l, adj_l, alive, visited)
                return new_p, jnp.where(par_p >= 0, par_p + row0, INT32_MAX)
            if backend in ("packed_pallas", "hybrid_pallas"):
                from repro.kernels.bfs_multi_step.ops import multi_bfs_step_packed

                new_p, par_p = multi_bfs_step_packed(f_l, adjw_l, alive,
                                                     visited)
                return new_p, jnp.where(par_p >= 0, par_p + row0, INT32_MAX)
            if backend in ("packed", "hybrid"):
                sel = jnp.where(f_l[:, :, None], tw_l[None, :, :],
                                jnp.uint32(0))
                reach_part = unpack_bits(or_reduce(sel, 1), v)
            else:
                reach_part = (f_l.astype(jnp.float32)
                              @ t_l.astype(jnp.float32)) > 0
            idx = (jnp.arange(per, dtype=jnp.int32) + row0)[:, None, None]
            cand3 = jnp.where(f_l.T[:, :, None] & t_l[:, None, :],
                              idx, INT32_MAX)
            return reach_part, jnp.min(cand3, axis=0)

        def _pull_local(f, visited):
            """Local bottom-up partial over the shard's in-rows (§11):
            disjoint [Q, V/S] destination slices embedded into [Q, V]."""
            visited_l = jax.lax.dynamic_slice(visited, (0, row0), (q, per))
            fw = pack_bits(f & alive[None, :])
            if backend == "hybrid_pallas":
                from repro.kernels.bfs_pull_step.ops import (
                    multi_bfs_pull_step_rows,
                )

                new_l, par_l = multi_bfs_pull_step_rows(
                    fw, adjw_in_l, alive_l, visited_l)
                pmin_l = jnp.where(new_l, par_l, INT32_MAX)
            else:
                cand_w = adjw_in_l[None, :, :] & fw[:, None, :]  # [Q,per,W]
                hit_l = jnp.any(cand_w != 0, axis=2)
                new_l = hit_l & alive_l[None, :] & ~visited_l
                widx = (jnp.arange(adjw_in_l.shape[1], dtype=jnp.int32)
                        * ggraph.WORD_BITS)[None, None, :]
                pc = jnp.where(cand_w != 0, widx + ctz32(cand_w), INT32_MAX)
                pmin_l = jnp.where(new_l, jnp.min(pc, axis=2), INT32_MAX)
            reach_part = jax.lax.dynamic_update_slice(
                jnp.zeros((q, v), jnp.bool_), new_l, (0, row0))
            cand = jax.lax.dynamic_update_slice(
                jnp.full((q, v), INT32_MAX, jnp.int32), pmin_l, (0, row0))
            return reach_part, cand

        def body(c):
            frontiers, visited, parent, dist, expanded, steps, step = c[:7]
            act = _active(frontiers, visited, step)
            f = frontiers & act[:, None]
            expanded = expanded | f
            f_l = jax.lax.dynamic_slice(f, (0, row0), (q, per))
            if hybrid:
                # replicated popcounts → identical decision on every shard;
                # both cond branches are collective-free, the exchange
                # below is shared (§11)
                nf = jnp.sum(f.astype(jnp.int32))
                nu = jnp.sum(((alive[None, :] & ~visited)
                              & act[:, None]).astype(jnp.int32))
                pulling = pick_direction(c[7], nf, nu, q * v, alpha, beta)
                reach_part, cand = jax.lax.cond(
                    pulling,
                    lambda ff, ff_l, vis: _pull_local(ff, vis),
                    _push_local,
                    f, f_l, visited)
            else:
                reach_part, cand = _push_local(f, f_l, visited)
            if packed:
                # the DESIGN.md §10 frontier exchange: the partial next
                # frontiers cross the wire as packed uint32 bitsets
                # ([Q, V/32] words, 32x less than the int32 psum), OR-folded
                # after ONE all_gather
                parts = jax.lax.all_gather(pack_bits(reach_part), AXIS)
                reach = unpack_bits(or_reduce(parts, 0), v)
            else:
                reach = jax.lax.psum(reach_part.astype(jnp.int32), AXIS) > 0
            par_min = jax.lax.pmin(cand, AXIS)
            new = reach & alive[None, :] & ~visited
            parent = jnp.where(new, par_min, parent)
            dist = jnp.where(new, step + 1, dist)
            visited = visited | new
            steps = steps + act.astype(jnp.int32)
            out = (new, visited, parent, dist, expanded, steps, step + 1)
            return out + (pulling,) if hybrid else out

        init = (frontier0, visited0, parent0, dist0, expanded0, steps0,
                jnp.int32(0))
        if hybrid:
            init = init + (_pvary(jnp.asarray(False)),)
        final = jax.lax.while_loop(cond, body, init)
        frontiers, visited, parent, dist, expanded, steps, supersteps = \
            final[:7]
        found = ((dsts >= 0)
                 & visited[jnp.arange(q), jnp.maximum(dsts, 0)] & src_ok)
        return found, parent, dist, expanded, steps, supersteps

    found, parent, dist, expanded, steps, supersteps = run(
        state.valive, state.adj_packed, state.adj_in_packed,
        src_slots, dst_slots)
    return MultiBFSResult(found, parent, dist, expanded, steps, supersteps)


__all__ = [
    "ShardedGraphState",
    "apply_ops_fast",
    "compact",
    "grow",
    "make_graph_mesh",
    "multi_bfs",
    "shard_state",
    "unshard",
]
