"""Row-sharded distributed graph: the paper's algorithm at cluster scale.

The vertex slot table and adjacency rows are sharded over a 1-D device mesh
axis (``rows``). Every device owns V/S contiguous slots (their adjacency rows,
keys, versions). The paper's operations map onto bulk-synchronous collectives:

  * lookup (LocV/LocC)    : local masked match + psum        (1 scalar AR)
  * edge/vertex mutation  : routed to the owner shard; owners apply locally
                            without coordination (disjoint-access parallelism
                            across the cluster = the lock-free property)
  * BFS superstep         : local tile mat-vec over owned rows + psum-OR of
                            the partial next frontier (+ min-combine parents)
  * double collect        : local (ecnt, vver) snapshots; validation is a
                            psum of mismatch counts — ONE scalar collective
                            per collect pair, so queries stay cheap relative
                            to traversal exactly as in the paper

Vertex placement: owner(key) = hash(key) mod S; each owner allocates from its
own slot range, so AddVertex never needs cross-shard coordination either.

This module is mesh-size agnostic: with one device it degenerates to the
single-pod engine (used by unit tests); tests/test_distributed.py re-runs the
suite under ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` in a
subprocess to exercise real sharding, and launch/dryrun.py lowers the same
code for the 256/512-chip production meshes.

This file keeps the fully-row-sharded engines (metadata AND adjacency
partitioned; owner-routed mutation). The production scale-out path is
``core.partition`` (DESIGN.md §8): adjacency rows sharded, version metadata
replicated, engines bit-identical to the dense ones. partition.py shares
this module's mesh axis (``AXIS``), row-block arithmetic
(``_row_block_info``) and the ``_pvary`` helper.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.graph import (
    EMPTY_KEY,
    GraphState,
    OpBatch,
    pack_bits,
    pack_transpose,
    traversable,
    unpack_bits,
)
from repro.core import ops as gops

AXIS = "rows"


def make_graph_mesh(devices=None) -> Mesh:
    devices = devices if devices is not None else jax.devices()
    import numpy as np

    return Mesh(np.asarray(devices), (AXIS,))


def shard_graph(mesh: Mesh, state: GraphState) -> GraphState:
    """Place a GraphState with rows sharded over the mesh axis."""
    row = NamedSharding(mesh, P(AXIS))
    mat = NamedSharding(mesh, P(AXIS, None))
    return GraphState(
        vkey=jax.device_put(state.vkey, row),
        valive=jax.device_put(state.valive, row),
        vver=jax.device_put(state.vver, row),
        ecnt=jax.device_put(state.ecnt, row),
        adj_packed=jax.device_put(state.adj_packed, mat),
        adj_in_packed=jax.device_put(state.adj_in_packed, mat),
    )


# ----------------------------------------------------------------------------
# Inside-shard_map helpers (operate on the LOCAL block; axis name in scope)
# ----------------------------------------------------------------------------
def _global_find(vkey_l, valive_l, keys, row0):
    """Global slot ids [B] for keys (replicated), -1 if absent anywhere."""
    hit = (vkey_l[None, :] == keys[:, None]) & valive_l[None, :] & (keys[:, None] >= 0)
    loc = jnp.argmax(hit, axis=1).astype(jnp.int32)
    has = jnp.any(hit, axis=1)
    mine = jnp.where(has, loc + row0, -1)
    return jax.lax.pmax(mine, AXIS)


def _pvary(x):
    """Mark a shard-replicated value as device-varying (no-op if it already
    is), so a loop carry that starts replicated can hold per-shard values."""
    if AXIS in jax.typeof(x).vma:
        return x
    return jax.lax.pcast(x, AXIS, to="varying")


def _row_block_info(nrows_total, size):
    """(shard id, axis size, rows per shard, first owned row).

    ``size`` is the STATIC mesh-axis extent (callers pass mesh.shape[AXIS]):
    rows-per-shard feeds dynamic_slice sizes, which must be static.
    """
    s = jax.lax.axis_index(AXIS)
    per = nrows_total // size
    return s, size, per, s * per


# ----------------------------------------------------------------------------
# Distributed BFS
# ----------------------------------------------------------------------------
def dbfs(mesh: Mesh, state: GraphState, src_slot, dst_slot):
    """Distributed BFS; returns (found, parent[V], dist[V], expanded[V], steps).

    Supersteps: each shard expands its OWNED frontier rows (local dense
    mat-vec over adj rows) and the partial next-frontiers are OR-combined
    with a psum — the standard BSP frontier exchange, here derived as the
    sharded form of the paper's TreeCollect.
    """
    v = state.capacity

    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(AXIS), P(AXIS), P(AXIS, None), P(), P()),
        out_specs=(P(), P(), P(), P(), P()),
        # Outputs are value-replicated (every shard computes the full combined
        # frontier/parents), which the VMA analysis cannot infer past pvary.
        check_vma=False,
    )
    def run(vkey_l, valive_l, adjw_l, src, dst):
        _, _, per, row0 = _row_block_info(v, mesh.shape[AXIS])
        alive_g = jax.lax.all_gather(valive_l, AXIS, tiled=True)  # bool[V]
        # legacy engine: dense local block, edge view via the ONE
        # traversable predicate (row-slice form, DESIGN.md §10)
        adj_l = traversable(unpack_bits(adjw_l, v), valive_l, alive_g)
        src_ok = (src >= 0) & alive_g[jnp.maximum(src, 0)]
        s = jnp.maximum(src, 0)
        frontier0 = jnp.zeros((v,), jnp.bool_).at[s].set(src_ok)
        visited0 = frontier0
        parent0 = jnp.full((v,), -1, jnp.int32)
        dist0 = jnp.where(frontier0, 0, -1).astype(jnp.int32)
        expanded0 = jnp.zeros((v,), jnp.bool_)
        # mark replicated initial carries as device-varying for the VMA check
        frontier0, visited0, parent0, dist0, expanded0 = jax.tree.map(
            _pvary, (frontier0, visited0, parent0, dist0, expanded0)
        )

        def cond(c):
            frontier, visited, parent, dist, expanded, step = c
            hit = (dst >= 0) & visited[jnp.maximum(dst, 0)]
            return jnp.any(frontier) & ~hit & (step < v)

        def body(c):
            frontier, visited, parent, dist, expanded, step = c
            expanded = expanded | frontier
            f_mine = jax.lax.dynamic_slice(frontier, (row0,), (per,))
            fa = f_mine.astype(jnp.float32)
            reach_part = (fa @ adj_l.astype(jnp.float32)) > 0
            idx = (jnp.arange(per, dtype=jnp.int32) + row0)[:, None]
            cand = jnp.where(f_mine[:, None] & adj_l, idx, jnp.int32(2**31 - 1))
            par_part = jnp.min(cand, axis=0)
            reach = jax.lax.psum(reach_part.astype(jnp.int32), AXIS) > 0
            parent_new = jax.lax.pmin(par_part, AXIS)
            new = reach & alive_g & ~visited
            parent = jnp.where(new, parent_new, parent)
            dist = jnp.where(new, step + 1, dist)
            visited = visited | new
            return new, visited, parent, dist, expanded, step + 1

        frontier, visited, parent, dist, expanded, steps = jax.lax.while_loop(
            cond, body, (frontier0, visited0, parent0, dist0, expanded0, jnp.int32(0))
        )
        found = (dst >= 0) & visited[jnp.maximum(dst, 0)] & src_ok
        return found, parent, dist, expanded, steps

    return run(
        state.vkey, state.valive, state.adj_packed,
        jnp.asarray(src_slot, jnp.int32), jnp.asarray(dst_slot, jnp.int32),
    )


# ----------------------------------------------------------------------------
# Distributed mutation batches (owner-routed)
# ----------------------------------------------------------------------------
def dapply_ops(mesh: Mesh, state: GraphState, ops: OpBatch):
    """Apply an op batch to the sharded graph, lane order = linearization.

    Ownership: a mutation's *home* is the owner of its source-vertex row
    (edge ops: key1's slot; AddVertex: hash owner). Owners apply their lanes
    locally; cross-shard information (the dst slot id of an edge, endpoint
    aliveness) is resolved with replicated lookups before application, and
    endpoint-aliveness races across shards are checked again at apply time
    (the Figure-6 recheck of the paper, here a second replicated read).
    """
    v = state.capacity
    b = ops.lanes

    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(P(AXIS), P(AXIS), P(AXIS), P(AXIS), P(AXIS, None),
                  P(), P(), P(), P()),
        out_specs=(P(AXIS), P(AXIS), P(AXIS), P(AXIS), P(AXIS, None), P()),
    )
    def run(vkey_l, valive_l, vver_l, ecnt_l, adjw_l, opc, k1, k2, expect):
        sid, ssize, per, row0 = _row_block_info(v, mesh.shape[AXIS])
        # legacy engine: run the lane loop on the dense local block, repack
        # at the boundary (the production packed engines live in partition.py)
        adj_l = unpack_bits(adjw_l, v).astype(jnp.uint8)

        def body(i, carry):
            vkey_l, valive_l, vver_l, ecnt_l, adj_l, res = carry
            op, a, bk, exp = opc[i], k1[i], k2[i], expect[i]
            s1 = _global_find(vkey_l, valive_l, jnp.array([a]), row0)[0]
            s2 = _global_find(vkey_l, valive_l, jnp.array([bk]), row0)[0]
            alive_any = jnp.ones((), jnp.bool_)

            # --- AddVertex (owner = hash) ---------------------------------
            owner = jnp.abs(a) % ssize
            free_l = vkey_l == EMPTY_KEY
            have_free = jnp.any(free_l)
            new_loc = jnp.argmax(free_l).astype(jnp.int32)
            addv_mine = (op == 1) & (owner == sid) & (s1 < 0) & have_free
            tgt = jnp.where(addv_mine, new_loc, per)
            vkey_l = vkey_l.at[tgt].set(a, mode="drop")
            valive_l = valive_l.at[tgt].set(True, mode="drop")
            vver_l = vver_l.at[tgt].add(1, mode="drop")
            ecnt_l = ecnt_l.at[tgt].set(0, mode="drop")
            adj_l = adj_l.at[tgt, :].set(0, mode="drop")
            # clear the column for the reused slot globally
            col_clear = jax.lax.pmax(jnp.where(addv_mine, new_loc + row0, -1), AXIS)
            adj_l = jnp.where(col_clear >= 0, adj_l.at[:, jnp.maximum(col_clear, 0)].set(0), adj_l)
            r_addv = jnp.where(s1 >= 0, 0, jnp.where(jax.lax.pmax(addv_mine.astype(jnp.int32), AXIS) > 0, 1, 7))

            # --- RemoveVertex (owner = slot owner) -------------------------
            remv = (op == 2) & (s1 >= 0)
            loc1 = s1 - row0
            mine1 = (loc1 >= 0) & (loc1 < per)
            t = jnp.where(remv & mine1, loc1, per)
            valive_l = valive_l.at[t].set(False, mode="drop")
            vver_l = vver_l.at[t].add(1, mode="drop")
            ecnt_l = ecnt_l.at[t].add(1, mode="drop")
            # bump local in-edge sources of the removed column
            col = jnp.maximum(s1, 0)
            bump = remv & (adj_l[:, col] > 0) & valive_l
            ecnt_l = ecnt_l + bump.astype(jnp.int32)
            r_remv = jnp.where(s1 >= 0, 1, 0)

            # --- Contains --------------------------------------------------
            r_conv = jnp.where(s1 >= 0, 1, 0)

            # --- Edge ops (owner = key1 slot owner) -------------------------
            both = (s1 >= 0) & (s2 >= 0)
            e_mine = mine1 & both
            er, ec = jnp.where(e_mine, loc1, per), jnp.maximum(s2, 0)
            cur_mine = adj_l[jnp.minimum(er, per - 1), ec] > 0
            cur = jax.lax.pmax(jnp.where(e_mine, cur_mine.astype(jnp.int32), 0), AXIS) > 0
            my_ecnt = ecnt_l[jnp.minimum(jnp.where(mine1, loc1, 0), per - 1)]
            src_ecnt = jax.lax.pmax(jnp.where(mine1 & (s1 >= 0), my_ecnt, -(2**31)), AXIS)
            cas_ok = (exp < 0) | (src_ecnt == exp)
            do_add = (op == 4) & both & cas_ok & ~cur
            do_rem = (op == 5) & both & cas_ok & cur
            et = jnp.where((do_add | do_rem) & e_mine, er, per)
            adj_l = adj_l.at[et, ec].set(jnp.where(do_add, 1, 0).astype(adj_l.dtype), mode="drop")
            ecnt_l = ecnt_l.at[et].add(1, mode="drop")
            r_adde = jnp.where(both, jnp.where(cas_ok, jnp.where(cur, 4, 5), 8), 2)
            r_reme = jnp.where(both, jnp.where(cas_ok, jnp.where(cur, 6, 3), 8), 2)
            r_cone = jnp.where(both, jnp.where(cur, 4, 3), 2)

            r = jax.lax.switch(
                jnp.clip(op, 0, 6),
                [lambda: jnp.int32(0), lambda: r_addv.astype(jnp.int32), lambda: r_remv.astype(jnp.int32),
                 lambda: r_conv.astype(jnp.int32), lambda: r_adde.astype(jnp.int32),
                 lambda: r_reme.astype(jnp.int32), lambda: r_cone.astype(jnp.int32)],
            )
            res = res.at[i].set(r)
            return vkey_l, valive_l, vver_l, ecnt_l, adj_l, res

        res0 = jnp.zeros((b,), jnp.int32)
        vkey_l, valive_l, vver_l, ecnt_l, adj_l, res = jax.lax.fori_loop(
            0, b, body, (vkey_l, valive_l, vver_l, ecnt_l, adj_l, res0))
        return (vkey_l, valive_l, vver_l, ecnt_l,
                pack_bits(adj_l.astype(jnp.bool_)), res)

    vkey, valive, vver, ecnt, adj, res = run(
        state.vkey, state.valive, state.vver, state.ecnt, state.adj_packed,
        ops.opcode, ops.key1, ops.key2, ops.expect,
    )
    # Legacy engine: the lane loop mutates only the dense out-rows; the
    # maintained in-adjacency is restored by one packed transpose at the
    # boundary (the production partition.py engine mirrors every RMW
    # in place instead, DESIGN.md §11).
    adj_in = pack_transpose(adj, state.capacity)
    return GraphState(vkey, valive, vver, ecnt, adj, adj_in), res


# ----------------------------------------------------------------------------
# Distributed double collect (GetPath)
# ----------------------------------------------------------------------------
class DCollect(NamedTuple):
    found: jax.Array
    parent: jax.Array
    touched: jax.Array
    ver_ecnt: jax.Array
    ver_vver: jax.Array
    src_slot: jax.Array
    dst_slot: jax.Array


def dcollect(mesh: Mesh, state: GraphState, k, l) -> DCollect:
    keys = jnp.asarray([k, l], jnp.int32)

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(AXIS), P(AXIS), P()),
        out_specs=(P(), P()),
    )
    def lookup(vkey_l, valive_l, ks):
        _, _, per, row0 = _row_block_info(state.capacity, mesh.shape[AXIS])
        s = _global_find(vkey_l, valive_l, ks, row0)
        return s[0], s[1]

    sk, sl = lookup(state.vkey, state.valive, keys)
    found, parent, dist, expanded, steps = dbfs(mesh, state, sk, sl)
    touched = expanded
    touched = touched.at[jnp.maximum(sk, 0)].set(touched[jnp.maximum(sk, 0)] | (sk >= 0))
    touched = touched.at[jnp.maximum(sl, 0)].set(touched[jnp.maximum(sl, 0)] | (sl >= 0))
    # Version snapshot stays SHARDED — no gather; compare is local + psum.
    return DCollect(found, parent, touched, state.ecnt, state.vver, sk, sl)


def dcompare(mesh: Mesh, a: DCollect, b: DCollect) -> jax.Array:
    """Validation = ONE scalar psum over local mismatch counts."""
    v = a.parent.shape[0]

    @functools.partial(
        shard_map, mesh=mesh,
        in_specs=(P(AXIS), P(AXIS), P(AXIS), P(AXIS), P(), P()),
        out_specs=P(),
    )
    def vers_mismatch(ea, eb, va, vb, ta, tb):
        _, _, per, row0 = _row_block_info(v, mesh.shape[AXIS])
        t_a = jax.lax.dynamic_slice(ta, (row0,), (per,))
        t_b = jax.lax.dynamic_slice(tb, (row0,), (per,))
        bad = (t_a != t_b) | (t_a & ((ea != eb) | (va != vb)))
        return jax.lax.psum(jnp.sum(bad.astype(jnp.int32)), AXIS)

    mism = vers_mismatch(a.ver_ecnt, b.ver_ecnt, a.ver_vver, b.ver_vver, a.touched, b.touched)
    same_tree = jnp.all(jnp.where(a.touched, a.parent, -1) == jnp.where(b.touched, b.parent, -1))
    return (
        (a.found == b.found)
        & (a.src_slot == b.src_slot)
        & (a.dst_slot == b.dst_slot)
        & (mism == 0)
        & same_tree
    )


def dget_path_session(mesh, fetch_state, k, l, max_rounds: int = 64):
    """Distributed GetPath: host-level double-collect loop (see snapshot.py)."""
    from repro.core.bfs import extract_path

    prev_state = fetch_state()
    prev = dcollect(mesh, prev_state, k, l)
    rounds = 1
    while rounds < max_rounds:
        st = fetch_state()
        cur = dcollect(mesh, st, k, l)
        rounds += 1
        if bool(dcompare(mesh, prev, cur)):
            n, slots = extract_path(cur.parent, cur.src_slot, cur.dst_slot)
            keys = jnp.where(slots >= 0, st.vkey[jnp.clip(slots, 0, st.capacity - 1)], -1)
            ok = bool(cur.found)
            return ok, (int(n) if ok else 0), ([int(x) for x in keys[: int(n)]] if ok else []), rounds
        prev, prev_state = cur, st
    return False, 0, [], rounds
