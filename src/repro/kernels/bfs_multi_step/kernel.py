"""Pallas TPU kernel: ONE fused superstep for Q concurrent BFS frontiers.

The multi-query analogue of kernels/bfs_step (DESIGN.md §7). A batch of Q
reachability queries advances all frontiers with a single frontier-matrix
product per (row, col) adjacency tile:

    reach[q, c-tile] |= any_r ( frontier[q, r-tile] @ adj[r-tile, c-tile] )

The frontier block carries the WHOLE padded query slab [TQ, TR] (TQ = Q
rounded up to the f32 sublane multiple), so each adjacency tile is streamed
HBM->VMEM exactly once per superstep — not once per query as the vmapped
single-query path pays — and the MXU sees a real [TQ,TR]x[TR,TC] matmul
instead of Q rank-1 mat-vecs.

Grid = (col_tiles, row_tiles), row axis innermost so each [TQ, TC] output
tile is produced once and revisited across the reduction ("arbitrary"
dimension semantics). A row tile in which NO query has an active frontier
row is skipped entirely with @pl.when — late supersteps, where most queries
have finished (early-exit masking zeroes their frontiers, core/bfs.py) and
survivors touch few rows, cost almost nothing.

Parent extraction (smallest source row per (query, dst) pair) is a masked
min over the tile's rows, one query at a time (a ``fori_loop`` over the
slab), so only one [TR, TC] candidate slice is live in VMEM. The query's
frontier flags are needed as a [TR, 1] sublane column: the wrapper also
passes the slab transposed ([R, TQ]) and the kernel reads column q off it
with a masked lane reduction — no in-kernel relayout.

Mosaic layout rules shape every operand: all blocks are 2-D, the last
block dim is a multiple of 128 lanes or the whole axis, and vectors
(alive) travel as [1, V] rows. The dense adjacency arrives as uint8 and is
widened through int32 (Mosaic has no direct uint8 -> f32 cast).

VMEM footprint per program instance (TQ=64, TR=TC=256 defaults):
    adj tile       256*256 u8       =  64 KiB (+ f32 copy 256 KiB)
    frontier slabs 2 * 64*256 f32   = 128 KiB
    out slabs      2 * 64*256 i32   = 128 KiB        << 16 MiB VMEM

The PACKED variant (``multi_bfs_step_packed_pallas``, DESIGN.md §10)
streams uint32[TR, TW] word tiles of the packed adjacency — 32x less HBM
per superstep, the term this kernel is bandwidth-bound on. Column
c = 32*w + b of a word tile is bit b of word w, so the kernel works in a
BIT-MAJOR column order: for each bit b it masks ``(words >> b) & 1``
([TR, TW], lane-aligned — no unpack reshape), takes the per-query masked
row min (the parent) and ORs ``parent < MAX`` back into the reach word.
Parents come out as [TQ*32, W] rows (row q*32+b, lane w) and reach/new as
packed [TQ, W] words; the wrapper restores the [Q, W*32] column order.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.graph import WORD_BITS, pack_bits, unpack_bits
from repro.kernels.mosaic import interpret_mode

INT32_MAX = 2**31 - 1  # python int: pallas kernels must not capture tracers


def frontier_column(ft, q):
    """Query ``q``'s frontier flags of this row tile as a bool [TR, 1]
    column, read off the transposed [TR, TQ] frontier block by a masked
    lane reduction."""
    lane = jax.lax.broadcasted_iota(jnp.int32, ft.shape, 1)
    return jnp.max(jnp.where(lane == q, ft, 0.0), axis=1, keepdims=True) > 0


def _multi_bfs_step_kernel(f_ref, ft_ref, adj_ref, alive_ref, visited_ref,
                           reach_ref, parent_ref, *, tq: int, tr: int):
    r = pl.program_id(1)
    nr = pl.num_programs(1)

    @pl.when(r == 0)
    def _init():
        reach_ref[...] = jnp.zeros_like(reach_ref)
        parent_ref[...] = jnp.full_like(parent_ref, INT32_MAX)

    ft = ft_ref[...]  # f32[TR, TQ] — the slab, transposed

    @pl.when(jnp.max(ft) > 0)
    def _accumulate():
        # raw tile: liveness is masked in the epilogue (alive & ~visited)
        edge = adj_ref[...].astype(jnp.int32) > 0  # repro-lint: allow(traversable-predicate)
        hits = jnp.dot(f_ref[...], edge.astype(jnp.float32),
                       preferred_element_type=jnp.float32)     # MXU [TQ, TC]
        reach_ref[...] = jnp.maximum(reach_ref[...],
                                     (hits > 0).astype(jnp.int32))
        rows = r * tr + jax.lax.broadcasted_iota(jnp.int32, edge.shape, 0)

        def per_query(q, carry):
            cand = jnp.where(frontier_column(ft, q) & edge, rows, INT32_MAX)
            cur = parent_ref[pl.ds(q, 1), :]
            parent_ref[pl.ds(q, 1), :] = jnp.minimum(
                cur, jnp.min(cand, axis=0, keepdims=True))
            return carry

        jax.lax.fori_loop(0, tq, per_query, 0)

    @pl.when(r == nr - 1)
    def _epilogue():
        new = ((reach_ref[...] > 0) & (alive_ref[...] > 0)
               & (visited_ref[...] == 0))
        reach_ref[...] = new.astype(jnp.int32)
        parent_ref[...] = jnp.where(new, parent_ref[...], jnp.int32(-1))


@functools.partial(jax.jit, static_argnames=("tr", "tc", "interpret"))
def multi_bfs_step_pallas(frontiers, adj, alive, visited, *, tr: int = 256,
                          tc: int = 256, interpret: bool | None = None):
    """One fused expansion of Q frontiers. R % tr == 0 and V % tc == 0.

    frontiers: f32[Q, R] (0/1)   adj: int8/uint8[R, V]
    alive:     int32[V] (0/1)    visited: int32[Q, V] (0/1)
    Returns (new_frontiers int32[Q, V], parent int32[Q, V]).

    ``adj`` may be a contiguous ROW SLICE of the global adjacency (R < V) —
    the per-shard superstep of the partitioned engine (DESIGN.md §8). Parent
    ids are then relative to the slice; the caller adds its row offset
    before the cross-shard min-combine.

    Q is the full (already padded) query-slab height; callers align it to
    the f32 sublane multiple (kernels/bfs_multi_step/ops.py pads).
    """
    q, rows = frontiers.shape
    v = adj.shape[1]
    assert adj.shape[0] == rows, (frontiers.shape, adj.shape)
    assert alive.shape == (v,) and visited.shape == (q, v), \
        (alive.shape, visited.shape)
    assert rows % tr == 0 and v % tc == 0, (rows, v, tr, tc)
    grid = (v // tc, rows // tr)
    return pl.pallas_call(
        functools.partial(_multi_bfs_step_kernel, tq=q, tr=tr),
        grid=grid,
        in_specs=[
            pl.BlockSpec((q, tr), lambda c, r: (0, r)),
            pl.BlockSpec((tr, q), lambda c, r: (r, 0)),
            pl.BlockSpec((tr, tc), lambda c, r: (r, c)),
            pl.BlockSpec((1, tc), lambda c, r: (0, c)),
            pl.BlockSpec((q, tc), lambda c, r: (0, c)),
        ],
        out_specs=[
            pl.BlockSpec((q, tc), lambda c, r: (0, c)),
            pl.BlockSpec((q, tc), lambda c, r: (0, c)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((q, v), jnp.int32),
            jax.ShapeDtypeStruct((q, v), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret_mode(interpret),
    )(frontiers, frontiers.T, adj, alive[None, :], visited)


# ----------------------------------------------------------------------------
# Packed-word variant (DESIGN.md §10)
# ----------------------------------------------------------------------------
def _multi_bfs_step_packed_kernel(ft_ref, adjw_ref, alivew_ref, visw_ref,
                                  neww_ref, parent_ref, reachw_ref, *,
                                  tq: int, tr: int):
    r = pl.program_id(1)
    nr = pl.num_programs(1)

    @pl.when(r == 0)
    def _init():
        reachw_ref[...] = jnp.zeros_like(reachw_ref)
        parent_ref[...] = jnp.full_like(parent_ref, INT32_MAX)

    ft = ft_ref[...]  # f32[TR, TQ] — the slab, transposed

    @pl.when(jnp.max(ft) > 0)
    def _accumulate():
        a = adjw_ref[...]                                       # u32[TR, TW]
        rows = r * tr + jax.lax.broadcasted_iota(jnp.int32, a.shape, 0)

        def per_query(q, carry):
            sel = jnp.where(frontier_column(ft, q), a, jnp.uint32(0))
            words = jnp.zeros((1, a.shape[1]), jnp.uint32)
            for b in range(WORD_BITS):                  # bit-major columns
                hit = ((sel >> b) & jnp.uint32(1)) != 0
                pm = jnp.min(jnp.where(hit, rows, INT32_MAX), axis=0,
                             keepdims=True)             # [1, TW]
                row = pl.ds(q * WORD_BITS + b, 1)
                parent_ref[row, :] = jnp.minimum(parent_ref[row, :], pm)
                words = words | jnp.where(pm < INT32_MAX,
                                          jnp.uint32(1 << b), jnp.uint32(0))
            qrow = pl.ds(q, 1)
            reachw_ref[qrow, :] = reachw_ref[qrow, :] | words
            return carry

        jax.lax.fori_loop(0, tq, per_query, 0)

    @pl.when(r == nr - 1)
    def _epilogue():
        neww_ref[...] = reachw_ref[...] & alivew_ref[...] & ~visw_ref[...]

        def mask_query(q, carry):
            nq = neww_ref[pl.ds(q, 1), :]
            for b in range(WORD_BITS):
                row = pl.ds(q * WORD_BITS + b, 1)
                parent_ref[row, :] = jnp.where(
                    ((nq >> b) & jnp.uint32(1)) != 0, parent_ref[row, :],
                    jnp.int32(-1))
            return carry

        jax.lax.fori_loop(0, tq, mask_query, 0)


@functools.partial(jax.jit, static_argnames=("tr", "tw", "interpret"))
def multi_bfs_step_packed_pallas(frontiers, adj_packed, alive, visited, *,
                                 tr: int = 256, tw: int = 128,
                                 interpret: bool | None = None):
    """One packed fused expansion of Q frontiers. R % tr == 0, W % tw == 0.

    frontiers: f32[Q, R] (0/1)   adj_packed: uint32[R, W]
    alive:     int32[W*32]       visited: int32[Q, W*32]
    Returns (new int32[Q, W*32], parent int32[Q, W*32], reach_words
    uint32[Q, W]). Like the dense kernel, ``adj_packed`` may be a contiguous
    ROW SLICE of the packed adjacency (the per-shard superstep, DESIGN.md
    §8): parent ids come back slice-relative, and ``reach_words`` carries
    the raw pre-mask OR partial the sharded engine exchanges as packed
    uint32 frontiers. Callers slice the word padding (columns >= V) off.
    """
    q, rows = frontiers.shape
    w = adj_packed.shape[1]
    vc = w * WORD_BITS
    assert adj_packed.shape[0] == rows, (frontiers.shape, adj_packed.shape)
    assert alive.shape == (vc,) and visited.shape == (q, vc), \
        (alive.shape, visited.shape, vc)
    assert rows % tr == 0 and w % tw == 0, (rows, w, tr, tw)
    grid = (w // tw, rows // tr)
    new_w, parent_bm, reach_w = pl.pallas_call(
        functools.partial(_multi_bfs_step_packed_kernel, tq=q, tr=tr),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tr, q), lambda c, r: (r, 0)),
            pl.BlockSpec((tr, tw), lambda c, r: (r, c)),
            pl.BlockSpec((1, tw), lambda c, r: (0, c)),
            pl.BlockSpec((q, tw), lambda c, r: (0, c)),
        ],
        out_specs=[
            pl.BlockSpec((q, tw), lambda c, r: (0, c)),
            pl.BlockSpec((q * WORD_BITS, tw), lambda c, r: (0, c)),
            pl.BlockSpec((q, tw), lambda c, r: (0, c)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((q, w), jnp.uint32),
            jax.ShapeDtypeStruct((q * WORD_BITS, w), jnp.int32),
            jax.ShapeDtypeStruct((q, w), jnp.uint32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret_mode(interpret),
    )(frontiers.T, adj_packed, pack_bits(alive > 0)[None, :],
      pack_bits(visited > 0))
    parent = parent_bm.reshape(q, WORD_BITS, w).transpose(0, 2, 1)
    return (unpack_bits(new_w, vc).astype(jnp.int32),
            parent.reshape(q, vc), reach_w)
