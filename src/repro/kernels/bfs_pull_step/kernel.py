"""Pallas TPU kernel: one bottom-up ("pull") BFS superstep (DESIGN.md §11).

The direction-optimizing counterpart of kernels/bfs_step &
kernels/bfs_multi_step: when the frontier covers a large fraction of the
graph, top-down push streams almost every adjacency row only to rediscover
vertices it already visited. Pull inverts the scan — every NOT-yet-visited
vertex ANDs its own maintained packed in-adjacency row against the packed
frontier bitset(s):

    hit[q, r]    = any_w ( adj_in[r, w] & frontier_words[q, w] )
    parent[q, r] = lowest set bit index of adj_in[r, :] & frontier_words[q, :]

Because the in-adjacency is maintained first-class (core/ops.py mirrors
every edge RMW; the transpose invariant pins it), the kernel streams
uint32[TR, W] word tiles straight from the stored representation — no
transpose, no unpack on the HBM path.

Grid = (row_tiles,): each program owns TR destination rows and the FULL
word axis, so the kernel is embarrassingly parallel — there is NO
cross-tile reduction (the push kernels revisit each output tile across an
"arbitrary" row-tile axis; pull's reduction runs over the word axis,
entirely in-tile). Row tiles where every row is already visited or dead —
most tiles in late supersteps — skip the word scan with @pl.when, the pull
analogue of the push kernels' empty-frontier-tile skip.

Parent extraction: the first frontier parent of row r is the lowest set
bit of the AND-ed words. Any nonzero word at index w dominates every later
word in the masked min (32*w + ctz < 32*(w+1)), so the vectorized min over
words IS the per-word early exit — the scan effectively stops at the first
word containing a parent. ctz comes from the two's-complement low-bit
trick (x & -x, then popcount(x-1)); both verified native on uint32.

Layout: a destination row's answer is a lane reduction over its words, so
it lands in a [TR, 1] sublane column. The kernel therefore works on the
TRANSPOSED slabs — the "still to visit" mask and both outputs are [R, TQ]
(rows on sublanes, queries on lanes) — and writes query q's column with a
lane-masked select; the wrapper transposes to the [Q, R] contract. Every
block is 2-D with a whole-axis or 128-multiple last dim (Mosaic's rule),
and the per-query ``fori_loop`` keeps one [TR, W] candidate slice live.

VMEM footprint per program instance (TQ=64, TR=256, W=1024 => V=32768):
    adj_in tile    256*1024 u32    =   1 MiB
    frontier slab  64*1024 u32     = 256 KiB
    todo/out slabs 3 * 256*64 i32  = 192 KiB
    candidate temps ~3 * 1 MiB                       << 16 MiB VMEM
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.graph import WORD_BITS
from repro.kernels.mosaic import interpret_mode

INT32_MAX = 2**31 - 1  # python int: pallas kernels must not capture tracers


def _ctz32(words):
    """Count-trailing-zeros per uint32 word (32 for zero words; callers
    mask those out)."""
    low = words & (jnp.uint32(0) - words)
    return jax.lax.population_count(low - jnp.uint32(1)).astype(jnp.int32)


def _bfs_pull_step_kernel(fw_ref, adjin_ref, todo_ref, new_ref, parent_ref,
                          *, tq: int):
    new_ref[...] = jnp.zeros_like(new_ref)
    parent_ref[...] = jnp.full_like(parent_ref, -1)

    todo = todo_ref[...]                               # int32 [TR, TQ]
    fw_any = jnp.max((fw_ref[...] != jnp.uint32(0)).astype(jnp.int32))

    @pl.when((jnp.max(todo) > 0) & (fw_any > 0))
    def _scan():
        a = adjin_ref[...]                             # uint32 [TR, W]
        widx = jax.lax.broadcasted_iota(jnp.int32, a.shape, 1) * WORD_BITS
        lane = jax.lax.broadcasted_iota(jnp.int32, todo.shape, 1)

        def per_query(q, carry):
            hit, pmin = carry
            cand = a & fw_ref[pl.ds(q, 1), :]          # [TR, W]
            pc = jnp.where(cand != jnp.uint32(0), widx + _ctz32(cand),
                           INT32_MAX)
            pq = jnp.min(pc, axis=1, keepdims=True)    # [TR, 1]
            mine = lane == q
            return (jnp.where(mine, (pq < INT32_MAX).astype(jnp.int32), hit),
                    jnp.where(mine, pq, pmin))

        hit, pmin = jax.lax.fori_loop(
            0, tq, per_query,
            (jnp.zeros(todo.shape, jnp.int32),
             jnp.full(todo.shape, INT32_MAX, jnp.int32)))
        new = (hit > 0) & (todo > 0)
        new_ref[...] = new.astype(jnp.int32)
        parent_ref[...] = jnp.where(new, pmin, jnp.int32(-1))


@functools.partial(jax.jit, static_argnames=("tr", "interpret"))
def bfs_pull_step_pallas(frontier_words, adj_in_rows, alive, visited, *,
                         tr: int = 256, interpret: bool | None = None):
    """One pull expansion of Q frontiers over R destination rows. R % tr == 0.

    frontier_words: uint32[Q, W] — packed (frontier & alive) bitsets
    adj_in_rows:    uint32[R, W] — maintained packed in-adjacency rows
    alive:          int32[R] (0/1) — liveness of the destination rows
    visited:        int32[Q, R] (0/1)
    Returns (new int32[Q, R], parent int32[Q, R]).

    ``adj_in_rows`` may be a contiguous ROW SLICE of the in-adjacency — the
    sharded engine's column-sharded in-rows (DESIGN.md §8, §11): outputs
    then cover exactly those destination rows, while parent ids are GLOBAL
    frontier bit indices read off the word axis, so the caller needs no
    row-offset fixup (unlike the push kernels' slice-relative parents).

    Q is the full (already padded) query-slab height; callers align it to
    the sublane multiple (kernels/bfs_pull_step/ops.py pads).
    """
    q, w = frontier_words.shape
    r = adj_in_rows.shape[0]
    assert adj_in_rows.shape[1] == w, (frontier_words.shape, adj_in_rows.shape)
    assert alive.shape == (r,) and visited.shape == (q, r), \
        (alive.shape, visited.shape, (q, r))
    assert r % tr == 0, (r, tr)
    todo = ((alive[None, :] > 0) & (visited == 0)).astype(jnp.int32)
    grid = (r // tr,)
    new_t, parent_t = pl.pallas_call(
        functools.partial(_bfs_pull_step_kernel, tq=q),
        grid=grid,
        in_specs=[
            pl.BlockSpec((q, w), lambda i: (0, 0)),
            pl.BlockSpec((tr, w), lambda i: (i, 0)),
            pl.BlockSpec((tr, q), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((tr, q), lambda i: (i, 0)),
            pl.BlockSpec((tr, q), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((r, q), jnp.int32),
            jax.ShapeDtypeStruct((r, q), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret_mode(interpret),
    )(frontier_words, adj_in_rows, todo.T)
    return new_t.T, parent_t.T
