"""jit'd public wrappers for the bfs_pull_step kernel (adapt GraphState dtypes).

Pads the query axis up to the sublane multiple (8) so the frontier-word
slab and the [Q, R] output tiles are legal TPU blocks, runs the pull
kernel, and slices the padding back off. Padded queries carry an all-zero
frontier bitset, so they can never produce a hit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.graph import pack_bits
from repro.kernels.bfs_pull_step.kernel import bfs_pull_step_pallas
from repro.kernels.mosaic import pick_row_tile

_Q_ALIGN = 8  # sublane multiple for the 32-bit slabs


@functools.partial(jax.jit, static_argnames=())
def multi_bfs_pull_step_rows(frontier_words, adj_in_rows, alive_rows,
                             visited_rows):
    """Row-slice pull step — the sharded engine's form (DESIGN.md §8, §11).

    frontier_words: uint32[Q, W] (packed frontier & alive bitsets);
    adj_in_rows: uint32[R, W] (R == V, or one shard's column-sharded
    in-rows); alive_rows: bool[R]; visited_rows: bool[Q, R]
    -> (new bool[Q, R], parent int32[Q, R])

    Parent ids are GLOBAL frontier bit indices (read off the word axis),
    so the sharded caller needs no row-offset fixup.
    """
    q, w = frontier_words.shape
    rows = adj_in_rows.shape[0]
    qpad = -(-q // _Q_ALIGN) * _Q_ALIGN
    fwp = jnp.zeros((qpad, w), jnp.uint32).at[:q].set(frontier_words)
    visp = jnp.zeros((qpad, rows), jnp.int32).at[:q].set(
        visited_rows.astype(jnp.int32))
    new, parent = bfs_pull_step_pallas(
        fwp,
        adj_in_rows,
        alive_rows.astype(jnp.int32),
        visp,
        tr=pick_row_tile(rows),
    )
    return new[:q] > 0, parent[:q]


@functools.partial(jax.jit, static_argnames=())
def multi_bfs_pull_step(frontiers, adj_in_packed, alive, visited):
    """Drop-in replacement for core.bfs.multi_bfs_step_pull_jnp (bool
    interface): frontiers bool[Q, V]; adj_in_packed uint32[V, W]; alive
    bool[V]; visited bool[Q, V] -> (new bool[Q, V], parent int32[Q, V])."""
    fw = pack_bits(frontiers & alive[None, :])
    return multi_bfs_pull_step_rows(fw, adj_in_packed, alive, visited)


@functools.partial(jax.jit, static_argnames=())
def bfs_pull_step(frontier, adj_in_packed, alive, visited):
    """Single-query drop-in for core.bfs.bfs_step_pull_jnp (bool interface):
    frontier/alive/visited bool[V]; adj_in_packed uint32[V, W]
    -> (new bool[V], parent int32[V])."""
    new, parent = multi_bfs_pull_step(
        frontier[None, :], adj_in_packed, alive, visited[None, :])
    return new[0], parent[0]
