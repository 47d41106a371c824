"""jit'd public wrapper for the bfs_multi_step kernel (adapts GraphState dtypes).

Pads the query axis up to the f32 sublane multiple (8) so the frontier slab
is a legal TPU tile, runs the fused kernel, and slices the padding back off.
Padded queries carry an all-zero frontier, so they are dead weight the
@pl.when tile-skip removes — they never reach the MXU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.graph import WORD_BITS
from repro.kernels.bfs_multi_step.kernel import (
    multi_bfs_step_packed_pallas,
    multi_bfs_step_pallas,
)
from repro.kernels.mosaic import pick_row_tile, pick_word_tile

_Q_ALIGN = 8  # f32 sublane multiple


@functools.partial(jax.jit, static_argnames=())
def multi_bfs_step(frontiers, adj, alive, visited):
    """Drop-in replacement for core.bfs.multi_bfs_step_jnp (bool interface).

    frontiers: bool[Q, R]; adj: uint8[R, V]; alive: bool[V]; visited: bool[Q, V]
    -> (new_frontiers bool[Q, V], parent int32[Q, V])

    R == V for the dense engine; R = V/S rows for one shard of the
    partitioned engine (DESIGN.md §8), in which case parent ids are local to
    the row slice (the caller adds its row offset).
    """
    q, rows = frontiers.shape
    v = adj.shape[1]
    qpad = -(-q // _Q_ALIGN) * _Q_ALIGN
    tr = pick_row_tile(rows)
    tc = pick_row_tile(v)
    f = jnp.zeros((qpad, rows), jnp.float32).at[:q].set(frontiers.astype(jnp.float32))
    vis = jnp.zeros((qpad, v), jnp.int32).at[:q].set(visited.astype(jnp.int32))
    new, parent = multi_bfs_step_pallas(
        f,
        adj,
        alive.astype(jnp.int32),
        vis,
        tr=tr,
        tc=tc,
    )
    return new[:q] > 0, parent[:q]


@functools.partial(jax.jit, static_argnames=())
def multi_bfs_step_packed(frontiers, adj_packed, alive, visited):
    """Packed drop-in replacement for core.bfs.multi_bfs_step_packed_jnp.

    frontiers: bool[Q, R]; adj_packed: uint32[R, W]; alive: bool[V];
    visited: bool[Q, V] -> (new bool[Q, V], parent int32[Q, V])

    R == V for the dense engine, R = V/S rows of one shard otherwise
    (parent ids then local to the slice). The kernel sees the word-padded
    column range W * 32 (alive/visited zero-padded; padding sliced off).
    """
    q, rows = frontiers.shape
    v = alive.shape[0]
    w = adj_packed.shape[1]
    vc = w * WORD_BITS
    qpad = -(-q // _Q_ALIGN) * _Q_ALIGN
    f = jnp.zeros((qpad, rows), jnp.float32).at[:q].set(
        frontiers.astype(jnp.float32))
    alive_p = jnp.zeros((vc,), jnp.int32).at[:v].set(alive.astype(jnp.int32))
    vis_p = jnp.zeros((qpad, vc), jnp.int32).at[:q, :v].set(
        visited.astype(jnp.int32))
    new, parent, _words = multi_bfs_step_packed_pallas(
        f,
        adj_packed,
        alive_p,
        vis_p,
        tr=pick_row_tile(rows),
        tw=pick_word_tile(w),
    )
    return new[:q, :v] > 0, parent[:q, :v]
