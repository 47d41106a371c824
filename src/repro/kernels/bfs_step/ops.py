"""Single-query BFS superstep on the Pallas kernels (adapts GraphState dtypes).

One frontier is the Q=1 case of the fused multi-query superstep
(kernels/bfs_multi_step, DESIGN.md §7): every entry point here runs that
kernel on a one-query slab zero-padded to the 8-row f32 sublane tile. The
padded rows carry empty frontiers, which the kernel's @pl.when tile skip
and per-query loop never expand, so one kernel body per adjacency encoding
holds all the Mosaic layout work.
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

_SLAB = 8  # f32 sublane multiple: the one-query slab is padded to 8 rows


def _slab(frontier):
    return jnp.zeros((_SLAB,) + frontier.shape, jnp.float32).at[0].set(
        frontier.astype(jnp.float32))


def _visited_slab(visited):
    return jnp.zeros((_SLAB,) + visited.shape, jnp.int32).at[0].set(
        visited.astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("tr", "tc"))
def bfs_step_pallas(frontier, adj, alive, visited, *, tr: int = 256,
                    tc: int = 256):
    """One frontier expansion. All inputs length-V / VxV, V % max(tr,tc) == 0.

    frontier: f32[V] (0/1)   adj: int8/uint8[V, V]
    alive:    int32[V] (0/1) visited: int32[V] (0/1)
    Returns (new_frontier int32[V], parent int32[V]).
    """
    new, parent = multi_bfs_step_pallas(
        _slab(frontier), adj, alive, _visited_slab(visited), tr=tr, tc=tc)
    return new[0], parent[0]


@functools.partial(jax.jit, static_argnames=())
def bfs_step(frontier, adj, alive, visited):
    """Drop-in replacement for core.bfs.bfs_step_jnp (bool interface).

    frontier/alive/visited: bool[V]; adj: uint8[V, V]
    -> (new_frontier bool[V], parent int32[V])
    """
    t = pick_row_tile(adj.shape[0])
    new, parent = bfs_step_pallas(
        frontier, adj, alive.astype(jnp.int32), visited, tr=t, tc=t)
    return new > 0, parent


@functools.partial(jax.jit, static_argnames=())
def bfs_step_packed(frontier, adj_packed, alive, visited):
    """Packed drop-in replacement for core.bfs.bfs_step_packed_jnp.

    frontier/alive/visited: bool[V]; adj_packed: uint32[V, W = ceil(V/32)]
    -> (new_frontier bool[V], parent int32[V])

    The kernel works on the word-padded column range W * 32; alive/visited
    are zero-padded (pad columns can never enter the frontier) and the
    padding is sliced back off here.
    """
    v, w = adj_packed.shape
    vc = w * WORD_BITS
    alive_p = jnp.zeros((vc,), jnp.int32).at[:v].set(alive.astype(jnp.int32))
    vis_p = jnp.zeros((vc,), jnp.int32).at[:v].set(visited.astype(jnp.int32))
    new, parent, _words = multi_bfs_step_packed_pallas(
        _slab(frontier), adj_packed, alive_p, _visited_slab(vis_p),
        tr=pick_row_tile(v), tw=pick_word_tile(w))
    return new[0, :v] > 0, parent[0, :v]
