"""jit'd public wrapper for the edge_update kernel."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.edge_update.kernel import (
    edge_update_packed_pallas,
    edge_update_pallas,
)


def _pick_tile(v: int) -> int:
    for t in (8, 4, 2):
        if v % t == 0:
            return t
    return 1


@functools.partial(jax.jit, static_argnames=())
def edge_update(adj, ecnt, rows, cols, vals, mask):
    """Apply pre-resolved edge writes; see kernel module docstring."""
    t = _pick_tile(adj.shape[0])
    return edge_update_pallas(
        adj, ecnt,
        rows.astype(jnp.int32), cols.astype(jnp.int32),
        vals.astype(jnp.int32), mask.astype(jnp.int32),
        tr=t,
    )


@functools.partial(jax.jit, static_argnames=())
def edge_update_packed(adj_packed, ecnt, rows, cols, vals, mask):
    """Packed form: masked single-bit set/clear per fired op (DESIGN.md §10).

    adj_packed: uint32[V, ceil(V/32)] — the GraphState storage format.
    """
    t = _pick_tile(adj_packed.shape[0])
    return edge_update_packed_pallas(
        adj_packed, ecnt,
        rows.astype(jnp.int32), cols.astype(jnp.int32),
        vals.astype(jnp.int32), mask.astype(jnp.int32),
        tr=t,
    )
