"""Pure-jnp oracle for the single-query BFS superstep."""
from __future__ import annotations

import jax.numpy as jnp

INT32_MAX = jnp.int32(2**31 - 1)


def bfs_step_ref(frontier, adj, alive, visited):
    """Same contract as ops.bfs_step_pallas.

    frontier f32[V] (0/1), adj (u)int8[V,V], alive/visited int32[V] (0/1)
    -> (new_frontier int32[V], parent int32[V]).
    """
    v = adj.shape[0]
    f = frontier.astype(jnp.float32)
    # repro-lint: allow(traversable-predicate) — raw tile; next line masks
    reach = (f @ adj.astype(jnp.float32)) > 0
    new = reach & (alive > 0) & (visited == 0)
    idx = jnp.arange(v, dtype=jnp.int32)
    # parent scan over the raw tile; `new` above already gates which
    # parents survive  # repro-lint: allow(traversable-predicate)
    cand = jnp.where((frontier[:, None] > 0) & (adj > 0), idx[:, None], INT32_MAX)
    parent = jnp.min(cand, axis=0)
    parent = jnp.where(new, parent, jnp.int32(-1))
    return new.astype(jnp.int32), parent
