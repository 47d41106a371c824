"""BFS traversal as tiled mat-vec — the TPU-native replacement for pointer chasing.

The paper's TreeCollect walks edge-lists node by node. On TPU the same
traversal is a sequence of *frontier expansion* steps over adjacency tiles:

    reach[j]  = OR_i  frontier[i] AND adj[i, j]          (MXU tile mat-vec)
    parent[j] = min_i { i : frontier[i] AND adj[i, j] }  (VPU masked min)
    new       = reach AND alive AND NOT visited

One step costs O(V^2 / P) dense work with high arithmetic intensity instead of
O(E) random accesses — the hardware-adaptation core of this reproduction
(DESIGN.md §1). ``step_fn`` is pluggable per backend (DESIGN.md §10, §11):

  "jnp"           float32-MXU reference: unpack the packed words, expand via
                  a frontier mat-vec (always available)
  "pallas"        kernels/bfs_step on the unpacked view
  "packed"        pure-jnp AND/OR reduction over the packed uint32 words —
                  no unpack, no matmul, ~32x less adjacency traffic
  "packed_pallas" kernels/bfs_step packed kernel (words streamed HBM->VMEM)
  "hybrid"        direction-optimizing superstep (DESIGN.md §11): per-step
                  frontier/unvisited popcounts pick the packed top-down
                  "push" expansion or a bottom-up "pull" word reduction
                  over the maintained ``adj_in_packed`` (Beamer-style
                  alpha/beta switch)
  "hybrid_pallas" same switch; push = the packed bfs_step kernel, pull =
                  kernels/bfs_pull_step

All six backends produce bit-identical BFSResults; every edge view is
derived from the ONE ``core.graph.traversable`` predicate. ``backend=None``
anywhere in this module resolves through ``default_backend()`` — the single
place the repo's fastest engine is named (DESIGN.md §11).
"""
from __future__ import annotations

import functools
import os
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import trace as _trace
from repro.obs.metrics import global_registry as _obs_registry
from repro.core.graph import (
    WORD_BITS,
    GraphState,
    or_reduce,
    pack_bits,
    popcount,
    traversable,
    traversable_packed,
    unpack_bits,
)

INT32_MAX = jnp.int32(2**31 - 1)

# backends whose step functions consume ``state.adj_packed`` directly
PACKED_BACKENDS = ("packed", "packed_pallas")
# direction-optimizing backends: consume adj_packed AND adj_in_packed
HYBRID_BACKENDS = ("hybrid", "hybrid_pallas")

# Beamer-style direction-switch knobs (DESIGN.md §11), static jit args:
# go bottom-up when |frontier| * alpha >= |unvisited|, return top-down once
# |frontier| < V / beta. Vertex-count forms of Beamer's edge-count rules —
# the dense engines' per-step cost is row-count-, not edge-count-, shaped.
# alpha defaults to the packed WORD WIDTH: a pull superstep touches a 32x
# denser encoding per row (words, not parent-candidate lanes), so bottom-up
# pays off once the frontier reaches ~1/32 of the unvisited set — matching
# the measured push/pull crossover recorded in BENCH_fig9_throughput.json.
# On tile-skipping TPU hardware (where push cost really is
# frontier-proportional) serve paths can lower alpha toward Beamer's
# classical ~14; both knobs are static jit args precisely for that.
DEFAULT_ALPHA = WORD_BITS
DEFAULT_BETA = 64


def default_backend() -> str:
    """The fastest BFS backend for this build — the ONE resolution point
    every ``backend=None`` call site threads through (DESIGN.md §11).

    "hybrid" since the direction-optimizing engine landed (previously
    "packed"); override with the ``REPRO_BFS_BACKEND`` environment variable
    (e.g. "hybrid_pallas" runs both directions through the Pallas kernels,
    compiled on a TPU and interpreted elsewhere — kernels/mosaic.py). No
    backend has been measured against another on a chip yet.
    tests/test_hybrid.py pins the resolution.
    """
    return os.environ.get("REPRO_BFS_BACKEND", "hybrid")


def _resolve_backend(backend: str | None) -> str:
    return default_backend() if backend is None else backend


def bfs_step_jnp(frontier, adj, alive, visited):
    """Reference frontier expansion. Returns (new_frontier[V] bool, parent[V] int32).

    parent[j] = smallest frontier index i with a traversable edge i->j (-1
    if none). Both the expansion and the parent scan read the SAME
    ``traversable`` mask, so endpoint liveness cannot drift between them.
    """
    t = traversable(adj, alive)
    f = frontier.astype(jnp.float32)
    reach = (f @ t.astype(jnp.float32)) > 0
    new = reach & ~visited
    v = adj.shape[0]
    idx = jnp.arange(v, dtype=jnp.int32)
    # candidate parent rows: masked min over i of (frontier_i & t_ij)
    cand = jnp.where(frontier[:, None] & t, idx[:, None], INT32_MAX)
    parent = jnp.min(cand, axis=0)
    parent = jnp.where(new, parent, jnp.int32(-1))
    return new, parent


def bfs_step_packed_jnp(frontier, adj_packed, alive, visited):
    """Packed frontier expansion (DESIGN.md §10): reach is a bitwise OR of
    the frontier rows' traversable words — no unpack of the streamed
    adjacency, no matmul. Bit-identical to ``bfs_step_jnp``."""
    v = alive.shape[0]
    t = traversable_packed(adj_packed, alive, pack_bits(alive))
    sel = jnp.where(frontier[:, None], t, jnp.uint32(0))
    reach = unpack_bits(or_reduce(sel, 0), v)
    new = reach & ~visited
    idx = jnp.arange(v, dtype=jnp.int32)
    cand = jnp.where(frontier[:, None] & unpack_bits(t, v),
                     idx[:, None], INT32_MAX)
    parent = jnp.min(cand, axis=0)
    parent = jnp.where(new, parent, jnp.int32(-1))
    return new, parent


def ctz32(words: jax.Array) -> jax.Array:
    """Per-word count-trailing-zeros for uint32 (int32 out; 32 for a zero
    word): isolate the lowest set bit with the two's-complement trick, then
    popcount the trailing-zero mask below it."""
    low = words & (jnp.uint32(0) - words)
    return popcount(low - jnp.uint32(1))


def bfs_step_pull_jnp(frontier, adj_in_packed, alive, visited):
    """Bottom-up ("pull") frontier expansion (DESIGN.md §11): every
    not-yet-visited vertex scans ITS OWN in-adjacency row for a frontier
    parent — one [V, W] word AND against the packed frontier bitset instead
    of the push step's frontier-row selection + [V, V] parent-candidate
    matrix. parent[j] = lowest set bit of ``adj_in[j] & frontier`` = the
    smallest frontier index with a traversable edge into j, so the result
    is bit-identical to ``bfs_step_packed_jnp`` (the masked word-min
    realizes first-parent-wins at word granularity).
    """
    w = adj_in_packed.shape[1]
    fw = pack_bits(frontier & alive)            # only live sources expand
    cand = adj_in_packed & fw[None, :]          # [V, W]
    hit = jnp.any(cand != 0, axis=1)
    new = hit & alive & ~visited
    widx = (jnp.arange(w, dtype=jnp.int32) * WORD_BITS)[None, :]
    pcand = jnp.where(cand != 0, widx + ctz32(cand), INT32_MAX)
    parent = jnp.min(pcand, axis=1)
    parent = jnp.where(new, parent, jnp.int32(-1))
    return new, parent


def pick_direction(pulling, nf, nu, v: int, alpha: int, beta: int):
    """The Beamer-style push/pull switch (DESIGN.md §11), on vertex
    popcounts: enter pull when the frontier has grown to 1/alpha of the
    unvisited set, leave it once the frontier shrinks below V/beta. The
    hysteresis (``pulling`` carried across supersteps) mirrors Beamer's
    two-threshold design; both directions are bit-identical, so the choice
    is pure cost steering. Products are formed in float32: the comparison
    is a heuristic, and nf * alpha can exceed int32 for large Q * V.
    """
    go_pull = nf.astype(jnp.float32) * alpha >= nu.astype(jnp.float32)
    stay_pull = nf.astype(jnp.float32) * beta >= jnp.float32(v)
    return jnp.where(pulling, stay_pull, go_pull)


def _get_step_fn(backend: str):
    if backend == "jnp":
        return bfs_step_jnp
    if backend == "packed":
        return bfs_step_packed_jnp
    if backend == "pallas":
        from repro.kernels.bfs_step.ops import bfs_step as bfs_step_pallas

        return bfs_step_pallas
    if backend == "packed_pallas":
        from repro.kernels.bfs_step.ops import bfs_step_packed

        return bfs_step_packed
    raise ValueError(f"unknown bfs backend {backend!r}")


def _get_hybrid_step_fns(backend: str):
    """(push_fn, pull_fn) for the direction-optimizing backends. Push is
    the packed top-down expansion, pull the bottom-up in-row reduction
    (DESIGN.md §11); "hybrid" stays in jnp, "hybrid_pallas" runs both
    directions through their Pallas kernels."""
    if backend == "hybrid":
        return bfs_step_packed_jnp, bfs_step_pull_jnp
    if backend == "hybrid_pallas":
        from repro.kernels.bfs_pull_step.ops import bfs_pull_step
        from repro.kernels.bfs_step.ops import bfs_step_packed

        return bfs_step_packed, bfs_pull_step
    raise ValueError(f"unknown hybrid bfs backend {backend!r}")


class BFSResult(NamedTuple):
    found: jax.Array    # bool   — dst reached
    parent: jax.Array   # int32[V] — BFS tree (slot -> parent slot, -1 root/unvisited)
    dist: jax.Array     # int32[V] — BFS depth (-1 unvisited)
    expanded: jax.Array  # bool[V] — rows whose adjacency was read (visited set)
    steps: jax.Array    # int32  — number of frontier expansions


def bfs(state: GraphState, src_slot, dst_slot, backend: str | None = None,
        alpha: int = DEFAULT_ALPHA, beta: int = DEFAULT_BETA) -> BFSResult:
    """Full BFS from ``src_slot``; early exit when ``dst_slot`` is reached.

    ``dst_slot < 0`` explores the full reachable set (used by benchmarks).
    Traversable edge: adj[u, w] & alive[u] & alive[w] — a dead endpoint makes
    the ENode logically absent, exactly the paper's marked-ptv rule.

    ``backend=None`` resolves via ``default_backend()`` — HERE, outside
    the jit boundary, so the resolved name (not None) is the static cache
    key and a changed ``REPRO_BFS_BACKEND`` takes effect on the next call.
    The hybrid backends run the direction-optimizing superstep
    (DESIGN.md §11): per-step popcounts of the frontier and the unvisited
    set pick push or pull via ``pick_direction`` (``alpha``/``beta`` are
    the static Beamer knobs, ignored by the single-direction backends).
    """
    return _bfs_jit(state, src_slot, dst_slot,
                    backend=_resolve_backend(backend), alpha=alpha,
                    beta=beta)


@functools.partial(jax.jit, static_argnames=("backend", "alpha", "beta"))
def _bfs_jit(state: GraphState, src_slot, dst_slot, backend: str,
             alpha: int, beta: int) -> BFSResult:
    v = state.capacity
    alive = state.valive
    src_ok = (src_slot >= 0) & alive[jnp.maximum(src_slot, 0)]
    s = jnp.maximum(src_slot, 0)

    frontier0 = jnp.zeros((v,), jnp.bool_).at[s].set(src_ok)
    visited0 = frontier0
    parent0 = jnp.full((v,), -1, jnp.int32)
    dist0 = jnp.where(frontier0, 0, -1).astype(jnp.int32)
    expanded0 = jnp.zeros((v,), jnp.bool_)
    hybrid = backend in HYBRID_BACKENDS
    if hybrid:
        push_fn, pull_fn = _get_hybrid_step_fns(backend)
        adj_arg = state.adj_packed
        adj_in_arg = state.adj_in_packed
    else:
        step_fn = _get_step_fn(backend)
        # packed backends stream the stored words; the float32-MXU backends
        # get the unpacked view, materialized once outside the superstep loop
        adj_arg = state.adj_packed if backend in PACKED_BACKENDS else state.adj

    def cond(c):
        frontier, visited, parent, dist, expanded, step = c[:6]
        hit_dst = (dst_slot >= 0) & visited[jnp.maximum(dst_slot, 0)]
        return jnp.any(frontier) & ~hit_dst & (step < v)

    def body(c):
        frontier, visited, parent, dist, expanded, step = c[:6]
        expanded = expanded | frontier
        if hybrid:
            pulling = pick_direction(
                c[6], jnp.sum(frontier.astype(jnp.int32)),
                jnp.sum((alive & ~visited).astype(jnp.int32)), v, alpha, beta)
            new, par = jax.lax.cond(
                pulling,
                lambda f, vis: pull_fn(f, adj_in_arg, alive, vis),
                lambda f, vis: push_fn(f, adj_arg, alive, vis),
                frontier, visited)
        else:
            new, par = step_fn(frontier, adj_arg, alive, visited)
        parent = jnp.where(new, par, parent)
        dist = jnp.where(new, step + 1, dist)
        visited = visited | new
        out = (new, visited, parent, dist, expanded, step + 1)
        return out + (pulling,) if hybrid else out

    init = (frontier0, visited0, parent0, dist0, expanded0, jnp.int32(0))
    if hybrid:
        init = init + (jnp.asarray(False),)
    final = jax.lax.while_loop(cond, body, init)
    frontier, visited, parent, dist, expanded, steps = final[:6]
    found = (dst_slot >= 0) & visited[jnp.maximum(dst_slot, 0)] & src_ok
    return BFSResult(found, parent, dist, expanded, steps)


@jax.jit
def extract_path(parent: jax.Array, src_slot, dst_slot):
    """Walk the BFS tree from dst back to src.

    Returns (length, slots[V]) — ``slots[:length]`` is the path src..dst in
    order, padded with -1. This is the paper's p-pointer trace in GetPath.
    """
    v = parent.shape[0]
    # reversed walk: collect dst, parent(dst), ...
    def cond(c):
        cur, n, _ = c
        return (cur >= 0) & (n < v)

    def body(c):
        cur, n, buf = c
        buf = buf.at[n].set(cur)
        nxt = jnp.where(cur == src_slot, -1, parent[cur])
        return nxt, n + 1, buf

    _, n, rev = jax.lax.while_loop(
        cond, body, (jnp.asarray(dst_slot, jnp.int32), jnp.int32(0), jnp.full((v,), -1, jnp.int32))
    )
    idx = jnp.arange(v, dtype=jnp.int32)
    fwd = jnp.where(idx < n, rev[jnp.clip(n - 1 - idx, 0, v - 1)], -1)
    return n, fwd


def reachable_count(state: GraphState, src_slot,
                    backend: str | None = None) -> jax.Array:
    """|{w : src ->* w}| — exercised by benchmarks. ``backend=None``
    resolves via ``default_backend()`` (DESIGN.md §11)."""
    r = bfs(state, src_slot, jnp.int32(-1), backend=backend)
    return jnp.sum((r.dist >= 0).astype(jnp.int32))


# ----------------------------------------------------------------------------
# Fused multi-source BFS — Q frontiers advanced by ONE [Q,V] @ [V,V] matmul
# per superstep (DESIGN.md §7)
# ----------------------------------------------------------------------------
def multi_bfs_step_jnp(frontiers, adj, alive, visited):
    """Reference fused expansion for Q frontiers at once.

    frontiers: bool[Q, V], visited: bool[Q, V], alive: bool[V].
    Returns (new bool[Q, V], parent int32[Q, V]) with
    parent[q, j] = smallest i with frontiers[q, i] and a traversable edge
    i->j (else -1) — identical per-query semantics to ``bfs_step_jnp``, but
    the frontier expansion is one real [Q,V]x[V,V] matmul instead of Q
    mat-vecs. Expansion and parent scan share the ``traversable`` mask.
    """
    t = traversable(adj, alive)
    f = frontiers.astype(jnp.float32)
    reach = (f @ t.astype(jnp.float32)) > 0
    new = reach & ~visited
    v = adj.shape[1]
    idx = jnp.arange(v, dtype=jnp.int32)
    # per-query masked min over source rows, laid out src-major
    # [V(src), Q, V(dst)] so the reduction runs over the leading axis
    # (contiguous inner [Q, V] panels — measurably faster than the
    # query-major layout on CPU/VPU)
    cand = jnp.where(frontiers.T[:, :, None] & t[:, None, :],
                     idx[:, None, None], INT32_MAX)
    parent = jnp.min(cand, axis=0)
    parent = jnp.where(new, parent, jnp.int32(-1))
    return new, parent


def multi_bfs_step_packed_jnp(frontiers, adj_packed, alive, visited):
    """Packed fused expansion (DESIGN.md §10): per query, reach is the
    bitwise OR of its frontier rows' traversable words. Bit-identical to
    ``multi_bfs_step_jnp``."""
    v = alive.shape[0]
    t = traversable_packed(adj_packed, alive, pack_bits(alive))
    sel = jnp.where(frontiers[:, :, None], t[None, :, :], jnp.uint32(0))
    reach = unpack_bits(or_reduce(sel, 1), v)
    new = reach & ~visited
    idx = jnp.arange(v, dtype=jnp.int32)
    cand = jnp.where(frontiers.T[:, :, None] & unpack_bits(t, v)[:, None, :],
                     idx[:, None, None], INT32_MAX)
    parent = jnp.min(cand, axis=0)
    parent = jnp.where(new, parent, jnp.int32(-1))
    return new, parent


def multi_bfs_step_pull_jnp(frontiers, adj_in_packed, alive, visited):
    """Fused bottom-up expansion for Q frontiers (DESIGN.md §11): per query,
    every unvisited vertex ANDs its maintained in-adjacency row against that
    query's packed frontier bitset — a [Q, V, W] word volume instead of the
    push step's [V, Q, V] parent-candidate volume (a 32x cut in the term
    that dominates each superstep). Bit-identical to
    ``multi_bfs_step_packed_jnp``."""
    w = adj_in_packed.shape[1]
    fw = pack_bits(frontiers & alive[None, :])          # [Q, W]
    cand = adj_in_packed[None, :, :] & fw[:, None, :]   # [Q, V, W]
    hit = jnp.any(cand != 0, axis=2)
    new = hit & alive[None, :] & ~visited
    widx = (jnp.arange(w, dtype=jnp.int32) * WORD_BITS)[None, None, :]
    pcand = jnp.where(cand != 0, widx + ctz32(cand), INT32_MAX)
    parent = jnp.min(pcand, axis=2)
    parent = jnp.where(new, parent, jnp.int32(-1))
    return new, parent


def _get_multi_step_fn(backend: str):
    if backend == "jnp":
        return multi_bfs_step_jnp
    if backend == "packed":
        return multi_bfs_step_packed_jnp
    if backend == "pallas":
        from repro.kernels.bfs_multi_step.ops import multi_bfs_step

        return multi_bfs_step
    if backend == "packed_pallas":
        from repro.kernels.bfs_multi_step.ops import multi_bfs_step_packed

        return multi_bfs_step_packed
    raise ValueError(f"unknown multi-bfs backend {backend!r}")


def _get_hybrid_multi_step_fns(backend: str):
    """(push_fn, pull_fn) for the fused direction-optimizing backends
    (DESIGN.md §11)."""
    if backend == "hybrid":
        return multi_bfs_step_packed_jnp, multi_bfs_step_pull_jnp
    if backend == "hybrid_pallas":
        from repro.kernels.bfs_multi_step.ops import multi_bfs_step_packed
        from repro.kernels.bfs_pull_step.ops import multi_bfs_pull_step

        return multi_bfs_step_packed, multi_bfs_pull_step
    raise ValueError(f"unknown hybrid multi-bfs backend {backend!r}")


class MultiBFSResult(NamedTuple):
    found: jax.Array     # bool[Q]    — dst reached (per query)
    parent: jax.Array    # int32[Q,V] — per-query BFS tree (-1 root/unvisited)
    dist: jax.Array      # int32[Q,V] — per-query BFS depth (-1 unvisited)
    expanded: jax.Array  # bool[Q,V]  — rows whose adjacency this query read
    steps: jax.Array     # int32[Q]   — per-query frontier expansions
    supersteps: jax.Array  # int32    — shared loop iterations actually run


def multi_bfs(state: GraphState, src_slots, dst_slots,
              backend: str | None = None, parents: bool = True,
              alpha: int = DEFAULT_ALPHA,
              beta: int = DEFAULT_BETA) -> MultiBFSResult:
    """Fused BFS from Q sources with per-query early exit (DESIGN.md §7).

    Per-query results are bit-identical to ``jax.vmap(bfs)`` over the same
    (src, dst) pairs — tests/test_multi_bfs.py asserts this — but the cost
    model is different: ONE shared ``while_loop`` whose body performs a
    single [Q,V] @ [V,V] frontier-matrix product, so the adjacency matrix is
    streamed from HBM once per superstep instead of once per query per
    superstep. Queries that have already reached their destination (or
    exhausted their frontier) are masked to an empty frontier and stop
    contributing work; the loop exits when every query is done.

    ``dst_slots[q] < 0`` explores query q's full reachable set.

    ``parents=False`` is closure-only mode (DESIGN.md §9): parent
    extraction — the [Q,V,V]-shaped masked min that dominates each
    superstep — is skipped and ``parent`` comes back all -1. found, dist,
    expanded and steps are bit-identical to the default mode. The
    reachability-index build drives this: label construction needs
    closures, never trees. The expansion operand is hoisted out of the
    loop: the float32 traversable matrix for the MXU backends (the Pallas
    superstep earns its keep on parent extraction; the matmul alone XLA
    already tiles well), the traversable WORDS for the packed backends
    (DESIGN.md §10) — the latter stream 32x less adjacency per superstep.

    The hybrid backends (DESIGN.md §11) pick push or pull per superstep
    from the popcounts of the ACTIVE queries' pooled frontier and unvisited
    sets (one shared decision — a per-query split would compute both
    directions); ``alpha``/``beta`` are the static Beamer knobs. Closure
    mode stays in jnp for both hybrid flavors (parent extraction is the
    term the kernels exist to shrink, and closure mode has none).
    ``backend=None`` resolves via ``default_backend()`` here, outside the
    jit boundary, so the resolved name is the static cache key. With the
    tracing recorder enabled (DESIGN.md §14) — and only from host context,
    never inside an enclosing jit trace — the SAME superstep body runs
    under a host-driven loop instead of the fused ``lax.while_loop``, so
    every superstep lands as one ``bfs.superstep`` span carrying its
    direction tag and frontier/unvisited popcounts: bit-identical results,
    post-hoc-explainable push/pull decisions.
    """
    backend = _resolve_backend(backend)
    if _trace.enabled() and not _is_tracer(state.valive):
        return _multi_bfs_traced(state, src_slots, dst_slots,
                                 backend=backend, parents=parents,
                                 alpha=alpha, beta=beta)
    return _multi_bfs_jit(state, src_slots, dst_slots,
                          backend=backend,
                          parents=parents, alpha=alpha, beta=beta)


def _is_tracer(x) -> bool:
    """True when called under an enclosing jit trace — the traced host
    loop must never engage there (DESIGN.md §14)."""
    return isinstance(x, jax.core.Tracer)


def _multi_init(state: GraphState, src_slots, dst_slots, hybrid: bool):
    """Shared loop-carry initialization for the fused and traced loops."""
    q = src_slots.shape[0]
    v = state.capacity
    alive = state.valive
    src_ok = (src_slots >= 0) & alive[jnp.maximum(src_slots, 0)]
    s = jnp.maximum(src_slots, 0)

    frontier0 = jnp.zeros((q, v), jnp.bool_).at[jnp.arange(q), s].set(src_ok)
    visited0 = frontier0
    parent0 = jnp.full((q, v), -1, jnp.int32)
    dist0 = jnp.where(frontier0, 0, -1).astype(jnp.int32)
    expanded0 = jnp.zeros((q, v), jnp.bool_)
    steps0 = jnp.zeros((q,), jnp.int32)
    init = (frontier0, visited0, parent0, dist0, expanded0, steps0,
            jnp.int32(0))
    if hybrid:
        init = init + (jnp.asarray(False),)
    return init, src_ok


def _multi_step_fns(state: GraphState, dst_slots, backend: str,
                    parents: bool, alpha: int, beta: int):
    """(cond, body) of the fused superstep loop — ONE implementation shared
    by the jitted ``lax.while_loop`` and the traced host-driven loop
    (DESIGN.md §14), so the traced path cannot drift from production."""
    q = dst_slots.shape[0]
    v = state.capacity
    alive = state.valive
    hybrid = backend in HYBRID_BACKENDS
    is_packed = backend in PACKED_BACKENDS or hybrid
    if hybrid:
        push_fn, pull_fn = _get_hybrid_multi_step_fns(backend)
        adj_arg = state.adj_packed
        adj_in_arg = state.adj_in_packed
    else:
        step_fn = _get_multi_step_fn(backend)
        adj_arg = state.adj_packed if is_packed else state.adj
    if not parents:
        # closure-only expansion operand, hoisted out of the superstep loop:
        # traversable words for the packed path, the float32 traversable
        # matrix for the MXU path (DESIGN.md §9, §10)
        closure_op = (
            traversable_packed(state.adj_packed, alive, pack_bits(alive))
            if is_packed else
            traversable(state.adj, alive).astype(jnp.float32))

    def _active(frontiers, visited, step):
        # mirrors the single-query cond, evaluated per query
        hit_dst = (dst_slots >= 0) & visited[jnp.arange(q), jnp.maximum(dst_slots, 0)]
        return jnp.any(frontiers, axis=1) & ~hit_dst & (step < v)

    def cond(c):
        frontiers, visited, parent, dist, expanded, steps, step = c[:7]
        return jnp.any(_active(frontiers, visited, step))

    def body(c):
        frontiers, visited, parent, dist, expanded, steps, step = c[:7]
        act = _active(frontiers, visited, step)
        # early-exit masking: finished queries expose an all-empty frontier,
        # so their tiles are skipped by the kernel's @pl.when fast path and
        # their parent/dist/expanded stay frozen exactly as if their own
        # single-query loop had terminated.
        f = frontiers & act[:, None]
        if hybrid:
            # pooled direction decision over the active queries: finished
            # queries contribute empty frontiers and nothing to nu
            nf = jnp.sum(f.astype(jnp.int32))
            nu = jnp.sum(((alive[None, :] & ~visited)
                          & act[:, None]).astype(jnp.int32))
            pulling = pick_direction(c[7], nf, nu, q * v, alpha, beta)
        expanded = expanded | f
        if parents:
            if hybrid:
                new, par = jax.lax.cond(
                    pulling,
                    lambda ff, vis: pull_fn(ff, adj_in_arg, alive, vis),
                    lambda ff, vis: push_fn(ff, adj_arg, alive, vis),
                    f, visited)
            else:
                new, par = step_fn(f, adj_arg, alive, visited)
            parent = jnp.where(new, par, parent)
        elif hybrid:
            def _push_closure(ff, vis):
                sel = jnp.where(ff[:, :, None], closure_op[None, :, :],
                                jnp.uint32(0))
                return unpack_bits(or_reduce(sel, 1), v) & ~vis

            def _pull_closure(ff, vis):
                fw = pack_bits(ff & alive[None, :])
                cand = adj_in_arg[None, :, :] & fw[:, None, :]
                return jnp.any(cand != 0, axis=2) & alive[None, :] & ~vis

            new = jax.lax.cond(pulling, _pull_closure, _push_closure,
                               f, visited)
        elif is_packed:
            sel = jnp.where(f[:, :, None], closure_op[None, :, :],
                            jnp.uint32(0))
            new = unpack_bits(or_reduce(sel, 1), v) & ~visited
        else:
            new = ((f.astype(jnp.float32) @ closure_op) > 0) & ~visited
        dist = jnp.where(new, step + 1, dist)
        visited = visited | new
        steps = steps + act.astype(jnp.int32)
        out = (new, visited, parent, dist, expanded, steps, step + 1)
        return out + (pulling,) if hybrid else out

    return cond, body


def _multi_result(final, src_ok, dst_slots) -> MultiBFSResult:
    frontiers, visited, parent, dist, expanded, steps, supersteps = final[:7]
    q = visited.shape[0]
    found = (dst_slots >= 0) & visited[jnp.arange(q), jnp.maximum(dst_slots, 0)] & src_ok
    return MultiBFSResult(found, parent, dist, expanded, steps, supersteps)


@functools.partial(jax.jit,
                   static_argnames=("backend", "parents", "alpha", "beta"))
def _multi_bfs_jit(state: GraphState, src_slots, dst_slots, backend: str,
                   parents: bool, alpha: int,
                   beta: int) -> MultiBFSResult:
    src_slots = jnp.asarray(src_slots, jnp.int32)
    dst_slots = jnp.asarray(dst_slots, jnp.int32)
    hybrid = backend in HYBRID_BACKENDS
    init, src_ok = _multi_init(state, src_slots, dst_slots, hybrid)
    cond, body = _multi_step_fns(state, dst_slots, backend, parents,
                                 alpha, beta)
    final = jax.lax.while_loop(cond, body, init)
    return _multi_result(final, src_ok, dst_slots)


@functools.partial(jax.jit,
                   static_argnames=("backend", "parents", "alpha", "beta"))
def _multi_superstep_jit(state: GraphState, dst_slots, carry, backend: str,
                         parents: bool, alpha: int, beta: int):
    """ONE fused superstep — the traced host loop's jitted unit of work.
    Applies the same ``body`` the while_loop runs (DESIGN.md §14)."""
    _, body = _multi_step_fns(state, dst_slots, backend, parents,
                              alpha, beta)
    return body(carry)


def _multi_bfs_traced(state: GraphState, src_slots, dst_slots, *,
                      backend: str, parents: bool, alpha: int,
                      beta: int) -> MultiBFSResult:
    """Host-driven superstep loop under the tracing recorder
    (DESIGN.md §14): bit-identical to ``_multi_bfs_jit`` (same init, same
    superstep body, same termination predicate), but each superstep is one
    jitted call fenced by ``jax.block_until_ready`` and recorded as a
    ``bfs.superstep`` span with its direction tag and frontier/unvisited
    popcounts — the push/pull decision trail the Perfetto trace makes
    navigable. Never runs inside an enclosing jit (see ``multi_bfs``).
    """
    reg = _obs_registry()
    src_slots = jnp.asarray(src_slots, jnp.int32)
    dst_slots = jnp.asarray(dst_slots, jnp.int32)
    hybrid = backend in HYBRID_BACKENDS
    carry, src_ok = _multi_init(state, src_slots, dst_slots, hybrid)
    q = int(src_slots.shape[0])
    v = int(state.capacity)
    dst_np = np.asarray(dst_slots)
    alive_np = np.asarray(state.valive)
    last_dir = None
    with _trace.span("bfs.session", queries=q, capacity=v,
                     backend=backend, parents=parents) as session:
        while True:
            # the while_loop cond, evaluated host-side on materialized carry
            frontiers = np.asarray(carry[0])
            visited = np.asarray(carry[1])
            step = int(carry[6])
            hit_dst = (dst_np >= 0) & visited[np.arange(q),
                                             np.maximum(dst_np, 0)]
            act = frontiers.any(axis=1) & ~hit_dst & (step < v)
            if not act.any():
                break
            nf = int(frontiers[act].sum())
            nu = int(((alive_np[None, :] & ~visited) & act[:, None]).sum())
            with _trace.span("bfs.superstep", step=step, frontier_pop=nf,
                             unvisited_pop=nu) as sp:
                carry = _multi_superstep_jit(state, dst_slots, carry,
                                             backend=backend,
                                             parents=parents, alpha=alpha,
                                             beta=beta)
                _trace.fence(carry)
                # the carried ``pulling`` flag IS the decision this
                # superstep executed — read it back, never re-derive it
                direction = ("pull" if hybrid and bool(carry[7])
                             else "push")
                sp.set(direction=direction)
            reg.inc("bfs.supersteps")
            if direction == "pull":
                reg.inc("bfs.pull_supersteps")
            if last_dir is not None and direction != last_dir:
                reg.inc("bfs.direction_flips")
            last_dir = direction
        session.set(supersteps=int(carry[6]))
    return _multi_result(carry, src_ok, dst_slots)
