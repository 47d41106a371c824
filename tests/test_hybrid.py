"""Direction-optimizing BFS + maintained in-adjacency suite (DESIGN.md §11).

Four contracts:

  1. Transpose invariant: ``adj_in_packed == pack_transpose(adj_packed)``
     after ARBITRARY interleaved AddVertex/RemoveVertex/AddEdge/RemoveEdge
     streams with grow/compact (and undirected ops), on dense AND
     mesh-sharded state — the in-adjacency is maintained by mirrored RMWs,
     never derived, so this is the property that keeps every pull-side
     consumer (hybrid BFS, index backward closures, degree) honest.
  2. All SIX BFS backends (jnp, pallas, packed, packed_pallas, hybrid,
     hybrid_pallas) bit-identical to one numpy oracle, parents included.
  3. The index's reverse graph is an O(1) FIELD SWAP and the rebuilt index
     is bit-identical to the deleted unpack→T→repack oracle path on a
     randomized mutation stream (regression for ``_transposed``'s removal).
  4. ``default_backend()`` resolves to "hybrid" (env-overridable) and every
     threaded call site defaults to it (``backend=None``).
"""
import inspect

import numpy as np

import jax.numpy as jnp
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # container without hypothesis: deterministic fallback
    from repro.testing.proptest import given, settings, strategies as st

from repro.core import (
    OP_ADD_E, OP_ADD_V, OP_REM_E, OP_REM_V,
    apply_ops, apply_ops_fast, find_slots, make_graph, make_op_batch,
    multi_bfs, pack_transpose, transpose_invariant,
)
from repro.core import bfs as bfs_mod
from repro.core import partition, snapshot
from repro.core.bfs import (
    HYBRID_BACKENDS, bfs, ctz32, default_backend, pick_direction,
    reachable_count,
)
from repro.core.distributed import make_graph_mesh
from repro.core.graph import grow as dense_grow
from repro.core.ops import add_edge_undirected, compact as dense_compact
from repro.core.ops import remove_edge_undirected
from repro.index import labels as labels_mod
from repro.index.freshness import reach_counts_session, refresh
from repro.index.labels import build_index

RNG = np.random.default_rng(23)
CAP = 32
ALL_BACKENDS = ("jnp", "pallas", "packed", "packed_pallas") + HYBRID_BACKENDS


def _random_state(nv=12, cap=CAP, n_edges=40, n_dead=3, seed=0):
    rng = np.random.default_rng(seed)
    g = make_graph(cap)
    ops = [(OP_ADD_V, k) for k in range(nv)]
    ops += [(OP_ADD_E, int(a), int(b))
            for a, b in rng.integers(0, nv, (n_edges, 2))]
    g, _ = apply_ops(g, make_op_batch(ops))
    dead = rng.choice(nv, size=n_dead, replace=False)
    g, _ = apply_ops(g, make_op_batch([(OP_REM_V, int(k)) for k in dead]))
    return g


# ----------------------------------------------------------------------------
# helpers under test
# ----------------------------------------------------------------------------
def test_ctz32_matches_numpy():
    x = np.r_[RNG.integers(1, 2**32, 200), [1, 2**31, 2**32 - 1]] \
        .astype(np.uint32)
    got = np.asarray(ctz32(jnp.asarray(x)))
    want = np.array([int(v & -v).bit_length() - 1 for v in x.astype(object)])
    np.testing.assert_array_equal(got, want)
    # zero words report 32 (callers mask them out)
    assert int(ctz32(jnp.asarray([0], dtype=jnp.uint32))[0]) == 32


def test_pick_direction_thresholds():
    # sparse frontier from push mode stays push
    assert not bool(pick_direction(jnp.asarray(False), jnp.int32(1),
                                   jnp.int32(100), 128, 4, 24))
    # dense frontier trips the alpha threshold
    assert bool(pick_direction(jnp.asarray(False), jnp.int32(30),
                               jnp.int32(100), 128, 4, 24))
    # hysteresis: in pull mode we stay until the frontier shrinks below V/beta
    assert bool(pick_direction(jnp.asarray(True), jnp.int32(10),
                               jnp.int32(100), 128, 4, 24))
    assert not bool(pick_direction(jnp.asarray(True), jnp.int32(2),
                                   jnp.int32(100), 128, 4, 24))


def test_pull_kernel_matches_ref():
    from repro.kernels.bfs_pull_step.kernel import bfs_pull_step_pallas
    from repro.kernels.bfs_pull_step.ref import bfs_pull_step_ref

    rng = np.random.default_rng(7)
    q, r, w = 8, 64, 2
    fw = jnp.asarray(rng.integers(0, 2**32, (q, w), dtype=np.uint32))
    adjin = jnp.asarray(rng.integers(0, 2**32, (r, w), dtype=np.uint32))
    alive = jnp.asarray(rng.random(r) < 0.8).astype(jnp.int32)
    vis = jnp.asarray(rng.random((q, r)) < 0.3).astype(jnp.int32)
    want = bfs_pull_step_ref(fw, adjin, alive, vis)
    for tr in (32, 64):  # several row tiles, and one tile over all rows
        got = bfs_pull_step_pallas(fw, adjin, alive, vis, tr=tr)
        for name, a, b in zip(("new", "parent"), got, want):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=name)


# ----------------------------------------------------------------------------
# 1. Transpose invariant under arbitrary op streams (dense + sharded)
# ----------------------------------------------------------------------------
KEYS = st.integers(min_value=0, max_value=9)
OPC = st.sampled_from([OP_ADD_V, OP_REM_V, OP_ADD_E, OP_REM_E])
OP = st.tuples(OPC, KEYS, KEYS)
STREAM = st.lists(st.lists(OP, min_size=1, max_size=8), min_size=1, max_size=3)


@settings(max_examples=8, deadline=None)
@given(STREAM)
def test_transpose_invariant_over_mutation_stream(op_lists):
    mesh = make_graph_mesh()
    g = make_graph(CAP)
    gs = partition.shard_state(mesh, g)
    seedb = make_op_batch([(OP_ADD_V, k) for k in range(8)])
    g, _ = apply_ops_fast(g, seedb)
    gs, _ = partition.apply_ops_fast(gs, seedb)
    for step, ops in enumerate(op_lists):
        batch = make_op_batch([(op, a, b, -1) for (op, a, b) in ops])
        g, _ = apply_ops_fast(g, batch)
        gs, _ = partition.apply_ops_fast(gs, batch)
        if step == 1:  # exercise grow + compact mid-stream
            g = dense_grow(dense_compact(g), CAP * 2)
            gs = partition.grow(partition.compact(gs), CAP * 2)
        assert bool(transpose_invariant(g)), f"dense, step {step}"
        assert bool(transpose_invariant(partition.unshard(gs))), \
            f"sharded, step {step}"
    # serial reference engine + undirected extension preserve it too
    g, _ = apply_ops(g, make_op_batch([(OP_ADD_E, 0, 5), (OP_REM_V, 1),
                                       (OP_ADD_V, 1)]))
    g, _ = add_edge_undirected(g, 0, 5)
    assert bool(transpose_invariant(g))
    g, _ = remove_edge_undirected(g, 0, 5)
    assert bool(transpose_invariant(g))


# ----------------------------------------------------------------------------
# 2. Six-backend bit-identity against one numpy oracle (parents included)
# ----------------------------------------------------------------------------
def _np_traversable(g):
    adj = np.asarray(g.adj) > 0
    alive = np.asarray(g.valive)
    return adj & alive[:, None] & alive[None, :]


def _np_bfs_full(t, alive, src):
    """(dist, parent) of a full-exploration BFS with smallest-frontier-index
    parents — the per-step contract every backend implements."""
    v = t.shape[0]
    dist = np.full(v, -1, np.int32)
    parent = np.full(v, -1, np.int32)
    if src < 0 or not alive[src]:
        return dist, parent
    dist[src] = 0
    frontier = np.zeros(v, bool)
    frontier[src] = True
    visited = frontier.copy()
    d = 0
    while frontier.any():
        new = t[frontier].any(axis=0) & ~visited
        for j in np.nonzero(new)[0]:
            parent[j] = np.nonzero(frontier & t[:, j])[0].min()
        dist[new] = d + 1
        visited |= new
        frontier = new
        d += 1
    return dist, parent


def _assert_backends_match_oracle(g, srcs):
    t = _np_traversable(g)
    alive = np.asarray(g.valive)
    want = [_np_bfs_full(t, alive, int(s)) for s in srcs]
    dsts = jnp.full((len(srcs),), -1, jnp.int32)
    ref = None
    for backend in ALL_BACKENDS:
        m = multi_bfs(g, jnp.asarray(srcs, jnp.int32), dsts, backend=backend)
        for qi, (dist, parent) in enumerate(want):
            np.testing.assert_array_equal(np.asarray(m.dist[qi]), dist,
                                          err_msg=f"{backend} dist q{qi}")
            np.testing.assert_array_equal(np.asarray(m.parent[qi]), parent,
                                          err_msg=f"{backend} parent q{qi}")
        r = bfs(g, jnp.int32(int(srcs[0])), jnp.int32(-1), backend=backend)
        np.testing.assert_array_equal(np.asarray(r.dist), want[0][0],
                                      err_msg=f"{backend} bfs dist")
        np.testing.assert_array_equal(np.asarray(r.parent), want[0][1],
                                      err_msg=f"{backend} bfs parent")
        if ref is None:
            ref = m
        else:  # full-result bit-identity (expanded/steps/supersteps too)
            for name, xa, xb in zip(ref._fields, ref, m):
                np.testing.assert_array_equal(
                    np.asarray(xa), np.asarray(xb),
                    err_msg=f"{backend} field {name}")


def test_six_backends_bit_identical_vs_numpy_oracle():
    g = _random_state(seed=13)
    srcs = np.nonzero(np.asarray(g.valive))[0][:8].astype(np.int32)
    _assert_backends_match_oracle(g, srcs)


@pytest.mark.slow
def test_six_backends_large_v_dense_frontier():
    """Large-V variant: a dense random digraph whose frontier covers most of
    the graph after one hop, forcing the hybrid backends through BOTH
    directions (push on step 1, pull once the alpha threshold trips)."""
    rng = np.random.default_rng(31)
    nv, cap = 180, 256
    g = make_graph(cap)
    ops = [(OP_ADD_V, k) for k in range(nv)]
    g, _ = apply_ops_fast(g, make_op_batch(ops))
    edges = [(OP_ADD_E, int(a), int(b))
             for a, b in rng.integers(0, nv, (nv * 8, 2))]
    for i in range(0, len(edges), 256):
        g, _ = apply_ops_fast(g, make_op_batch(edges[i:i + 256], 256))
    g, _ = apply_ops_fast(
        g, make_op_batch([(OP_REM_V, int(k))
                          for k in rng.choice(nv, 12, replace=False)]))
    srcs = np.nonzero(np.asarray(g.valive))[0][:8].astype(np.int32)
    _assert_backends_match_oracle(g, srcs)


def test_hybrid_closure_mode_and_sharded_bit_identical():
    g = _random_state(seed=17)
    mesh = make_graph_mesh()
    gs = partition.shard_state(mesh, g)
    srcs = np.nonzero(np.asarray(g.valive))[0][:8].astype(np.int32)
    sj = jnp.asarray(srcs, jnp.int32)
    dsts = jnp.full((len(srcs),), -1, jnp.int32)
    ref = multi_bfs(g, sj, dsts, backend="jnp")
    for backend in HYBRID_BACKENDS:
        c = multi_bfs(g, sj, dsts, backend=backend, parents=False)
        np.testing.assert_array_equal(np.asarray(c.dist), np.asarray(ref.dist),
                                      err_msg=f"{backend} closure dist")
        assert (np.asarray(c.parent) == -1).all()
        s = partition.multi_bfs(gs, sj, dsts, backend=backend)
        for name, xa, xb in zip(ref._fields, ref, s):
            np.testing.assert_array_equal(np.asarray(xa), np.asarray(xb),
                                          err_msg=f"sharded {backend} {name}")


# ----------------------------------------------------------------------------
# 3. Index: reverse graph is a field swap; rebuilt index == transpose oracle
# ----------------------------------------------------------------------------
def test_reversed_is_an_O1_field_swap():
    g = _random_state(seed=19)
    rev = labels_mod._reversed(g)
    assert rev.adj_packed is g.adj_in_packed   # aliased, not recomputed
    assert rev.adj_in_packed is g.adj_packed
    np.testing.assert_array_equal(
        np.asarray(rev.adj_packed),
        np.asarray(pack_transpose(g.adj_packed, g.capacity)))


def test_index_bit_identical_to_pre_deletion_transpose_oracle(monkeypatch):
    """The deleted ``_transposed`` oracle path (unpack → T → repack) must
    produce the exact same index as the maintained-in-adjacency build, on a
    randomized mutation stream including refresh."""
    rng = np.random.default_rng(41)
    g = make_graph(CAP)
    g, _ = apply_ops_fast(g, make_op_batch(
        [(OP_ADD_V, k) for k in range(10)]))

    def transpose_oracle(state):  # the pre-deletion implementation
        return state._replace(
            adj_packed=pack_transpose(state.adj_packed, state.capacity),
            adj_in_packed=pack_transpose(state.adj_in_packed,
                                         state.capacity))

    for step in range(3):
        ops = [(int(rng.choice([OP_ADD_E, OP_REM_E, OP_REM_V, OP_ADD_V])),
                int(rng.integers(0, 10)), int(rng.integers(0, 10)))
               for _ in range(8)]
        g, _ = apply_ops_fast(g, make_op_batch(ops))
        new_idx = build_index(g)
        with monkeypatch.context() as mp:
            mp.setattr(labels_mod, "_reversed", transpose_oracle)
            oracle_idx = build_index(g)
        for name, xa, xb in zip(new_idx._fields, new_idx, oracle_idx):
            np.testing.assert_array_equal(
                np.asarray(xa), np.asarray(xb),
                err_msg=f"step {step} field {name}")
    # refresh stays bit-identical to a rebuild PINNED to the landmark set
    # the refreshed index actually carries — a valid oracle for BOTH the
    # incremental and the full path, so the comparison is never vacuous
    idx = build_index(g)
    g, _ = apply_ops_fast(g, make_op_batch([(OP_ADD_E, 2, 6),
                                            (OP_REM_V, 4)]))
    idx2, info = refresh(idx, g)
    assert info["mode"] != "noop"
    full = build_index(g, landmark_slots=np.asarray(idx2.landmarks))
    for name, xa, xb in zip(idx2._fields, idx2, full):
        if name == "requested":  # landmark-budget metadata, not index state
            continue
        np.testing.assert_array_equal(np.asarray(xa), np.asarray(xb),
                                      err_msg=f"refresh field {name}")


# ----------------------------------------------------------------------------
# 4. default_backend resolution + threading
# ----------------------------------------------------------------------------
def test_default_backend_resolution(monkeypatch):
    assert default_backend() == "hybrid"
    monkeypatch.setenv("REPRO_BFS_BACKEND", "packed")
    assert default_backend() == "packed"
    monkeypatch.delenv("REPRO_BFS_BACKEND")
    assert default_backend() == "hybrid"


def test_default_backend_threaded_everywhere():
    """Every traversal surface defaults its ``backend`` to None, i.e. to
    ``default_backend()`` — the fastest engine is the default everywhere."""
    from repro.data.pathgen import PathTaskGenerator
    from repro.index.freshness import affected_landmarks, reach_session
    from repro.index.labels import rebuild_rows

    sites = [bfs, multi_bfs, reachable_count, partition.multi_bfs,
             snapshot.collect, snapshot.get_path, snapshot.collect_batch,
             snapshot.get_paths_session, snapshot.get_path_session,
             snapshot.interleaved_getpath, build_index, rebuild_rows,
             refresh, affected_landmarks, reach_session,
             reach_counts_session, PathTaskGenerator.__init__]
    for fn in sites:
        target = getattr(fn, "__wrapped__", fn)
        default = inspect.signature(target).parameters["backend"].default
        assert default is None, f"{fn} does not thread default_backend()"


def test_default_backend_results_match_explicit_hybrid():
    g = _random_state(seed=29)
    srcs = np.nonzero(np.asarray(g.valive))[0][:4].astype(np.int32)
    sj = jnp.asarray(srcs, jnp.int32)
    dsts = jnp.full((4,), -1, jnp.int32)
    a = multi_bfs(g, sj, dsts)                       # default → hybrid
    b = multi_bfs(g, sj, dsts, backend="hybrid")
    for name, xa, xb in zip(a._fields, a, b):
        np.testing.assert_array_equal(np.asarray(xa), np.asarray(xb),
                                      err_msg=name)
    n = reachable_count(g, jnp.int32(int(srcs[0])))
    r = bfs(g, jnp.int32(int(srcs[0])), jnp.int32(-1), backend="jnp")
    assert int(n) == int((np.asarray(r.dist) >= 0).sum())
    keys = np.asarray(g.vkey)[srcs]
    pairs = [(int(keys[0]), int(keys[1])), (int(keys[2]), int(keys[3]))]
    out, _rounds = snapshot.get_paths_session(lambda: g, pairs)
    ref = snapshot.get_paths_session(lambda: g, pairs, backend="jnp")[0]
    assert [f for f, _ in out] == [f for f, _ in ref]
