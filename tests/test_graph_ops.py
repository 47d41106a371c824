"""Unit tests: ADT semantics of the concurrent graph (paper §2.1)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    OP_ADD_E, OP_ADD_V, OP_CON_E, OP_CON_V, OP_NOP, OP_REM_E, OP_REM_V,
    R_CAS_FAIL, R_EDGE_ADDED, R_EDGE_NOT_PRESENT, R_EDGE_PRESENT,
    R_EDGE_REMOVED, R_FALSE, R_TABLE_FULL, R_TRUE, R_VERTEX_NOT_PRESENT,
    add_edge, add_vertex, apply_ops, apply_ops_fast, compact, contains_edge,
    contains_vertex, grow, make_graph, make_op_batch, num_edges, num_vertices,
    remove_edge, remove_vertex,
)


def build(keys=(), edges=()):
    g = make_graph(32)
    for k in keys:
        g, r = add_vertex(g, k)
        assert int(r) == R_TRUE
    for (a, b) in edges:
        g, r = add_edge(g, a, b)
        assert int(r) == R_EDGE_ADDED
    return g


def test_add_vertex_semantics():
    g = build()
    g, r = add_vertex(g, 5)
    assert int(r) == R_TRUE
    g, r = add_vertex(g, 5)            # duplicate -> false (paper ADT 1)
    assert int(r) == R_FALSE
    assert bool(contains_vertex(g, 5))
    assert not bool(contains_vertex(g, 6))


def test_remove_vertex_removes_incident_edges():
    g = build([1, 2, 3], [(1, 2), (2, 3), (3, 1)])
    g, r = remove_vertex(g, 2)
    assert int(r) == R_TRUE
    # paper ADT 2: all (j,2), (2,l) logically removed
    assert int(contains_edge(g, 1, 2)) == R_VERTEX_NOT_PRESENT
    assert int(contains_edge(g, 3, 1)) == R_EDGE_PRESENT
    assert int(num_vertices(g)) == 2 and int(num_edges(g)) == 1
    g, r = remove_vertex(g, 2)
    assert int(r) == R_FALSE


def test_edge_requires_both_vertices():
    g = build([1])
    g, r = add_edge(g, 1, 9)
    assert int(r) == R_VERTEX_NOT_PRESENT
    g, r = remove_edge(g, 9, 1)
    assert int(r) == R_VERTEX_NOT_PRESENT


def test_edge_add_remove_cycle():
    g = build([1, 2])
    g, r = add_edge(g, 1, 2)
    assert int(r) == R_EDGE_ADDED
    g, r = add_edge(g, 1, 2)
    assert int(r) == R_EDGE_PRESENT
    g, r = remove_edge(g, 1, 2)
    assert int(r) == R_EDGE_REMOVED
    g, r = remove_edge(g, 1, 2)
    assert int(r) == R_EDGE_NOT_PRESENT


def test_ecnt_faa_on_edge_mutations():
    """The paper's FetchAndAdd on ecnt (lines 57/93): one bump per effective op."""
    g = build([1, 2])
    s1 = int(g.ecnt[0])
    g, _ = add_edge(g, 1, 2)
    g, _ = add_edge(g, 1, 2)  # EDGE PRESENT: no bump
    g, _ = remove_edge(g, 1, 2)
    slot = int(np.argmax(np.asarray(g.vkey) == 1))
    assert int(g.ecnt[slot]) == s1 + 2


def test_versioned_cas_semantics():
    g = build([1, 2])
    slot = int(np.argmax(np.asarray(g.vkey) == 1))
    cur = int(g.ecnt[slot])
    ops = make_op_batch([(OP_ADD_E, 1, 2, cur + 7)])
    g, res = apply_ops(g, ops)
    assert int(res[0]) == R_CAS_FAIL              # stale expectation
    ops = make_op_batch([(OP_ADD_E, 1, 2, cur)])
    g, res = apply_ops(g, ops)
    assert int(res[0]) == R_EDGE_ADDED            # matching expectation


def test_capacity_and_grow_unbounded():
    g = make_graph(4)
    for k in range(4):
        g, r = add_vertex(g, k)
        assert int(r) == R_TRUE
    g, r = add_vertex(g, 99)
    assert int(r) == R_TABLE_FULL
    g = grow(g, 8)                                 # the 'unbounded' part
    g, r = add_vertex(g, 99)
    assert int(r) == R_TRUE
    assert int(num_vertices(g)) == 5


def test_compact_frees_slots_and_preserves_live_edges():
    g = make_graph(4)
    for k in range(4):
        g, _ = add_vertex(g, k)
    g, _ = add_edge(g, 0, 1)
    g, _ = remove_vertex(g, 2)
    g, r = add_vertex(g, 7)
    assert int(r) == R_TABLE_FULL                  # dead slot still occupied
    g = compact(g)                                 # physical removal (helping)
    g, r = add_vertex(g, 7)
    assert int(r) == R_TRUE
    assert int(contains_edge(g, 0, 1)) == R_EDGE_PRESENT


def test_vertex_readd_gets_fresh_edges():
    g = build([1, 2], [(1, 2)])
    g, _ = remove_vertex(g, 1)
    g, r = add_vertex(g, 1)
    assert int(r) == R_TRUE
    assert int(contains_edge(g, 1, 2)) == R_EDGE_NOT_PRESENT  # no stale ENodes


def test_engines_match_on_conflicting_batch():
    ops = make_op_batch([
        (OP_ADD_V, 1), (OP_ADD_V, 1), (OP_ADD_V, 2), (OP_ADD_E, 1, 2),
        (OP_REM_V, 1), (OP_ADD_E, 1, 2), (OP_CON_V, 1), (OP_CON_E, 1, 2),
    ])
    g1, r1 = apply_ops(make_graph(16), ops)
    g2, r2 = apply_ops_fast(make_graph(16), ops)
    np.testing.assert_array_equal(np.asarray(r1), np.asarray(r2))
    for f in g1._fields:
        np.testing.assert_array_equal(np.asarray(getattr(g1, f)),
                                      np.asarray(getattr(g2, f)))


def test_correction_pass_applies_only_the_selected_lanes():
    """The serial pass visits the masked lanes in lane order and leaves the
    others' results as given: the same as the reference engine on a batch
    whose unselected lanes are NOPs."""
    from repro.core.ops import _serial_masked

    g = build([1, 2, 3], [(1, 2), (3, 1)])
    lanes = [(OP_REM_V, 9), (OP_REM_V, 1), (OP_ADD_V, 7), (OP_ADD_E, 3, 2),
             (OP_ADD_V, 8), (OP_REM_E, 3, 1)]
    mask = [False, True, False, True, False, True]
    res0 = jnp.full((len(lanes),), -7, jnp.int32)
    got, res = _serial_masked(g, make_op_batch(lanes), jnp.asarray(mask),
                              res0)
    want, res_want = apply_ops(g, make_op_batch(
        [op if m else (OP_NOP,) for op, m in zip(lanes, mask)]))
    res, res_want = np.asarray(res), np.asarray(res_want)
    np.testing.assert_array_equal(res[~np.asarray(mask)], -7)
    np.testing.assert_array_equal(res[np.asarray(mask)],
                                  res_want[np.asarray(mask)])
    for f in g._fields:
        np.testing.assert_array_equal(np.asarray(getattr(got, f)),
                                      np.asarray(getattr(want, f)))
