"""Pallas kernel validation: shape/dtype sweeps, assert_allclose vs ref.py."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.graph import pack_bits
from repro.kernels.bfs_step.ops import bfs_step_pallas
from repro.kernels.bfs_step.ops import bfs_step, bfs_step_packed
from repro.kernels.bfs_step.ref import bfs_step_ref
from repro.kernels.bfs_multi_step.kernel import multi_bfs_step_pallas
from repro.kernels.bfs_multi_step.ops import (
    multi_bfs_step,
    multi_bfs_step_packed,
)
from repro.kernels.bfs_multi_step.ref import multi_bfs_step_ref
from repro.kernels.edge_update.kernel import edge_update_pallas
from repro.kernels.edge_update.ops import edge_update, edge_update_packed
from repro.kernels.edge_update.ref import edge_update_packed_ref, edge_update_ref

RNG = np.random.default_rng(42)


def _graph_inputs(v, density, adtype):
    adj = (RNG.random((v, v)) < density).astype(adtype)
    frontier = RNG.random(v) < 0.15
    alive = RNG.random(v) < 0.9
    visited = frontier | (RNG.random(v) < 0.2)
    return adj, frontier, alive, visited


@pytest.mark.parametrize("v", [16, 64, 128, 256, 512])
@pytest.mark.parametrize("density", [0.0, 0.05, 0.5])
def test_bfs_step_shapes(v, density):
    adj, frontier, alive, visited = _graph_inputs(v, density, np.uint8)
    nf_k, par_k = bfs_step(jnp.asarray(frontier), jnp.asarray(adj),
                           jnp.asarray(alive), jnp.asarray(visited))
    nf_r, par_r = bfs_step_ref(jnp.asarray(frontier, jnp.float32), jnp.asarray(adj),
                               jnp.asarray(alive, jnp.int32),
                               jnp.asarray(visited, jnp.int32))
    np.testing.assert_allclose(np.asarray(nf_k, np.int32), np.asarray(nf_r))
    np.testing.assert_allclose(np.asarray(par_k), np.asarray(par_r))


@pytest.mark.parametrize("adtype", [np.uint8, np.int8])
def test_bfs_step_dtypes(adtype):
    adj, frontier, alive, visited = _graph_inputs(128, 0.05, adtype)
    nf_k, par_k = bfs_step_pallas(
        jnp.asarray(frontier, jnp.float32), jnp.asarray(adj),
        jnp.asarray(alive, jnp.int32), jnp.asarray(visited, jnp.int32),
        tr=64, tc=64)
    nf_r, par_r = bfs_step_ref(
        jnp.asarray(frontier, jnp.float32), jnp.asarray(adj),
        jnp.asarray(alive, jnp.int32), jnp.asarray(visited, jnp.int32))
    np.testing.assert_allclose(np.asarray(nf_k), np.asarray(nf_r))
    np.testing.assert_allclose(np.asarray(par_k), np.asarray(par_r))


@pytest.mark.parametrize("tr,tc", [(8, 8), (32, 128), (128, 32), (128, 128)])
def test_bfs_step_block_shapes(tr, tc):
    v = 256
    adj, frontier, alive, visited = _graph_inputs(v, 0.05, np.uint8)
    nf_k, par_k = bfs_step_pallas(
        jnp.asarray(frontier, jnp.float32), jnp.asarray(adj),
        jnp.asarray(alive, jnp.int32), jnp.asarray(visited, jnp.int32),
        tr=tr, tc=tc)
    nf_r, par_r = bfs_step_ref(
        jnp.asarray(frontier, jnp.float32), jnp.asarray(adj),
        jnp.asarray(alive, jnp.int32), jnp.asarray(visited, jnp.int32))
    np.testing.assert_allclose(np.asarray(nf_k), np.asarray(nf_r))
    np.testing.assert_allclose(np.asarray(par_k), np.asarray(par_r))


def test_bfs_step_empty_frontier():
    v = 128
    adj = (RNG.random((v, v)) < 0.1).astype(np.uint8)
    nf, par = bfs_step(jnp.zeros(v, bool), jnp.asarray(adj),
                       jnp.ones(v, bool), jnp.zeros(v, bool))
    assert not bool(jnp.any(nf))
    assert bool(jnp.all(par == -1))


def _multi_inputs(q, v, density):
    adj = (RNG.random((v, v)) < density).astype(np.uint8)
    f = (RNG.random((q, v)) < 0.15).astype(np.float32)
    alive = (RNG.random(v) < 0.9).astype(np.int32)
    visited = ((f > 0) | (RNG.random((q, v)) < 0.2)).astype(np.int32)
    return [jnp.asarray(x) for x in (f, adj, alive, visited)]


@pytest.mark.parametrize("q", [1, 8, 64])
@pytest.mark.parametrize("v", [64, 256])
@pytest.mark.parametrize("density", [0.0, 0.05, 0.5])
def test_multi_bfs_step_shapes(q, v, density):
    f, adj, alive, visited = _multi_inputs(q, v, density)
    nf_k, par_k = multi_bfs_step(f > 0, adj, alive > 0, visited > 0)
    nf_r, par_r = multi_bfs_step_ref(f, adj, alive, visited)
    np.testing.assert_allclose(np.asarray(nf_k, np.int32), np.asarray(nf_r))
    np.testing.assert_allclose(np.asarray(par_k), np.asarray(par_r))


@pytest.mark.parametrize("tr,tc", [(32, 32), (32, 128), (128, 32)])
def test_multi_bfs_step_block_shapes(tr, tc):
    f, adj, alive, visited = _multi_inputs(8, 128, 0.05)
    nf_k, par_k = multi_bfs_step_pallas(f, adj, alive, visited, tr=tr, tc=tc)
    nf_r, par_r = multi_bfs_step_ref(f, adj, alive, visited)
    np.testing.assert_allclose(np.asarray(nf_k), np.asarray(nf_r))
    np.testing.assert_allclose(np.asarray(par_k), np.asarray(par_r))


def test_multi_bfs_step_parent_loop_fallback():
    """Parent extraction runs one query of the slab at a time (a fori_loop
    that keeps one [TR, TC] candidate slice live, the VMEM bound); a
    16-query slab over several row and column tiles must agree with the
    ref."""
    f, adj, alive, visited = _multi_inputs(16, 128, 0.08)
    ref = multi_bfs_step_ref(f, adj, alive, visited)
    out = multi_bfs_step_pallas(f, adj, alive, visited, tr=64, tc=64)
    np.testing.assert_allclose(np.asarray(out[0]), np.asarray(ref[0]))
    np.testing.assert_allclose(np.asarray(out[1]), np.asarray(ref[1]))


def test_multi_bfs_step_empty_frontier():
    v, q = 128, 5
    adj = (RNG.random((v, v)) < 0.1).astype(np.uint8)
    nf, par = multi_bfs_step(jnp.zeros((q, v), bool), jnp.asarray(adj),
                             jnp.ones(v, bool), jnp.zeros((q, v), bool))
    assert not bool(jnp.any(nf))
    assert bool(jnp.all(par == -1))


@pytest.mark.parametrize("v,b", [(16, 4), (64, 32), (128, 64), (256, 256)])
def test_edge_update_shapes(v, b):
    adj = (RNG.random((v, v)) < 0.05).astype(np.uint8)
    ecnt = RNG.integers(0, 5, v).astype(np.int32)
    rows = RNG.integers(0, v, b).astype(np.int32)
    cols = RNG.integers(0, v, b).astype(np.int32)
    vals = RNG.integers(0, 2, b).astype(np.int32)
    mask = RNG.integers(0, 2, b).astype(np.int32)
    args = [jnp.asarray(x) for x in (adj, ecnt, rows, cols, vals, mask)]
    a_k, e_k = edge_update(*args)
    a_r, e_r = edge_update_ref(*args)
    np.testing.assert_allclose(np.asarray(a_k), np.asarray(a_r))
    np.testing.assert_allclose(np.asarray(e_k), np.asarray(e_r))


def test_edge_update_duplicate_targets_last_wins():
    v = 16
    adj = np.zeros((v, v), np.uint8)
    ecnt = np.zeros(v, np.int32)
    rows = np.array([3, 3, 3], np.int32)
    cols = np.array([5, 5, 5], np.int32)
    vals = np.array([1, 0, 1], np.int32)   # last lane sets 1
    mask = np.ones(3, np.int32)
    a_k, e_k = edge_update(*[jnp.asarray(x) for x in (adj, ecnt, rows, cols, vals, mask)])
    assert int(a_k[3, 5]) == 1
    assert int(e_k[3]) == 3                 # one FAA per fired op


def test_edge_update_tile_sweep():
    v, b = 64, 32
    adj = (RNG.random((v, v)) < 0.1).astype(np.uint8)
    ecnt = np.zeros(v, np.int32)
    rows = RNG.integers(0, v, b).astype(np.int32)
    cols = RNG.integers(0, v, b).astype(np.int32)
    vals = RNG.integers(0, 2, b).astype(np.int32)
    mask = np.ones(b, np.int32)
    ref = edge_update_ref(*[jnp.asarray(x) for x in (adj, ecnt, rows, cols, vals, mask)])
    for tr in (2, 4, 8, 16):
        out = edge_update_pallas(
            jnp.asarray(adj), jnp.asarray(ecnt), jnp.asarray(rows),
            jnp.asarray(cols), jnp.asarray(vals), jnp.asarray(mask), tr=tr)
        np.testing.assert_allclose(np.asarray(out[0]), np.asarray(ref[0]))
        np.testing.assert_allclose(np.asarray(out[1]), np.asarray(ref[1]))


# ----------------------------------------------------------------------------
# Packed-word kernel variants (DESIGN.md §10): the kernel and its jnp ref must
# agree with the DENSE kernel on the packed form of the same inputs — frontier
# rows restricted to alive vertices, the precondition every engine guarantees.
# ----------------------------------------------------------------------------
def _packed_graph_inputs(v, density):
    adjb = RNG.random((v, v)) < density
    alive = RNG.random(v) < 0.9
    frontier = (RNG.random(v) < 0.15) & alive
    visited = frontier | ((RNG.random(v) < 0.2) & alive)
    return adjb, frontier, alive, visited


@pytest.mark.parametrize("v", [6, 64, 200, 256])
@pytest.mark.parametrize("density", [0.0, 0.05, 0.5])
def test_bfs_step_packed_matches_dense(v, density):
    adjb, frontier, alive, visited = _packed_graph_inputs(v, density)
    nf_d, par_d = bfs_step(jnp.asarray(frontier), jnp.asarray(adjb, jnp.uint8),
                           jnp.asarray(alive), jnp.asarray(visited))
    nf_p, par_p = bfs_step_packed(jnp.asarray(frontier),
                                  pack_bits(jnp.asarray(adjb)),
                                  jnp.asarray(alive), jnp.asarray(visited))
    np.testing.assert_array_equal(np.asarray(nf_d), np.asarray(nf_p))
    np.testing.assert_array_equal(np.asarray(par_d), np.asarray(par_p))


@pytest.mark.parametrize("q,v", [(1, 64), (5, 200), (8, 256)])
def test_multi_bfs_step_packed_matches_dense(q, v):
    adjb = RNG.random((v, v)) < 0.08
    alive = RNG.random(v) < 0.9
    f = (RNG.random((q, v)) < 0.15) & alive[None, :]
    visited = f | ((RNG.random((q, v)) < 0.2) & alive[None, :])
    args_d = (jnp.asarray(f), jnp.asarray(adjb, jnp.uint8),
              jnp.asarray(alive), jnp.asarray(visited))
    nf_d, par_d = multi_bfs_step(*args_d)
    nf_p, par_p = multi_bfs_step_packed(
        jnp.asarray(f), pack_bits(jnp.asarray(adjb)),
        jnp.asarray(alive), jnp.asarray(visited))
    np.testing.assert_array_equal(np.asarray(nf_d), np.asarray(nf_p))
    np.testing.assert_array_equal(np.asarray(par_d), np.asarray(par_p))


def test_multi_bfs_step_packed_row_slice():
    """The sharded engine hands the packed kernel a contiguous ROW SLICE;
    parent ids come back slice-relative, like the dense kernel's."""
    v, rows, q = 64, 16, 4
    adjb = jnp.asarray(RNG.random((rows, v)) < 0.1)
    f = jnp.asarray(RNG.random((q, rows)) < 0.3)
    alive = jnp.asarray(RNG.random(v) < 0.9)
    visited = jnp.asarray(RNG.random((q, v)) < 0.2)
    nf_p, par_p = multi_bfs_step_packed(f, pack_bits(adjb), alive, visited)
    nf_d, par_d = multi_bfs_step(f, adjb.astype(jnp.uint8), alive, visited)
    np.testing.assert_array_equal(np.asarray(nf_p), np.asarray(nf_d))
    np.testing.assert_array_equal(np.asarray(par_p), np.asarray(par_d))


@pytest.mark.parametrize("v,b", [(16, 4), (64, 32), (128, 64)])
def test_edge_update_packed_matches_dense_and_ref(v, b):
    adjb = RNG.random((v, v)) < 0.05
    adjp = pack_bits(jnp.asarray(adjb))
    ecnt = jnp.asarray(RNG.integers(0, 5, v), jnp.int32)
    rows = jnp.asarray(RNG.integers(0, v, b), jnp.int32)
    cols = jnp.asarray(RNG.integers(0, v, b), jnp.int32)
    vals = jnp.asarray(RNG.integers(0, 2, b), jnp.int32)
    mask = jnp.asarray(RNG.integers(0, 2, b), jnp.int32)
    a_d, e_d = edge_update(jnp.asarray(adjb, jnp.uint8), ecnt,
                           rows, cols, vals, mask)
    a_p, e_p = edge_update_packed(adjp, ecnt, rows, cols, vals, mask)
    a_r, e_r = edge_update_packed_ref(adjp, ecnt, rows, cols, vals, mask)
    np.testing.assert_array_equal(
        np.asarray(pack_bits(a_d.astype(jnp.bool_))), np.asarray(a_p))
    np.testing.assert_array_equal(np.asarray(a_p), np.asarray(a_r))
    np.testing.assert_array_equal(np.asarray(e_d), np.asarray(e_p))
    np.testing.assert_array_equal(np.asarray(e_d), np.asarray(e_r))


def test_label_join_packed_matches_dense():
    from repro.kernels.label_join.ops import label_join_packed
    from repro.kernels.label_join.ref import label_join_packed_ref, label_join_ref

    for q, l in ((1, 1), (5, 7), (16, 130), (33, 256)):
        a = jnp.asarray(RNG.random((q, l)) < 0.2)
        b = jnp.asarray(RNG.random((q, l)) < 0.2)
        hd, ud = label_join_ref(a.astype(jnp.int32), b.astype(jnp.int32))
        hp, up = label_join_packed(pack_bits(a), pack_bits(b))
        hr, ur = label_join_packed_ref(pack_bits(a), pack_bits(b))
        for got_h, got_u in ((hp, up), (hr, ur)):
            np.testing.assert_array_equal(np.asarray(hd), np.asarray(got_h),
                                          err_msg=f"{q},{l}")
            np.testing.assert_array_equal(np.asarray(ud), np.asarray(got_u),
                                          err_msg=f"{q},{l}")


def test_pallas_backend_full_bfs_matches_jnp():
    from repro.core import add_edge, add_vertex, get_path, make_graph
    g = make_graph(64)
    for k in range(12):
        g, _ = add_vertex(g, k)
    for (a, b) in [(0, 1), (1, 2), (2, 3), (3, 11), (0, 5), (5, 11), (4, 0)]:
        g, _ = add_edge(g, a, b)
    for (s, d) in [(0, 11), (4, 3), (11, 0), (6, 7)]:
        pj = get_path(g, s, d, backend="jnp")
        pp = get_path(g, s, d, backend="pallas")
        assert bool(pj.found) == bool(pp.found)
        np.testing.assert_array_equal(np.asarray(pj.keys), np.asarray(pp.keys))


# ----------------------------------------------------------------------------
# The one interpret decision (kernels/mosaic.py)
# ----------------------------------------------------------------------------
def test_interpret_mode_reads_the_platform(monkeypatch):
    from repro.kernels.mosaic import interpret_mode

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert interpret_mode() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert interpret_mode() is True
    assert interpret_mode(False) is False   # an explicit choice wins
    assert interpret_mode(True) is True


def test_no_wrapper_hard_codes_interpret():
    """No ops.py wrapper passes a literal ``interpret=True``, and every
    kernel's ``interpret`` defaults to None (the platform decides)."""
    import ast
    import pathlib

    import repro.kernels

    root = pathlib.Path(repro.kernels.__file__).parent
    ops_files = sorted(root.glob("*/ops.py"))
    assert ops_files
    for path in ops_files:
        for node in ast.walk(ast.parse(path.read_text())):
            if isinstance(node, ast.keyword) and node.arg == "interpret":
                assert not (isinstance(node.value, ast.Constant)
                            and node.value.value is True), path
    for path in sorted(root.glob("*/kernel.py")):
        for fn in ast.walk(ast.parse(path.read_text())):
            if not isinstance(fn, ast.FunctionDef):
                continue
            for arg, default in zip(fn.args.kwonlyargs, fn.args.kw_defaults):
                if arg.arg == "interpret":
                    assert isinstance(default, ast.Constant)
                    assert default.value is None, (path, fn.name)
