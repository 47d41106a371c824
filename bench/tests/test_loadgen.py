"""The traffic generator: exact shares, seed-independent sizes, the key
laws, and a key model that follows what it generated."""
import json
import pathlib

import numpy as np

from bench import loadgen
from bench.reference import OPCODES, ReferenceGraph

ROOT = pathlib.Path(__file__).resolve().parents[2]


def _mix(name):
    return json.loads((ROOT / "bench" / "traffic" / f"{name}.json").read_text())


def test_templates_keep_their_shares_for_every_seed():
    mix = _mix("update-heavy")
    counts = []
    for seed in (1, 2 ** 40 + 3):
        s = loadgen.OpStream(mix, 1024, [], [], np.random.default_rng(seed))
        ops = [op[0] for _ in range(125) for op in s.next_batch()]
        counts.append(np.bincount(ops, minlength=7).tolist())
    assert counts[0] == counts[1]
    assert counts[0][OPCODES["AddV"]] == 900
    assert counts[0][OPCODES["ConE"]] == 200


def test_open_loop_sends_the_same_number_for_every_seed():
    n = [len(loadgen.poisson_due_times(40.0, 30.0,
                                       np.random.default_rng(s)))
         for s in (0, 7, 2 ** 33)]
    assert n[0] == n[1] == n[2] and abs(n[0] - 1200) <= 2
    due = loadgen.poisson_due_times(40.0, 30.0, np.random.default_rng(0))
    assert np.all(np.diff(due) > 0) and due[-1] < 30.0


def test_zipfian_keys_are_skewed_and_the_hot_set_moves():
    law = loadgen.KeyLaw({"dist": "zipfian", "theta": 0.99,
                          "hot_shift_per_batch": 100}, 4096,
                         np.random.default_rng(0))
    first = np.bincount([law.draw() for _ in range(20000)], minlength=4096)
    hot = int(first.argmax())
    assert first[hot] > 20 * 20000 / 4096
    law.next_batch()
    moved = np.bincount([law.draw() for _ in range(20000)], minlength=4096)
    assert int(moved.argmax()) == (hot + 100) % 4096


def test_symmetric_updates_follow_the_key_model():
    from bench import datagen
    from bench.tests.conftest import PATHS_MIXED

    e = datagen.kronecker_edges(8, 4, 5)
    e = e[e[:, 0] != e[:, 1]]
    e = np.unique(np.concatenate([e, e[:, ::-1]]), axis=0)
    keys = np.unique(e)
    edges = e.tolist()
    s = loadgen.OpStream(PATHS_MIXED, 512, keys, edges,
                         np.random.default_rng(3))
    ref = ReferenceGraph()
    for k in keys:
        ref.apply((OPCODES["AddV"], int(k)))
    for a, b in edges:
        ref.apply((OPCODES["AddE"], int(a), int(b)))
    codes = []
    for _ in range(400):
        for op in s.next_batch():
            codes.append((op[0], ref.apply(op)))
    # generated and applied in one order, every update does its work (an
    # edge pair added between vertices already joined finds the edge)
    want = {OPCODES["AddV"]: {1}, OPCODES["RemV"]: {1},
            OPCODES["AddE"]: {4, 5}, OPCODES["RemE"]: {6}}
    assert all(c in want[op] for op, c in codes), set(codes)
    assert set(ref.ecnt) == set(s.model.live)
    assert sorted((u, w) for u, ws in ref.out.items() for w in ws) == \
        sorted(s.model.edges)


def test_reachable_sets_match_the_reference():
    from bench import datagen

    edges = datagen.kronecker_edges(7, 4, 11)
    keys = np.unique(edges)
    m = loadgen.KeyModel(keys, edges, 128, np.random.default_rng(0))
    ref = ReferenceGraph()
    for k in keys:
        ref.apply((OPCODES["AddV"], int(k)))
    for a, b in edges:
        ref.apply((OPCODES["AddE"], int(a), int(b)))
    for src in keys[:20]:
        want = {int(k) for k in keys
                if k != src and ref.distance(int(src), int(k)) > 0}
        assert set(m.reachable(int(src)).tolist()) == want
