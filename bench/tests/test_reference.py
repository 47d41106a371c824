"""The benchmark's reference agrees with the repository's sequential
oracle, op for op, and its BFS with the oracle's shortest paths."""
import numpy as np
import pytest

from bench.reference import ReferenceGraph


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_reference_matches_oracle(seed):
    from repro.core.oracle import GraphOracle

    rng = np.random.default_rng(seed)
    ref, ora = ReferenceGraph(), GraphOracle()
    for _ in range(3000):
        opc = int(rng.integers(0, 7))
        k, l = (int(x) for x in rng.integers(0, 24, 2))
        expect = int(rng.integers(-1, 3)) if rng.random() < 0.2 else -1
        assert ref.apply((opc, k, l, expect)) == ora.apply(opc, k, l, expect)
    assert {k: v for k, v in ref.ecnt.items()} == ora.ecnt
    assert {(u, w) for u, ws in ref.out.items() for w in ws} == ora.edges
    for k in range(24):
        for l in range(24):
            d = ref.distance(k, l)
            assert d + 1 == ora.shortest_path_len(k, l) or (
                d < 0 and ora.shortest_path_len(k, l) == 0)


def test_path_fault_names_each_wrong_answer():
    ref = ReferenceGraph()
    for op in [(1, 0), (1, 1), (1, 2), (4, 0, 1), (4, 1, 2), (4, 0, 2)]:
        ref.apply(op)
    assert ref.path_fault(0, 2, True, [0, 2]) is None
    assert "hops" in ref.path_fault(0, 2, True, [0, 1, 2])
    assert ref.path_fault(2, 0, False, []) is None
    assert "absent edge" in ref.path_fault(0, 2, True, [0, 0, 2])
    assert "found" in ref.path_fault(2, 0, True, [2, 0])
    assert "endpoints" in ref.path_fault(0, 2, True, [0])
    assert ref.reach_fault(0, 2, False) is not None
