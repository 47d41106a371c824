"""Seeded generators of the benchmark's initial graphs.

Each configuration file names its generator under ``"generator"``, which
returns ``(keys, edges)``: the vertex keys to add and the directed edges to
add after them, in load order. The program under test never sees
these functions, only the ops the loader builds from their output.
"""
from __future__ import annotations

import numpy as np

GRAPH500_A = 0.57
GRAPH500_B = 0.19
GRAPH500_C = 0.19


def kronecker_edges(scale: int, edge_factor: int = 16, seed: int = 0,
                    a: float = GRAPH500_A, b: float = GRAPH500_B,
                    c: float = GRAPH500_C) -> np.ndarray:
    """int32[edge_factor * 2**scale, 2] of (src, dst) vertex ids in
    ``[0, 2**scale)``, generated as the Graph500 reference code does
    (specification section 3): each endpoint chosen bit by bit from the 2x2
    initiator ``[[A, B], [C, D]]``, then vertex labels and edge order
    shuffled by random permutations. Self-loops and duplicate edges are
    kept, as the specification keeps them."""
    n = 1 << int(scale)
    m = int(edge_factor) * n
    rng = np.random.default_rng(seed)
    ab = a + b
    c_norm = c / (1.0 - ab)
    a_norm = a / ab
    src = np.zeros(m, np.int64)
    dst = np.zeros(m, np.int64)
    for bit in range(int(scale)):
        src_bit = rng.random(m) > ab
        dst_bit = rng.random(m) > np.where(src_bit, c_norm, a_norm)
        src += src_bit.astype(np.int64) << bit
        dst += dst_bit.astype(np.int64) << bit
    perm = rng.permutation(n)
    edges = np.stack([perm[src], perm[dst]], axis=1)[rng.permutation(m)]
    return edges.astype(np.int32)


def initial_graph(gen: dict, capacity: int, seed: int
                  ) -> tuple[np.ndarray, np.ndarray]:
    """``(vertex keys, directed edges)`` of a configuration's initial graph,
    in load order, from its ``"generator"`` entry."""
    kind = gen["kind"]
    if kind == "kronecker":
        edges = kronecker_edges(gen["scale"], gen["edge_factor"], seed,
                                gen["a"], gen["b"], gen["c"])
        return np.unique(edges).astype(np.int32), edges
    raise ValueError(f"unknown generator kind {kind!r}")
