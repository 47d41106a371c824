"""Graph500 Kronecker edge generator (Graph500 specification, section 3).

The reference generator of the Graph500 benchmark: ``edge_factor * 2**scale``
directed edges over ``2**scale`` vertices, each endpoint chosen bit by bit
from the 2x2 initiator ``[[A, B], [C, D]]`` with A=0.57, B=C=0.19 (D=0.05),
then vertex labels and edge order are shuffled by random permutations so
the hubs land on random ids. Self-loops and duplicate edges are kept, as
the specification keeps them; consumers decide what they mean.

Deterministic in ``seed`` and vectorized over all edges at once (one numpy
pass per scale bit), so a scale-16 graph takes well under a second.
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
    ``[0, 2**scale)``, generated as the Graph500 reference code does."""
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
