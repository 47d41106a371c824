"""The plain reference: the graph's sequential specification over Python
sets, independent of the program under test.

Same semantics as the paper's sequential graph (and the repository's
``GraphOracle``, which it follows op for op): result codes, the per-vertex
``ecnt`` that edge ops bump and that a RemoveVertex bumps on every in-edge
source, and conditional edge ops against ``ecnt``. It keeps out- and
in-neighbour sets so that a RemoveVertex costs its degree, not a scan of
every edge, and answers shortest-path questions by a bidirectional BFS.
The table is unbounded (the served graph grows instead of answering
TABLE FULL), so AddVertex never fails for want of slots.
"""
from __future__ import annotations

# opcodes and result codes of the graph's client protocol (DESIGN.md §2)
OP_NOP, OP_ADD_V, OP_REM_V, OP_CON_V, OP_ADD_E, OP_REM_E, OP_CON_E = range(7)
R_FALSE = 0
R_TRUE = 1
R_VERTEX_NOT_PRESENT = 2
R_EDGE_NOT_PRESENT = 3
R_EDGE_PRESENT = 4
R_EDGE_ADDED = 5
R_EDGE_REMOVED = 6
R_CAS_FAIL = 8

OPCODES = {"NOP": OP_NOP, "AddV": OP_ADD_V, "RemV": OP_REM_V,
           "ConV": OP_CON_V, "AddE": OP_ADD_E, "RemE": OP_REM_E,
           "ConE": OP_CON_E}


class ReferenceGraph:
    """Sequential graph over dicts of sets."""

    def __init__(self):
        self.ecnt: dict[int, int] = {}
        self.out: dict[int, set] = {}
        self.inn: dict[int, set] = {}

    def apply(self, op) -> int:
        """Apply one client op ``(opcode[, k1[, k2[, expect]]])``; returns
        its result code."""
        opc = int(op[0])
        k = int(op[1]) if len(op) > 1 else -1
        l = int(op[2]) if len(op) > 2 else -1
        expect = int(op[3]) if len(op) > 3 else -1
        if opc == OP_NOP:
            return R_FALSE
        if opc == OP_ADD_V:
            if k in self.ecnt:
                return R_FALSE
            self.ecnt[k] = 0
            self.out[k] = set()
            self.inn[k] = set()
            return R_TRUE
        if opc == OP_REM_V:
            if k not in self.ecnt:
                return R_FALSE
            for u in self.inn[k]:
                self.ecnt[u] += 1
            for u in self.inn[k]:
                self.out[u].discard(k)
            for w in self.out[k]:
                self.inn[w].discard(k)
            del self.ecnt[k], self.out[k], self.inn[k]
            return R_TRUE
        if opc == OP_CON_V:
            return R_TRUE if k in self.ecnt else R_FALSE
        if opc not in (OP_ADD_E, OP_REM_E, OP_CON_E):
            raise ValueError(f"bad opcode {opc}")
        if k not in self.ecnt or l not in self.ecnt:
            return R_VERTEX_NOT_PRESENT
        present = l in self.out[k]
        if opc == OP_CON_E:
            return R_EDGE_PRESENT if present else R_EDGE_NOT_PRESENT
        if expect >= 0 and self.ecnt[k] != expect:
            return R_CAS_FAIL
        if opc == OP_ADD_E:
            if present:
                return R_EDGE_PRESENT
            self.out[k].add(l)
            self.inn[l].add(k)
            self.ecnt[k] += 1
            return R_EDGE_ADDED
        if not present:
            return R_EDGE_NOT_PRESENT
        self.out[k].discard(l)
        self.inn[l].discard(k)
        self.ecnt[k] += 1
        return R_EDGE_REMOVED

    def distance(self, k: int, l: int) -> int:
        """Hops on a shortest directed path k -> l; -1 when there is none
        (or either endpoint is absent). Bidirectional BFS, expanding the
        smaller frontier each step."""
        if k not in self.ecnt or l not in self.ecnt:
            return -1
        if k == l:
            return 0
        fwd, bwd = {k}, {l}
        seen_f, seen_b = {k}, {l}
        df = db = 0
        while fwd and bwd:
            if len(fwd) <= len(bwd):
                nxt = set()
                for u in fwd:
                    nxt.update(self.out[u])
                df += 1
                if nxt & seen_b:
                    return df + db
                nxt -= seen_f
                seen_f |= nxt
                fwd = nxt
            else:
                nxt = set()
                for u in bwd:
                    nxt.update(self.inn[u])
                db += 1
                if nxt & seen_f:
                    return df + db
                nxt -= seen_b
                seen_b |= nxt
                bwd = nxt
        return -1

    def path_fault(self, k: int, l: int, found: bool, keys) -> str | None:
        """Why a GetPath answer ``(found, keys)`` for ``k -> l`` is wrong
        against this state, or None when it is right: ``found`` must say
        whether l is reachable, and a found path must run k..l over present
        edges with the fewest hops."""
        d = self.distance(k, l)
        if bool(found) != (d >= 0):
            return f"found={bool(found)}, reference distance {d}"
        if d < 0:
            return None
        keys = [int(x) for x in keys]
        if not keys or keys[0] != k or keys[-1] != l:
            return f"path endpoints {keys[:1]}..{keys[-1:]}"
        for a, b in zip(keys, keys[1:]):
            if a not in self.out or b not in self.out[a]:
                return f"path uses absent edge ({a}, {b})"
        if len(keys) - 1 != d:
            return f"path has {len(keys) - 1} hops, shortest is {d}"
        return None

    def reach_fault(self, k: int, l: int, found: bool) -> str | None:
        """Why a reachability answer is wrong, or None."""
        d = self.distance(k, l)
        if bool(found) != (d >= 0):
            return f"found={bool(found)}, reference distance {d}"
        return None
