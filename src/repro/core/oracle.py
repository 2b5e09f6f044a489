"""Pure-Python sequential oracle for the graph's sequential specification.

This is the ground truth the concurrent engine is validated against
(linearizability: the engine's per-op results must equal the oracle's results
for the phase-ordered sequential application).

Semantics follow the paper's §2.1 on the *abstract* graph G=(V, E):

* ``remove_vertex(u)`` removes u and (abstractly) all incident edges — any
  later ``contains_edge``/``remove_edge`` touching u fails because u is not
  present, and re-adding u yields a vertex with *no* incident edges.  (The
  paper realizes this with fresh VNode allocation + endpoint revalidation,
  Fig. 3; we realize it with incarnation counters.)
"""

from __future__ import annotations

from collections import deque
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from .types import (
    OP_ADD_EDGE,
    OP_ADD_VERTEX,
    OP_CONTAINS_EDGE,
    OP_CONTAINS_VERTEX,
    OP_NOP,
    OP_REMOVE_EDGE,
    OP_REMOVE_VERTEX,
)


class SequentialGraph:
    """Reference implementation: a plain sequential directed graph.

    Edges are kept as out- and in-adjacency sets, so every operation is O(1)
    except ``remove_vertex`` (O(degree)), and a BFS visits each edge once:
    the oracle replays a graph of millions of edges in seconds."""

    def __init__(self) -> None:
        self.vertices: Set[int] = set()
        self._out: Dict[int, Set[int]] = {}
        self._inn: Dict[int, Set[int]] = {}

    @property
    def edges(self) -> Set[Tuple[int, int]]:
        return {(a, b) for a, bs in self._out.items() for b in bs}

    @edges.setter
    def edges(self, value: Iterable[Tuple[int, int]]) -> None:
        self._out, self._inn = {}, {}
        for a, b in value:
            self._out.setdefault(a, set()).add(b)
            self._inn.setdefault(b, set()).add(a)

    # -- the six operations (paper §2.1) --------------------------------
    def add_vertex(self, u: int) -> bool:
        if u in self.vertices:
            return False
        self.vertices.add(u)
        return True

    def remove_vertex(self, u: int) -> bool:
        if u not in self.vertices:
            return False
        self.vertices.discard(u)
        for b in self._out.pop(u, ()):
            self._inn[b].discard(u)
        for a in self._inn.pop(u, ()):
            self._out[a].discard(u)
        return True

    def contains_vertex(self, u: int) -> bool:
        return u in self.vertices

    def _has_edge(self, u: int, v: int) -> bool:
        return v in self._out.get(u, ())

    def add_edge(self, u: int, v: int) -> bool:
        if u not in self.vertices or v not in self.vertices:
            return False
        if self._has_edge(u, v):
            return False
        self._out.setdefault(u, set()).add(v)
        self._inn.setdefault(v, set()).add(u)
        return True

    def remove_edge(self, u: int, v: int) -> bool:
        if u not in self.vertices or v not in self.vertices:
            return False
        if not self._has_edge(u, v):
            return False
        self._out[u].discard(v)
        self._inn[v].discard(u)
        return True

    def contains_edge(self, u: int, v: int) -> bool:
        if u not in self.vertices or v not in self.vertices:
            return False
        return self._has_edge(u, v)

    # -- traversal queries (sequential specification) --------------------
    def bfs(self, u: int) -> Dict[int, int]:
        """BFS level map {vertex: hop distance} from u (u itself at 0).
        Empty when u is absent — matching the engine's dead-source rows."""
        if u not in self.vertices:
            return {}
        levels = {u: 0}
        frontier = {u}
        depth = 0
        while frontier:
            depth += 1
            reached: Set[int] = set()
            for a in frontier:
                reached.update(self._out.get(a, ()))
            frontier = {b for b in reached if b not in levels}
            for b in frontier:
                levels[b] = depth
        return levels

    def reachable(self, u: int, v: int) -> bool:
        """Directed u ↝ v; u ↝ u is True iff u exists (the empty path)."""
        if u not in self.vertices or v not in self.vertices:
            return False
        return v in self.bfs(u)

    def khop(self, u: int, k: int) -> Set[int]:
        """Vertices within ≤k directed hops of u (including u)."""
        return {w for w, d in self.bfs(u).items() if d <= k}

    def path(self, u: int, v: int) -> Optional[List[int]]:
        """A shortest directed path u ↝ v as ``[u, ..., v]``, or None when
        unreachable / either endpoint absent.  ``path(u, u) == [u]`` when u
        exists (the empty path).  Ties between equal-length paths are broken
        arbitrarily — callers check validity + length, not the exact route
        (the engine's deterministic min-parent choice need not match)."""
        if u not in self.vertices or v not in self.vertices:
            return None
        parent = {u: u}
        q = deque([u])
        while q and v not in parent:
            a = q.popleft()
            for b in self._out.get(a, ()):
                if b not in parent:
                    parent[b] = a
                    q.append(b)
        if v not in parent:
            return None
        chain = [v]
        while chain[-1] != u:
            chain.append(parent[chain[-1]])
        return list(reversed(chain))

    def apply(self, op: int, u: int, v: int) -> bool:
        if op == OP_ADD_VERTEX:
            return self.add_vertex(u)
        if op == OP_REMOVE_VERTEX:
            return self.remove_vertex(u)
        if op == OP_CONTAINS_VERTEX:
            return self.contains_vertex(u)
        if op == OP_ADD_EDGE:
            return self.add_edge(u, v)
        if op == OP_REMOVE_EDGE:
            return self.remove_edge(u, v)
        if op == OP_CONTAINS_EDGE:
            return self.contains_edge(u, v)
        if op == OP_NOP:
            return False
        raise ValueError(f"unknown op {op}")


def run_sequential(
    ops: Sequence[int],
    us: Sequence[int],
    vs: Sequence[int],
    phases: Sequence[int] | None = None,
    graph: SequentialGraph | None = None,
) -> Tuple[List[bool], SequentialGraph]:
    """Apply a batch sequentially in increasing phase order.

    Returns results in the *original* batch order (matching the engine).
    """
    n = len(ops)
    g = graph if graph is not None else SequentialGraph()
    order: Iterable[int]
    if phases is None:
        order = range(n)
    else:
        order = sorted(range(n), key=lambda i: phases[i])
    results: List[bool] = [False] * n
    for i in order:
        results[i] = g.apply(int(ops[i]), int(us[i]), int(vs[i]))
    return results, g
