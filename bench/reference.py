"""The plain reference: a sequential directed graph with adjacency sets.

It states the store's sequential specification (the paper's six operations
on the abstract graph, and BFS level maps) in the most direct way, and
imports nothing of the system under test.  Op codes are the public API's
numbers.  Removing a vertex removes every edge that touches it, so a vertex
that is added again starts with no edges (the incarnation guarantee).
"""

from __future__ import annotations

NOP = 0
ADD_VERTEX = 1
REMOVE_VERTEX = 2
CONTAINS_VERTEX = 3
ADD_EDGE = 4
REMOVE_EDGE = 5
CONTAINS_EDGE = 6


class ReferenceGraph:
    def __init__(self) -> None:
        self.out: dict[int, set[int]] = {}  # vertex -> successors; key set = V
        self.inn: dict[int, set[int]] = {}  # vertex -> predecessors

    def add_vertex(self, u: int, v: int = 0) -> bool:
        if u in self.out:
            return False
        self.out[u] = set()
        self.inn[u] = set()
        return True

    def remove_vertex(self, u: int, v: int = 0) -> bool:
        if u not in self.out:
            return False
        for b in self.out.pop(u):
            if b != u:
                self.inn[b].discard(u)
        for a in self.inn.pop(u):
            if a != u:
                self.out[a].discard(u)
        return True

    def add_edge(self, u: int, v: int) -> bool:
        if u not in self.out or v not in self.out or v in self.out[u]:
            return False
        self.out[u].add(v)
        self.inn[v].add(u)
        return True

    def remove_edge(self, u: int, v: int) -> bool:
        if u not in self.out or v not in self.out or v not in self.out[u]:
            return False
        self.out[u].discard(v)
        self.inn[v].discard(u)
        return True

    def contains_edge(self, u: int, v: int) -> bool:
        return u in self.out and v in self.out and v in self.out[u]

    def contains_vertex(self, u: int, v: int = 0) -> bool:
        return u in self.out

    def nop(self, u: int, v: int = 0) -> bool:
        return False

    def apply_all(self, ops, us, vs) -> list[bool]:
        """Every op of a stream in order (vertex ops ignore ``v``); the list
        of their answers."""
        by_code = [
            self.nop,
            self.add_vertex,
            self.remove_vertex,
            self.contains_vertex,
            self.add_edge,
            self.remove_edge,
            self.contains_edge,
        ]
        if ops.size and (ops.min() < NOP or ops.max() > CONTAINS_EDGE):
            raise ValueError("unknown op code")
        return [by_code[o](u, v) for o, u, v in zip(ops.tolist(), us.tolist(), vs.tolist())]

    def bfs(self, u: int) -> dict[int, int]:
        """{vertex: hop distance} over directed edges from ``u`` (``u`` at 0);
        empty when ``u`` is absent."""
        if u not in self.out:
            return {}
        levels = {u: 0}
        frontier = {u}
        depth = 0
        while frontier:
            depth += 1
            frontier = set().union(*(self.out[a] for a in frontier)).difference(levels)
            levels.update(dict.fromkeys(frontier, depth))
        return levels

    def vertices(self) -> set[int]:
        return set(self.out)

    def edges(self) -> set[tuple[int, int]]:
        return {(a, b) for a, bs in self.out.items() for b in bs}
