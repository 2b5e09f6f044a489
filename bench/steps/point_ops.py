"""Point-op requests: batches of the paper's six operations through
``WaitFreeGraph.apply``, in the shares that the traffic file gives.

Every batch holds ``batch`` ops, each kind in its share of ``mix`` (rounded
to whole ops, the remainders to the largest shares), in an order drawn from
the run's seed.  A vertex op's key is drawn uniformly among the
configuration's vertices; an edge op's endpoints are one entry, drawn
uniformly, of the configuration's generated edge list, so edge ops touch
the graph's hubs as often as its edges do.

The keys are a fixed set: every one of them was added by the load, so
whatever the rate, the store's tables hold no more keys than the load left
in them, and no table growth can fall in the window at any speed.
"""

from __future__ import annotations

import numpy as np

from bench.reference import (
    ADD_EDGE,
    ADD_VERTEX,
    CONTAINS_EDGE,
    CONTAINS_VERTEX,
    REMOVE_EDGE,
    REMOVE_VERTEX,
    ReferenceGraph,
)
from bench.standin import ReferenceStandIn

OPS = {
    "add_vertex": ADD_VERTEX,
    "remove_vertex": REMOVE_VERTEX,
    "contains_vertex": CONTAINS_VERTEX,
    "add_edge": ADD_EDGE,
    "remove_edge": REMOVE_EDGE,
    "contains_edge": CONTAINS_EDGE,
}
VERTEX_OPS = (ADD_VERTEX, REMOVE_VERTEX, CONTAINS_VERTEX)


def op_counts(mix: dict, n: int) -> np.ndarray:
    """Whole op counts of each kind of ``mix`` in a batch of ``n`` that sum
    to ``n`` (largest remainders first)."""
    share = np.asarray(list(mix.values()), float)
    if abs(share.sum() - 1.0) > 1e-9:
        raise ValueError(f"the mix's shares sum to {share.sum()}, not 1")
    exact = share * n
    counts = np.floor(exact).astype(np.int64)
    counts[np.argsort(-(exact - counts), kind="stable")[: n - counts.sum()]] += 1
    return counts


class Step:
    """Closed-loop batches of point ops over the configuration's keys."""

    # the end-to-end metrics: work done over the window, and the 95th
    # percentile of latency over every op
    rate_metric = "ops_per_s"
    p95_metric = "op_p95_ms"
    span = "bench.apply"
    check = "bits_wrong"
    # every batch is compared: each answer depends on all before it
    check_sample = None
    # the faults of the timed path a run of this kind must catch
    faults = ("state_unchanged", "half_batch", "altered_answer")

    def __init__(self, params: dict, data: dict, rng: np.random.Generator):
        self.rng = rng
        self.n = params["batch"]
        kinds = np.asarray([OPS[k] for k in params["mix"]], np.int32)
        self.kinds = np.repeat(kinds, op_counts(params["mix"], self.n))
        self.keys = data["keys"]
        self.eu, self.ev = data["eu"], data["ev"]

    def next(self):
        ops = self.rng.permutation(self.kinds)
        vertex = np.isin(ops, VERTEX_OPS)
        e = self.rng.integers(0, self.eu.size, self.n)
        us = np.where(vertex, self.keys[self.rng.integers(0, self.keys.size, self.n)], self.eu[e])
        vs = np.where(vertex, 0, self.ev[e])
        return ops, us.astype(np.int32), vs.astype(np.int32)

    @staticmethod
    def size(req) -> int:
        return req[0].size

    @staticmethod
    def issue(graph, req):
        return graph.apply(*req)

    def warm(self, graph, make_graph) -> None:
        """Compile this batch shape on the loaded tables, with one batch of
        probes on a throwaway graph over the same state (``graph`` is left
        as it is)."""
        w = make_graph(graph.state.v_capacity, graph.state.e_capacity)
        w.state = graph.state
        e = np.arange(self.n) % self.eu.size
        w.apply(np.full(self.n, CONTAINS_EDGE, np.int32), self.eu[e], self.ev[e])

    @staticmethod
    def replay(ref, req, ans, checked: bool) -> dict:
        want = np.asarray(ref.apply_all(*req), bool)
        return dict(compared=want.size, wrong=int(np.count_nonzero(want != np.asarray(ans, bool))))

    @staticmethod
    def programs(params: dict, shapes) -> list:
        """The programs the window drives, for a compile without the chip."""
        from repro.core import engine

        n = params["batch"]
        return [(f"apply_batch {n}", engine.apply_batch, (shapes.state(), shapes.batch(n)))]


class _ForgetfulReference(ReferenceGraph):
    """Keeps a removed vertex's edge sets and hands them back on its re-add."""

    def __init__(self) -> None:
        super().__init__()
        self._kept: dict[int, tuple[set[int], set[int]]] = {}

    def remove_vertex(self, u: int, v: int = 0) -> bool:
        if u not in self.out:
            return False
        self._kept[u] = (self.out.pop(u), self.inn.pop(u))
        return True

    def add_vertex(self, u: int, v: int = 0) -> bool:
        if u in self.out:
            return False
        self.out[u], self.inn[u] = self._kept.pop(u, (set(), set()))
        return True


class Control(ReferenceStandIn):
    """The control: removing a vertex forgets the vertex but keeps its
    edges, so a vertex removed and added again gets its old edges back,
    which the incarnation guarantee rules out."""

    ref_class = _ForgetfulReference
