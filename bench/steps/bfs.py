"""The BFS request: Graph500 kernel 2 through ``WaitFreeGraph.bfs_batch``.

Each of ``iterations`` Graph500 iterations draws ``keys_per_iteration``
distinct search keys among the vertices of degree at least 1 (self-loops
not counted), as Graph500 does, and splits them into calls of
``sources_per_call``.  The draws come from the mix's ``keys_seed``, over the
graph's own vertices before their labels are permuted, so every run asks the
same calls of the same graph, in the same order, over and over: a window
that holds only some of the calls holds the same ones in every run.  The
run's seed gives the labels.  The graph is static after the load, so every
call answers against the snapshot the set-up built.
"""

from __future__ import annotations

import numpy as np

from bench.reference import ReferenceGraph
from bench.standin import ReferenceStandIn


class Step:
    # the end-to-end metrics: work done over the window, and the 95th
    # percentile of latency over every op or query
    rate_metric = "queries_per_s"
    p95_metric = "query_p95_ms"
    span = "bench.query"
    check = "bfs_wrong"
    # the faults of the timed path a run of this kind must catch; the
    # window changes no state, so a state left unchanged is no fault here
    faults = ("half_batch", "altered_answer")

    def __init__(self, params: dict, data: dict, rng: np.random.Generator):
        self.params = params
        self.absent = int(data["keys"].max()) + 1  # never added
        # the searchable vertices in the graph's own numbering, then their keys
        unlabelled = np.sort(np.argsort(data["labels"])[data["search_keys"]])
        draw = np.random.default_rng(params["keys_seed"])
        calls = []
        for _ in range(params["iterations"]):
            it = data["labels"][draw.choice(unlabelled, params["keys_per_iteration"], replace=False)]
            calls += np.split(it, it.size // params["sources_per_call"])
        self.calls = calls
        self._next = 0
        # the calls whose answers are compared: ``check_calls`` drawn from
        # the seed among those the window finishes
        self.check_sample = params["check_calls"]

    def next(self) -> np.ndarray:
        call = self.calls[self._next % len(self.calls)]
        self._next += 1
        return call

    @staticmethod
    def size(req) -> int:
        return req.size

    @staticmethod
    def issue(graph, req):
        return graph.bfs_batch(req)

    def warm(self, graph, make_graph) -> None:
        """Build the snapshot and compile the call's shape: sources that are
        absent start no frontier, so the call runs no level."""
        graph.bfs_batch(np.full(self.params["sources_per_call"], self.absent, np.int32))

    @staticmethod
    def replay(ref, req, ans, checked: bool) -> dict:
        """Compare a checked call's level maps with the reference's BFS, and
        count the bytes a level-synchronous BFS of the call must move at
        least: per level visited (level 0 up to the deepest level of any of
        its sources), every live directed edge's two int32 endpoints once;
        per source, a level read and written once per live vertex."""
        if not checked:
            return {}
        want = [ref.bfs(u) for u in req.tolist()]
        depth = max(max(w.values(), default=-1) for w in want)
        n_edges = sum(len(b) for b in ref.out.values())
        return dict(
            compared=req.size,
            wrong=sum(w != got for w, got in zip(want, ans)),
            least_bytes=8 * n_edges * (depth + 1) + 8 * len(ref.out) * req.size,
        )

    @staticmethod
    def programs(params: dict, shapes) -> list:
        """The programs the window drives, for a compile without the chip."""
        from repro.core import traversal

        n = params["sources_per_call"]
        return [
            ("build_csr", traversal.build_csr, (shapes.state(),)),
            (f"bfs_levels {n}", traversal.bfs_levels, (shapes.csr(), shapes.vector(n))),
        ]


class Control(ReferenceStandIn):
    """The control: BFS answers from the graph as it stood before the last
    batch, a snapshot that skipped its refresh."""

    def __init__(self, v_capacity: int, e_capacity: int):
        super().__init__(v_capacity, e_capacity)
        self._before = ReferenceGraph()
        self._last = None

    def apply(self, ops, us, vs):
        if self._last is not None:
            self._before.apply_all(*self._last)
        self._last = (np.asarray(ops), np.asarray(us), np.asarray(vs))
        return super().apply(ops, us, vs)

    def bfs_batch(self, sources):
        return [self._before.bfs(int(u)) for u in np.asarray(sources).tolist()]
