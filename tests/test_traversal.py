"""Oracle-equivalence tests for the batched traversal engine.

Every query form (pairwise reachability, BFS level maps, k-hop
neighborhoods) and the vectorized snapshot are validated exactly against the
sequential oracle, over deterministic constructions and ≥50 randomized
graphs — including vertex-deletion staleness and incarnation churn, the
Fig. 3 hazards that traversal must respect (a stale edge must never carry a
path)."""

import numpy as np
import pytest

from repro.core import (
    SequentialGraph,
    WaitFreeGraph,
    apply_delta,
    bfs_levels,
    build_csr,
    run_sequential,
)
from repro.core.types import (
    EMPTY_KEY,
    OP_ADD_EDGE,
    OP_ADD_VERTEX,
    OP_REMOVE_EDGE,
    OP_REMOVE_VERTEX,
)
from repro.core.workloads import sample_batch, sample_query_pairs, sample_update_batch

KEY_SPACE = 24  # small key space: dense conflicts, real path structure


def _apply_both(g: WaitFreeGraph, oracle: SequentialGraph, ops, us, vs):
    got = g.apply(ops, us, vs)
    exp, _ = run_sequential(ops, us, vs, graph=oracle)
    assert got.tolist() == exp


def _chain(g: WaitFreeGraph, oracle: SequentialGraph, keys):
    n = len(keys)
    ops = np.concatenate([np.full(n, OP_ADD_VERTEX, np.int32),
                          np.full(n - 1, OP_ADD_EDGE, np.int32)])
    us = np.concatenate([np.asarray(keys, np.int32), np.asarray(keys[:-1], np.int32)])
    vs = np.concatenate([np.zeros(n, np.int32), np.asarray(keys[1:], np.int32)])
    _apply_both(g, oracle, ops, us, vs)


# ---------------------------------------------------------------------------
# deterministic semantics
# ---------------------------------------------------------------------------

def test_chain_levels_and_khop():
    g, o = WaitFreeGraph(64, 64), SequentialGraph()
    _chain(g, o, [10, 11, 12, 13, 14])
    assert g.bfs(10) == o.bfs(10) == {10: 0, 11: 1, 12: 2, 13: 3, 14: 4}
    assert g.bfs(14) == o.bfs(14) == {14: 0}  # directed: no back edges
    for k in range(5):
        assert g.khop(10, k) == o.khop(10, k)
    assert g.khop(10, 2) == {10, 11, 12}


def test_self_reachability_and_absent_endpoints():
    g, o = WaitFreeGraph(64, 64), SequentialGraph()
    _chain(g, o, [1, 2])
    for u, v in [(1, 1), (1, 2), (2, 1), (1, 99), (99, 1), (99, 99)]:
        assert g.reachable(u, v) == o.reachable(u, v)
    assert g.reachable(1, 1) is True     # empty path: u exists
    assert g.reachable(99, 99) is False  # absent vertex
    assert g.bfs(99) == {} == o.bfs(99)
    assert g.khop(99, 3) == set() == o.khop(99, 3)


def test_deleted_vertex_breaks_paths():
    """Removing a cut vertex must sever every path through it."""
    g, o = WaitFreeGraph(64, 64), SequentialGraph()
    _chain(g, o, [1, 2, 3, 4])
    assert g.reachable(1, 4) and o.reachable(1, 4)
    _apply_both(g, o, [OP_REMOVE_VERTEX], [3], [0])
    assert not g.reachable(1, 4) and not o.reachable(1, 4)
    assert g.reachable(1, 2) and o.reachable(1, 2)
    assert g.bfs(1) == o.bfs(1) == {1: 0, 2: 1}


def test_incarnation_churn_stale_edges_carry_no_path():
    """The Fig. 3 hazard, traversal edition: after remove+re-add of an
    endpoint, the stale edge's binding must not conduct reachability."""
    g, o = WaitFreeGraph(64, 64), SequentialGraph()
    _chain(g, o, [1, 2, 3])
    _apply_both(g, o, [OP_REMOVE_VERTEX, OP_ADD_VERTEX], [2, 2], [0, 0])
    # 2 is live again, but edges 1->2 and 2->3 were bound to its old
    # incarnation: nothing is reachable through it.
    assert not g.reachable(1, 3) and not o.reachable(1, 3)
    assert not g.reachable(1, 2) and not o.reachable(1, 2)
    assert not g.reachable(2, 3) and not o.reachable(2, 3)
    assert g.bfs(1) == o.bfs(1) == {1: 0}
    # re-binding the edges at the new incarnation restores the path
    _apply_both(g, o, [OP_ADD_EDGE, OP_ADD_EDGE], [1, 2], [2, 3])
    assert g.reachable(1, 3) and o.reachable(1, 3)


def test_batch_queries_share_one_snapshot():
    """All queries in a batch linearize at the same batch boundary: pairs
    issued together see identical state, and the cached CSR is invalidated
    by the next apply."""
    g, o = WaitFreeGraph(64, 64), SequentialGraph()
    _chain(g, o, [1, 2, 3])
    csr1 = g.traversal_csr()
    assert g.traversal_csr() is csr1  # cached between applies
    got = g.reachable([1, 1, 2], [2, 3, 3])
    assert got.tolist() == [True, True, True]
    _apply_both(g, o, [OP_REMOVE_VERTEX], [2], [0])
    assert g.traversal_csr() is not csr1  # invalidated
    assert g.reachable([1, 1, 2], [2, 3, 3]).tolist() == [False, False, False]


def test_readonly_batches_keep_cached_snapshot():
    """contains/NOP-only batches leave the abstract graph unchanged, so the
    cached CSR must survive them (queries interleaved with lookups stay
    amortized); any mutating op invalidates it."""
    g, o = WaitFreeGraph(64, 64), SequentialGraph()
    _chain(g, o, [1, 2, 3])
    c0 = g.traversal_csr()
    assert g.contains_vertex(1) and g.contains_edge(1, 2)
    assert not g.contains_vertex(99)
    assert g.traversal_csr() is c0
    g.add_vertex(7)
    assert g.traversal_csr() is not c0


def test_csr_structure_invariants():
    g, o = WaitFreeGraph(64, 64), SequentialGraph()
    _chain(g, o, [1, 2, 3, 4])
    _apply_both(g, o, [OP_ADD_EDGE, OP_ADD_EDGE], [1, 1], [3, 4])
    csr = build_csr(g.state)
    src = np.asarray(csr.src)
    dst = np.asarray(csr.dst)
    rs = np.asarray(csr.row_start)
    re = np.asarray(csr.row_end)
    cv = csr.v_capacity
    assert int(csr.n_live) == 4
    assert int(csr.n_edges) == 5
    # sorted by source slot, invalid lanes (== Cv) pushed to the tail
    assert (np.diff(src) >= 0).all()
    assert (src[int(csr.n_edges):] == cv).all() and (dst[int(csr.n_edges):] == cv).all()
    # row ranges partition the valid prefix and degrees sum to edge count
    assert int((re - rs).sum()) == int(csr.n_edges)
    v_key = np.asarray(csr.v_key)
    v_live = np.asarray(csr.v_live)
    deg = {1: 3, 2: 1, 3: 1, 4: 0}
    for j in range(cv):
        if v_live[j]:
            assert int(re[j] - rs[j]) == deg[int(v_key[j])]
            # every out-neighbor slot in the row holds a live vertex
            for t in dst[rs[j]:re[j]]:
                assert v_live[int(t)]


def test_bfs_levels_padding_lanes_are_inert():
    """EMPTY_KEY query lanes (batch padding) must return all -1 rows."""
    g, o = WaitFreeGraph(64, 64), SequentialGraph()
    _chain(g, o, [1, 2])
    keys = np.asarray([1, EMPTY_KEY, 2, EMPTY_KEY], np.int32)
    lv = np.asarray(bfs_levels(build_csr(g.state), keys))
    assert (lv[1] == -1).all() and (lv[3] == -1).all()
    assert (lv[0] >= 0).sum() == 2 and (lv[2] >= 0).sum() == 1


def _sort_sites(jaxpr, in_loop=False):
    """One entry per ``sort`` in ``jaxpr`` and its sub-jaxprs: whether it
    sits inside a ``while`` body."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "sort":
            yield in_loop
        loop = in_loop or eqn.primitive.name == "while"
        for param in eqn.params.values():
            for sub in param if isinstance(param, (tuple, list)) else (param,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _sort_sites(inner, loop)


def test_level_loop_sorts_once_outside_the_loop():
    """The pull view's sort runs once per call, before the level loop; no
    level sorts."""
    import jax

    g, o = WaitFreeGraph(64, 256), SequentialGraph()
    _chain(g, o, [1, 2, 3, 4])
    keys = np.asarray([1, 3, EMPTY_KEY, EMPTY_KEY], np.int32)
    closed = jax.make_jaxpr(lambda c, k: bfs_levels(c, k, impl="xla"))(build_csr(g.state), keys)
    assert list(_sort_sites(closed.jaxpr)) == [False]


def test_cyclic_graph_terminates_and_matches():
    g, o = WaitFreeGraph(64, 64), SequentialGraph()
    _chain(g, o, [1, 2, 3])
    _apply_both(g, o, [OP_ADD_EDGE], [3], [1])  # close the cycle
    assert g.reachable(3, 2) and o.reachable(3, 2)
    assert g.bfs(2) == o.bfs(2) == {2: 0, 3: 1, 1: 2}


def test_edge_free_snapshot_early_return():
    """n_edges == 0 snapshots skip the frontier loop entirely but still
    answer every query form correctly (sources are the whole answer)."""
    g, o = WaitFreeGraph(64, 64), SequentialGraph()
    _apply_both(g, o, np.full(4, OP_ADD_VERTEX, np.int32),
                np.asarray([1, 2, 3, 4], np.int32), np.zeros(4, np.int32))
    assert int(build_csr(g.state).n_edges) == 0
    assert g.reachable([1, 1, 9], [1, 2, 9]).tolist() == [True, False, False]
    assert g.bfs(1) == o.bfs(1) == {1: 0}
    assert g.khop(2, 3) == o.khop(2, 3) == {2}
    assert g.get_path(1, 1) == [1]
    assert g.get_path(1, 2) is None


# ---------------------------------------------------------------------------
# GetPath: explicit shortest paths
# ---------------------------------------------------------------------------

def _assert_path_matches(g: WaitFreeGraph, o: SequentialGraph, u: int, v: int):
    """get_path must agree with the oracle on existence and *length*, and be
    a genuine path of the abstract graph (consecutive edges all present)."""
    got = g.get_path(u, v)
    exp = o.path(u, v)
    if exp is None:
        assert got is None
        return
    assert got is not None
    assert got[0] == u and got[-1] == v
    assert len(got) == len(exp)  # shortest-length guarantee
    for a, b in zip(got, got[1:]):
        assert (a, b) in o.edges, (got, (a, b))


def test_get_path_chain_and_shortcut():
    g, o = WaitFreeGraph(64, 64), SequentialGraph()
    _chain(g, o, [1, 2, 3, 4, 5])
    assert g.get_path(1, 5) == [1, 2, 3, 4, 5]
    _apply_both(g, o, [OP_ADD_EDGE], [2], [4])  # shortcut 2 -> 4
    assert g.get_path(1, 5) == [1, 2, 4, 5]  # must take the shortcut
    assert g.get_path(1, 1) == [1]
    assert g.get_path(5, 1) is None
    assert g.get_path(1, 99) is None and g.get_path(99, 1) is None


def test_get_path_batch_shares_snapshot_and_handles_mixed_pairs():
    g, o = WaitFreeGraph(64, 64), SequentialGraph()
    _chain(g, o, [1, 2, 3])
    got = g.get_path_batch([1, 2, 3, 1, 9], [3, 3, 1, 1, 9])
    assert got[0] == [1, 2, 3]
    assert got[1] == [2, 3]
    assert got[2] is None
    assert got[3] == [1]
    assert got[4] is None


def test_get_path_respects_deletion_and_churn():
    g, o = WaitFreeGraph(64, 64), SequentialGraph()
    _chain(g, o, [1, 2, 3, 4])
    _apply_both(g, o, [OP_REMOVE_VERTEX], [2], [0])
    _assert_path_matches(g, o, 1, 4)  # None: cut vertex
    _apply_both(g, o, [OP_ADD_VERTEX], [2], [0])
    _assert_path_matches(g, o, 1, 3)  # still None: stale edges carry no path
    _apply_both(g, o, [OP_ADD_EDGE, OP_ADD_EDGE], [1, 2], [2, 3])
    assert g.get_path(1, 4) == [1, 2, 3, 4]


# ---------------------------------------------------------------------------
# incremental CSR maintenance (apply_delta)
# ---------------------------------------------------------------------------

def _assert_csr_bit_identical(got, want, ctx=""):
    for name in want._fields:
        a, b = np.asarray(getattr(got, name)), np.asarray(getattr(want, name))
        assert a.dtype == b.dtype, (ctx, name, a.dtype, b.dtype)
        assert np.array_equal(a, b), (ctx, name)


def test_apply_delta_insert_delete_readd_sequence():
    """Deterministic churn: inserts, deletes, vertex removal (incident-edge
    invalidation), and re-add (incarnation bump) all fold in bit-identically."""
    g, o = WaitFreeGraph(64, 128, csr_maintenance="rebuild"), SequentialGraph()
    _chain(g, o, [1, 2, 3, 4])
    csr = build_csr(g.state)
    batches = [
        ([OP_ADD_EDGE, OP_ADD_EDGE], [1, 4], [3, 1]),          # inserts
        ([OP_REMOVE_EDGE, OP_ADD_EDGE], [1, 2], [2, 4]),       # delete + insert
        ([OP_REMOVE_VERTEX], [3], [0]),                        # incident drop
        ([OP_ADD_VERTEX, OP_ADD_EDGE], [3, 3], [0, 4]),        # re-add + bind
        ([OP_ADD_EDGE], [1], [2]),                             # tombstone revive
    ]
    for i, (ops, us, vs) in enumerate(batches):
        _apply_both(g, o, ops, us, vs)
        csr = apply_delta(csr, g.state, ops, us, vs)
        _assert_csr_bit_identical(csr, build_csr(g.state), f"batch {i}")
        assert g.snapshot() == (o.vertices, o.edges)


def test_apply_delta_readonly_and_nop_batches_are_free():
    g, o = WaitFreeGraph(64, 64), SequentialGraph()
    _chain(g, o, [1, 2, 3])
    csr = build_csr(g.state)
    out = apply_delta(csr, g.state, [0], [0], [0])  # NOP-only
    assert out is csr  # same object: nothing to fold


def test_apply_delta_falls_back_on_large_delta():
    """A delta above the footprint threshold must fall back to build_csr and
    still be exact."""
    rng = np.random.default_rng(3)
    g, o = WaitFreeGraph(256, 1024, csr_maintenance="rebuild"), SequentialGraph()
    ops, us, vs = sample_batch(rng, 64, "traversal", key_space=KEY_SPACE)
    _apply_both(g, o, ops, us, vs)
    csr = build_csr(g.state)
    ops, us, vs = sample_batch(rng, 512, "traversal", key_space=KEY_SPACE)
    _apply_both(g, o, ops, us, vs)
    out = apply_delta(csr, g.state, ops, us, vs)
    _assert_csr_bit_identical(out, build_csr(g.state), "large delta")


def test_cached_csr_delta_survives_growth_rehash():
    """Growth rehashes every slot mid-stream; the graph must detect it and
    fall back to a rebuild rather than splicing into a moved table."""
    g, o = WaitFreeGraph(8, 8), SequentialGraph()  # tiny: forces growth
    g.traversal_csr()  # prime the cache so delta maintenance engages
    for start in (0, 8, 16):
        keys = list(range(start, start + 8))
        ops = np.full(8, OP_ADD_VERTEX, np.int32)
        _apply_both(g, o, ops, np.asarray(keys, np.int32), np.zeros(8, np.int32))
        edges = [(k, k + 1) for k in keys[:-1]]
        eops = np.full(len(edges), OP_ADD_EDGE, np.int32)
        _apply_both(g, o, eops, np.asarray([a for a, _ in edges], np.int32),
                    np.asarray([b for _, b in edges], np.int32))
        _assert_csr_bit_identical(g.traversal_csr(), build_csr(g.state),
                                  f"after growth wave {start}")
        assert g.snapshot() == (o.vertices, o.edges)


def test_delta_queue_folds_lazily_at_query_time():
    """Update batches between queries are queued, not folded eagerly: the
    cost lands once per query epoch, read-only batches don't disturb the
    queue, and the single fold over the whole queue is bit-identical to a
    rebuild."""
    rng = np.random.default_rng(7)
    g, o = WaitFreeGraph(256, 1024), SequentialGraph()
    ops, us, vs = sample_batch(rng, 128, "traversal", key_space=KEY_SPACE)
    _apply_both(g, o, ops, us, vs)
    g.traversal_csr()  # prime the cache
    for i in range(4):
        ops, us, vs = sample_update_batch(rng, 12, key_space=KEY_SPACE)
        _apply_both(g, o, ops, us, vs)
        assert g._csr is None and len(g._delta_batches) == i + 1  # queued
        assert g.contains_vertex(int(us[0])) in (True, False)  # read-only op
        assert len(g._delta_batches) == i + 1  # queue survived it
    _assert_csr_bit_identical(g.traversal_csr(), build_csr(g.state), "queued fold")
    assert g._delta_batches == []  # folded and cleared
    assert g.snapshot() == (o.vertices, o.edges)


@pytest.mark.parametrize("seed", range(10))
def test_apply_delta_randomized_churn_matches_rebuild(seed):
    """Randomized insert/delete/re-add sequences: the delta-maintained CSR is
    bit-identical to a fresh rebuild after every update batch, and queries
    stay oracle-exact throughout."""
    rng = np.random.default_rng(1000 + seed)
    g = WaitFreeGraph(256, 1024, mode="fpsp")  # csr_maintenance="delta" default
    o = SequentialGraph()
    ops, us, vs = sample_batch(rng, 128, "traversal", key_space=KEY_SPACE)
    _apply_both(g, o, ops, us, vs)
    g.traversal_csr()  # prime the cache
    for _ in range(6):
        ops, us, vs = sample_update_batch(rng, 16, key_space=KEY_SPACE)
        _apply_both(g, o, ops, us, vs)
        _assert_csr_bit_identical(g.traversal_csr(), build_csr(g.state))
        us_q, vs_q = sample_query_pairs(rng, 16, KEY_SPACE)
        got = g.reachable(us_q, vs_q)
        assert got.tolist() == [o.reachable(int(a), int(b)) for a, b in zip(us_q, vs_q)]


# ---------------------------------------------------------------------------
# randomized oracle equivalence: 2 modes × 25 seeds = 50 graphs
# ---------------------------------------------------------------------------

def _build_random(seed: int, mode: str):
    rng = np.random.default_rng(seed)
    g = WaitFreeGraph(256, 1024, mode=mode)
    oracle = SequentialGraph()
    for _ in range(2):
        ops, us, vs = sample_batch(rng, 192, "traversal", key_space=KEY_SPACE)
        _apply_both(g, oracle, ops, us, vs)
    # deletion wave: tombstones + stale edges
    kill = rng.choice(KEY_SPACE, size=8, replace=False).astype(np.int32)
    _apply_both(g, oracle, np.full(8, OP_REMOVE_VERTEX, np.int32), kill,
                np.zeros(8, np.int32))
    # incarnation churn: re-add half of the killed keys
    revive = kill[:4]
    _apply_both(g, oracle, np.full(4, OP_ADD_VERTEX, np.int32), revive,
                np.zeros(4, np.int32))
    # fresh edges over the churned key space
    ops, us, vs = sample_batch(rng, 96, "traversal", key_space=KEY_SPACE)
    _apply_both(g, oracle, ops, us, vs)
    return g, oracle, rng


@pytest.mark.parametrize("mode", ["waitfree", "fpsp"])
@pytest.mark.parametrize("seed", range(25))
def test_randomized_graphs_match_oracle(mode, seed):
    g, oracle, rng = _build_random(seed, mode)
    # abstract state agrees
    assert g.snapshot() == (oracle.vertices, oracle.edges)
    # pairwise reachability, one shared snapshot
    us, vs = sample_query_pairs(rng, 64, KEY_SPACE)
    got = g.reachable(us, vs)
    exp = [oracle.reachable(int(a), int(b)) for a, b in zip(us, vs)]
    assert got.tolist() == exp
    # full BFS level maps from random sources
    srcs = rng.integers(0, KEY_SPACE, size=8).tolist()
    for s, levels in zip(srcs, g.bfs_batch(srcs)):
        assert levels == oracle.bfs(int(s))
    # bounded-depth neighborhoods
    u = int(rng.integers(0, KEY_SPACE))
    k = int(rng.integers(0, 4))
    assert g.khop(u, k) == oracle.khop(u, k)


@pytest.mark.parametrize("mode", ["waitfree", "fpsp"])
@pytest.mark.parametrize("seed", range(25))
def test_randomized_get_path_matches_oracle(mode, seed):
    """GetPath over the same 50 randomized churned graphs: every returned
    path is a valid path of the abstract graph with oracle-shortest length,
    and None exactly when the oracle says unreachable."""
    g, oracle, rng = _build_random(seed, mode)
    us, vs = sample_query_pairs(rng, 12, KEY_SPACE)
    paths = g.get_path_batch(us, vs)
    for u, v, got in zip(us, vs, paths):
        u, v = int(u), int(v)
        exp = oracle.path(u, v)
        if exp is None:
            assert got is None, (u, v, got)
            continue
        assert got is not None, (u, v)
        assert got[0] == u and got[-1] == v
        assert len(got) == len(exp), (u, v, got, exp)  # length-optimality
        assert len(set(got)) == len(got)  # simple path
        for a, b in zip(got, got[1:]):
            assert (a, b) in oracle.edges, (got, (a, b))


@pytest.mark.parametrize("seed", range(3))
def test_repeated_sources_answer_per_pair(seed):
    """A query batch that repeats its sources answers every pair on its own
    row: reachable and get_path agree with the oracle pair by pair, and the
    batched k-hop equals one k-hop call per source."""
    g, oracle, rng = _build_random(seed, "fpsp")
    us = rng.choice(4, 48).astype(np.int32)  # few distinct sources
    vs = rng.integers(0, KEY_SPACE, 48).astype(np.int32)
    assert g.reachable(us, vs).tolist() == [
        oracle.reachable(int(a), int(b)) for a, b in zip(us, vs)
    ]
    paths = g.get_path_batch(us, vs)
    for u, v, p in zip(us.tolist(), vs.tolist(), paths):
        want = oracle.path(u, v)
        assert (p is None) == (want is None)
        if p is not None:
            assert p[0] == u and p[-1] == v and len(p) == len(want)
    srcs = [int(s) for s in us[:6]]
    assert g.khop_batch(srcs, 2) == [g.khop(s, 2) for s in srcs] == [
        oracle.khop(s, 2) for s in srcs
    ]
