"""The generators are deterministic in the seed, and the mixes hold what
their traffic files say."""

import numpy as np
import pytest

from bench.datagen import kronecker
from bench.steps import bfs, point_ops
from bench.tests import tiny

G500 = dict(scale=8, edgefactor=16, initiator_a=0.57, initiator_b=0.19, initiator_c=0.19, graph_seed=1)
BIG_SEED = 2**31 + 5


def _same(a, b):
    assert a.keys() == b.keys()
    for k in a:
        if isinstance(a[k], tuple):
            for x, y in zip(a[k], b[k]):
                np.testing.assert_array_equal(x, y)
        else:
            np.testing.assert_array_equal(a[k], b[k])


def test_data_is_deterministic_in_the_seed():
    gen, cfg = kronecker, G500
    a = gen.build(cfg, 1024, np.random.default_rng(BIG_SEED))
    b = gen.build(cfg, 1024, np.random.default_rng(BIG_SEED))
    _same(a, b)
    c = gen.build(cfg, 1024, np.random.default_rng(BIG_SEED + 1))
    assert not np.array_equal(a["eu"], c["eu"])
    assert a["load"][0].size % 1024 == 0


def test_kronecker_structure_is_the_configurations():
    """Another run seed relabels the same graph."""
    a = kronecker.build(G500, 1024, np.random.default_rng(1))
    b = kronecker.build(G500, 1024, np.random.default_rng(2))
    assert not np.array_equal(a["labels"], b["labels"])

    def unlabelled(d):
        inv = np.argsort(d["labels"])
        return sorted(zip(inv[d["eu"]].tolist(), inv[d["ev"]].tolist()))

    assert unlabelled(a) == unlabelled(b)


def test_kronecker_shape():
    d = kronecker.build(G500, 1024, np.random.default_rng(1))
    n, m = 2**8, 16 * 2**8
    assert d["eu"].size == 2 * m and d["keys"].size == n
    assert d["eu"].min() >= 0 and d["eu"].max() < n
    # both directions of every generated edge
    fwd = set(zip(d["eu"].tolist(), d["ev"].tolist()))
    assert all((v, u) in fwd for u, v in fwd)
    ops = d["load"][0]
    assert (ops[:n] == 1).all() and (ops[n : n + 2 * m] == 4).all()
    loops = d["eu"] == d["ev"]
    deg = np.bincount(d["eu"][~loops], minlength=n)
    np.testing.assert_array_equal(d["search_keys"], np.flatnonzero(deg > 0))


def _point_ops_step(seed):
    s, c, cfg, traffic = tiny.cell("g500-s17-update")
    rng = np.random.default_rng(seed)
    data = kronecker.build(cfg, traffic["load_batch"], rng)
    return point_ops.Step(traffic["steps"][0], data, rng), data, traffic["steps"][0]


def test_point_ops_are_deterministic_in_the_seed():
    a, _, _ = _point_ops_step(BIG_SEED)
    b, _, _ = _point_ops_step(BIG_SEED)
    c, _, _ = _point_ops_step(BIG_SEED + 1)
    for _ in range(5):
        x, y, z = a.next(), b.next(), c.next()
        for p, q in zip(x, y):
            np.testing.assert_array_equal(p, q)
        assert not np.array_equal(x[1], z[1])


def test_point_ops_hold_the_mixs_shares_in_every_batch():
    step, _, params = _point_ops_step(7)
    want = point_ops.op_counts(params["mix"], params["batch"])
    assert want.sum() == params["batch"]
    shares = np.asarray(list(params["mix"].values()))
    assert np.all(np.abs(want - shares * params["batch"]) < 1)
    codes = [point_ops.OPS[k] for k in params["mix"]]
    for _ in range(4):
        ops, _, _ = step.next()
        np.testing.assert_array_equal([np.count_nonzero(ops == k) for k in codes], want)


@pytest.mark.parametrize("n", [7, 256, 4096])
def test_op_counts_sum_to_the_batch(n):
    mix = {"add_vertex": 0.225, "remove_vertex": 0.225, "contains_vertex": 0.05,
           "add_edge": 0.225, "remove_edge": 0.225, "contains_edge": 0.05}
    assert point_ops.op_counts(mix, n).sum() == n


def test_point_ops_touch_only_keys_the_load_added():
    """Vertex ops over the graph's vertices, edge ops over its generated
    edges: the store never sees a key the load did not add."""
    step, data, _ = _point_ops_step(11)
    edges = set(zip(data["eu"].tolist(), data["ev"].tolist()))
    keys = set(data["keys"].tolist())
    for _ in range(4):
        ops, us, vs = step.next()
        vertex = np.isin(ops, point_ops.VERTEX_OPS)
        assert set(us[vertex].tolist()) <= keys
        assert set(zip(us[~vertex].tolist(), vs[~vertex].tolist())) <= edges


def _bfs_calls(seed):
    s, c, cfg, traffic = tiny.cell("g500-s17-bfs")
    rng = np.random.default_rng(seed)
    data = kronecker.build(cfg, traffic["load_batch"], rng)
    step = bfs.Step(traffic["steps"][0], data, rng)
    return step, data, traffic["steps"][0]


def test_bfs_calls_are_deterministic_and_cycle():
    a, data, params = _bfs_calls(BIG_SEED)
    b, _, _ = _bfs_calls(BIG_SEED)
    n = params["iterations"] * params["keys_per_iteration"] // params["sources_per_call"]
    first = [a.next() for _ in range(2 * n)]
    for x in first:
        np.testing.assert_array_equal(x, b.next())
        assert x.size == params["sources_per_call"] and np.isin(x, data["search_keys"]).all()
    for x, y in zip(first, first[n:]):
        np.testing.assert_array_equal(x, y)


def test_bfs_calls_are_the_same_searches_for_every_seed():
    """The same vertices of the same graph, in the same calls and the same
    order; the seed gives only their labels."""

    def searches(seed):
        step, data, _ = _bfs_calls(seed)
        inv = np.argsort(data["labels"])
        return [inv[c].tolist() for c in step.calls], [c.tolist() for c in step.calls]

    (a, labelled_a), (b, labelled_b) = searches(1), searches(2)
    assert a == b and labelled_a != labelled_b
