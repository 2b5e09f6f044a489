"""The benchmark's own reference states the same semantics as the program's
sequential oracle, without importing it."""

import ast
import os

import numpy as np
import pytest

from bench import reference
from bench.reference import ReferenceGraph
from repro.core import types
from repro.core.oracle import SequentialGraph

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_op_codes_are_the_apis():
    for name in ("NOP", "ADD_VERTEX", "REMOVE_VERTEX", "CONTAINS_VERTEX", "ADD_EDGE", "REMOVE_EDGE", "CONTAINS_EDGE"):
        assert getattr(reference, name) == getattr(types, "OP_" + name)


def test_reference_imports_nothing_of_the_program():
    with open(os.path.join(BENCH, "reference.py")) as f:
        tree = ast.parse(f.read())
    names = [a.name for n in ast.walk(tree) if isinstance(n, ast.Import) for a in n.names]
    names += [n.module or "" for n in ast.walk(tree) if isinstance(n, ast.ImportFrom)]
    assert not [n for n in names if n.startswith("repro")]


@pytest.mark.parametrize("seed", range(6))
def test_agrees_with_the_sequential_oracle(seed):
    rng = np.random.default_rng(seed)
    keys = np.arange(12, dtype=np.int32)  # few keys: self-loops, removes and re-adds collide
    n = 3000
    ops = rng.integers(0, 7, n).astype(np.int32)
    us = rng.choice(keys, n).astype(np.int32)
    vs = rng.choice(keys, n).astype(np.int32)
    ref, seq = ReferenceGraph(), SequentialGraph()
    got = ref.apply_all(ops, us, vs)
    want = [seq.apply(int(o), int(u), int(v)) for o, u, v in zip(ops, us, vs)]
    assert got == want
    assert ref.vertices() == seq.vertices and ref.edges() == seq.edges
    for u in range(-1, 13):
        assert ref.bfs(u) == seq.bfs(u)


def test_bfs_levels_on_a_path_and_a_cycle():
    g = ReferenceGraph()
    ops = np.array([1, 1, 1, 1, 4, 4, 4, 4], np.int32)
    us = np.array([0, 1, 2, 3, 0, 1, 2, 3], np.int32)
    vs = np.array([0, 0, 0, 0, 1, 2, 0, 3], np.int32)
    assert all(g.apply_all(ops, us, vs))
    assert g.bfs(0) == {0: 0, 1: 1, 2: 2}
    assert g.bfs(3) == {3: 0}
    assert g.bfs(9) == {}


def test_unknown_op_is_refused():
    with pytest.raises(ValueError):
        ReferenceGraph().apply_all(np.array([7], np.int32), np.zeros(1, np.int32), np.zeros(1, np.int32))
