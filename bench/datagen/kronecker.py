"""Graph500 Kronecker graph: its structure from the configuration, its
labels and load order from the run's seed.

The generator of the Graph500 specification (its reference
``kronecker_generator.m``): ``edgefactor * 2**scale`` edges, each placed by
``scale`` independent choices of a quadrant of the adjacency matrix with
initiator probabilities A, B, C and D = 1 - A - B - C; then the vertex
labels are permuted and the edge list shuffled.  Self-loops and duplicate
edges stay, as the generator makes them.  Every label is a vertex of the
graph (isolated ones too), and each edge is loaded in both directions.

The quadrant choices come from the configuration's ``graph_seed``, so every
run searches the same graph: a BFS call's work is the depth of its sources'
searches, and a graph made anew from each run's seed changes that work from
run to run far more than the run's own noise does.  The run's seed permutes
the labels and the order of the load.
"""

from __future__ import annotations

import numpy as np

from bench.reference import ADD_EDGE, ADD_VERTEX, CONTAINS_EDGE, CONTAINS_VERTEX


def kronecker_edges(rng: np.random.Generator, scale: int, edgefactor: int, a: float, b: float, c: float):
    """(start, end) of the generated edges before the labels are permuted,
    int64[edgefactor * 2**scale] each."""
    n, m = 2**scale, edgefactor * 2**scale
    ab = a + b
    c_norm = c / (1.0 - ab)
    a_norm = a / ab
    ij = np.zeros((2, m), np.int64)
    for ib in range(scale):
        ii_bit = rng.random(m) > ab
        jj_bit = rng.random(m) > np.where(ii_bit, c_norm, a_norm)
        ij[0] += ii_bit.astype(np.int64) << ib
        ij[1] += jj_bit.astype(np.int64) << ib
    return ij[0], ij[1]


def build(cfg: dict, load_batch: int, rng: np.random.Generator) -> dict:
    """Vertex adds for every label, then both directions of every generated
    edge in a shuffled order, then contains ops up to a whole number of load
    batches.  ``search_keys`` are the vertices Graph500 draws search keys
    from: degree at least 1, self-loops not counted; ``labels[i]`` is the
    key of generated vertex ``i``."""
    n = 2 ** cfg["scale"]
    start, end = kronecker_edges(
        np.random.default_rng(cfg["graph_seed"]),
        cfg["scale"], cfg["edgefactor"], cfg["initiator_a"], cfg["initiator_b"], cfg["initiator_c"],
    )
    labels = rng.permutation(n)
    start, end = labels[start], labels[end]
    keys = np.arange(n, dtype=np.int32)
    eu = np.concatenate([start, end]).astype(np.int32)
    ev = np.concatenate([end, start]).astype(np.int32)
    order = rng.permutation(eu.size)
    eu, ev = eu[order], ev[order]
    ops = np.concatenate([np.full(n, ADD_VERTEX), np.full(eu.size, ADD_EDGE)]).astype(np.int32)
    us = np.concatenate([keys, eu])
    vs = np.concatenate([np.zeros(n, np.int32), ev])
    fill = -ops.size % load_batch
    f_ops = rng.choice([CONTAINS_VERTEX, CONTAINS_EDGE], fill).astype(np.int32)
    f_us = rng.choice(keys, fill).astype(np.int32)
    f_vs = rng.choice(keys, fill).astype(np.int32)
    loop = start == end
    degree = np.bincount(start[~loop], minlength=n) + np.bincount(end[~loop], minlength=n)
    return dict(
        keys=keys,
        eu=eu,
        ev=ev,
        search_keys=keys[degree > 0],
        labels=labels.astype(np.int32),
        load=(
            np.concatenate([ops, f_ops]),
            np.concatenate([us, f_us]),
            np.concatenate([vs, f_vs]),
        ),
    )
