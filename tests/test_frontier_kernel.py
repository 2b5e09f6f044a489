"""Frontier-expansion kernel parity: Pallas interpret mode vs the XLA
implementation, bit-exact, standalone and end-to-end through the traversal engine.

The kernel's contract is exact (integer scatter-min — no tolerances): the
tiled VMEM reduction must match the XLA path for any frontier/CSR input,
including the padding paths (lane-aligned widths, ragged edge counts), and
the whole BFS must produce identical levels/parents through either impl.
"""

import numpy as np
import pytest
import jax.numpy as jnp

from repro.core import SequentialGraph, WaitFreeGraph, bfs_parents, build_csr
from repro.core.workloads import sample_batch
from repro.kernels.frontier import (
    NBR_INF,
    frontier_expand,
    frontier_expand_xla,
    pull_view,
    xla as frontier_xla,
)
from repro.kernels.frontier.xla import frontier_expand_pull

KEY_SPACE = 24


@pytest.mark.parametrize(
    "S,C,Ce",
    [
        (1, 5, 3),        # degenerate: single source, tiny graph
        (3, 1, 1),        # single column
        (4, 65, 100),     # ragged everything
        (8, 128, 1000),   # lane-aligned C, ragged Ce (forces the extra block)
        (16, 257, 4096),  # multi-tile on both grid axes
        (5, 300, 2100),   # ragged S (padding rows) and Ce
        (33, 70, 1000),   # sources past one packed word (XLA pull path)
        (64, 40, 3000),   # two whole words
    ],
)
def test_frontier_expand_parity_random(S, C, Ce):
    rng = np.random.default_rng(S * 1009 + C * 31 + Ce)
    frontier = jnp.asarray(rng.random((S, C)) < 0.3)
    src = jnp.asarray(rng.integers(0, C, Ce).astype(np.int32))
    dst = jnp.asarray(rng.integers(0, C, Ce).astype(np.int32))
    ref = frontier_expand_xla(frontier, src, dst)
    ker = frontier_expand(frontier, src, dst, impl="kernel_interpret")
    np.testing.assert_array_equal(np.asarray(ker), np.asarray(ref))


def test_frontier_expand_empty_frontier_and_parent_semantics():
    rng = np.random.default_rng(7)
    C, Ce = 40, 200
    src = jnp.asarray(rng.integers(0, C, Ce).astype(np.int32))
    dst = jnp.asarray(rng.integers(0, C, Ce).astype(np.int32))
    # empty frontier: nothing proposed anywhere
    empty = jnp.zeros((4, C), bool)
    out = frontier_expand(empty, src, dst, impl="kernel_interpret")
    assert (np.asarray(out) == NBR_INF).all()
    # full frontier: every column with an in-edge gets its min in-neighbor
    full = jnp.ones((2, C), bool)
    out = np.asarray(frontier_expand(full, src, dst, impl="kernel_interpret"))
    src_np, dst_np = np.asarray(src), np.asarray(dst)
    for d in range(C):
        preds = src_np[dst_np == d]
        expect = preds.min() if preds.size else NBR_INF
        assert out[0, d] == out[1, d] == expect


def test_frontier_expand_block_tilings_agree():
    """The reduction must be tiling-invariant: any (block_s, block_e) split
    yields the same bits (min is associative + commutative)."""
    from repro.kernels.frontier.kernel import frontier_expand as raw_kernel

    rng = np.random.default_rng(11)
    S, C, Ce = 8, 100, 600
    frontier = jnp.asarray(rng.random((S, C)) < 0.25)
    src = jnp.asarray(rng.integers(0, C, Ce).astype(np.int32))
    dst = jnp.asarray(rng.integers(0, C, Ce).astype(np.int32))
    ref = np.asarray(frontier_expand_xla(frontier, src, dst))
    for block_s, block_e in [(1, 64), (4, 128), (8, 600), (8, 4096)]:
        got = raw_kernel(
            frontier, src, dst, block_s=block_s, block_e=block_e, interpret=True
        )
        np.testing.assert_array_equal(np.asarray(got), ref)


def _churned_graph(seed: int):
    rng = np.random.default_rng(seed)
    g, o = WaitFreeGraph(256, 1024), SequentialGraph()
    for _ in range(2):
        ops, us, vs = sample_batch(rng, 160, "traversal", key_space=KEY_SPACE)
        got = g.apply(ops, us, vs)
        from repro.core import run_sequential

        exp, _ = run_sequential(ops, us, vs, graph=o)
        assert got.tolist() == exp
    return g, o, rng


@pytest.mark.parametrize("seed", range(4))
def test_bfs_through_kernel_matches_reference_and_oracle(seed):
    """End-to-end: the whole level loop through the interpret-mode kernel is
    bit-identical to the XLA impl, and both match the oracle."""
    g, o, rng = _churned_graph(seed)
    csr = build_csr(g.state)
    keys = jnp.asarray(rng.integers(0, KEY_SPACE, 8).astype(np.int32))
    lv_ref, par_ref = bfs_parents(csr, keys, impl="xla")
    lv_ker, par_ker = bfs_parents(csr, keys, impl="kernel_interpret")
    np.testing.assert_array_equal(np.asarray(lv_ker), np.asarray(lv_ref))
    np.testing.assert_array_equal(np.asarray(par_ker), np.asarray(par_ref))

    v_key = np.asarray(csr.v_key)
    for s, row in zip(np.asarray(keys), np.asarray(lv_ker)):
        hit = np.nonzero(row >= 0)[0]
        assert {int(v_key[j]): int(row[j]) for j in hit} == o.bfs(int(s))


# ---------------------------------------------------------------------------
# the XLA pull path against a per-destination numpy min
# ---------------------------------------------------------------------------


def _numpy_min(frontier, src, dst):
    """i32[S, C]: per destination, the least source slot on the frontier
    among its in-edges; NBR_INF where none."""
    out = np.full(frontier.shape, NBR_INF, np.int32)
    for row, f in zip(out, frontier):
        on = f[src]
        np.minimum.at(row, dst[on], src[on])
    return out


def _csr_lanes(rng, cv, n_valid, ce, hub=None):
    """CSR-shaped lanes over ``cv`` vertex slots: ``n_valid`` edges sorted
    by source, then the invalid lanes (``src == dst == cv``); ``hub`` takes
    half of the in-edges."""
    src = np.sort(rng.integers(0, cv, n_valid)).astype(np.int32)
    dst = rng.integers(0, cv, n_valid).astype(np.int32)
    if hub is not None:
        dst[rng.random(n_valid) < 0.5] = hub
    pad = np.full(ce - n_valid, cv, np.int32)
    return np.concatenate([src, pad]), np.concatenate([dst, pad])


@pytest.mark.parametrize(
    "S,cv,n_valid,ce,block_elems,density,hub",
    [
        (16, 300, 1000, 2048, None, 0.3, None),   # one block, invalid tail
        (8, 200, 700, 1024, 2**9, 0.3, None),     # 64-lane blocks: 11 live of 16, last one ragged
        (4, 100, 900, 1024, 2**9, 0.5, 7),        # a hub whose segment crosses several blocks
        (16, 64, 1500, 2048, 2**10, 0.2, 63),     # a hub in the last column before the invalid tail
        (5, 120, 0, 512, None, 0.4, None),        # every lane invalid
        (16, 150, 600, 1024, 2**9, 0.0, None),    # empty frontier
        (40, 90, 800, 1024, 2**11, 0.3, 5),       # two packed words, 32-lane blocks
        (6, 80, 500, 700, 2**9, 0.3, 11),         # a lane count that is no power of two: padded view
    ],
)
def test_pull_path_matches_numpy_min(monkeypatch, S, cv, n_valid, ce, block_elems, density, hub):
    """The pull view over CSR-shaped lanes, streaming only the live blocks,
    matches a per-destination numpy min; so do the raw-array entry point
    (every lane live) and the Pallas kernel in interpret mode."""
    if block_elems is not None:
        monkeypatch.setattr(frontier_xla, "_BLOCK_ELEMS", block_elems)
    rng = np.random.default_rng(S * 7919 + cv * 31 + n_valid)
    src, dst = _csr_lanes(rng, cv, n_valid, ce, hub)
    frontier = np.zeros((S, cv + 1), bool)  # column cv: the invalid lanes' slot
    frontier[:, :cv] = rng.random((S, cv)) < density
    want = _numpy_min(frontier, src, dst)
    assert (want == NBR_INF).any()

    f, s, d = jnp.asarray(frontier), jnp.asarray(src), jnp.asarray(dst)
    view = pull_view(s, d, cv + 1, S, n_live=jnp.int32(n_valid))
    block, _ = frontier_xla.edge_blocks(ce, S)
    assert int(view.n_blocks) == -(-n_valid // block)
    np.testing.assert_array_equal(np.asarray(frontier_expand_pull(f, view)), want)
    np.testing.assert_array_equal(np.asarray(frontier_expand_xla(f, s, d)), want)
    np.testing.assert_array_equal(
        np.asarray(frontier_expand(f, s, d, impl="kernel_interpret")), want
    )
