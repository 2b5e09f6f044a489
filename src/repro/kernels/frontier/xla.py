"""XLA (pure-jnp) frontier expansion — the implementation every backend runs.

One level of the traversal engine's batched BFS: every edge lane whose
*source* slot is on the frontier proposes its source slot as the parent of
its *destination* slot, and each destination keeps the minimum proposer.  A
column is newly reached iff its min proposer is not :data:`NBR_INF`, and
that proposer *is* its BFS parent (the papers' ``GetPath`` pointer;
deterministic — min is order-independent, so the Pallas kernel tiling the
same reduction matches bit-exactly).

**Pull direction.**  The expansion runs over a destination-sorted view of
the lanes (:func:`pull_view`): one sort by destination with the source
riding along, so each destination's in-edges are one contiguous segment.
A level gathers the frontier bits at each lane's source, takes a segmented
min-scan of the proposals along the lanes, and reads each destination's
answer at the last lane of its segment — one gather per destination, and
no scatter.  The view is built once and serves every level: the traversal's
level loop builds it once per call, before the loop.

**Live blocks only.**  Lanes stream in blocks of at most
``_BLOCK_ELEMS // S`` lanes, which bounds the ``[S, block]`` proposal tile
(64 MiB); all ``[S, Ce]`` proposals at once would be 32 GiB for 1,024
sources over 2²³ lanes.  A view built with ``n_live`` streams only the
blocks that hold one of the first ``n_live`` lanes — a trip count computed
on the device — so a snapshot's invalid lanes, which its CSR pushes to the
end, are never read.  The scan's carry crosses block boundaries, as a
hub's segment can span several blocks.

**Layout for the TPU.**  The scan keeps the long lane axis minor
(``[S, block]``): with ``S`` minor a tile would pad 16 sources to 128
lanes.  The frontier bits are gathered as rows of the transposed
``[C, S]`` frontier and packed into one int32 word per lane (32 sources a
word) before they are unpacked along the lanes; the 1-D word array keeps
the gather's row layout from spreading into the scan.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

# "no in-frontier neighbor" sentinel: larger than any slot index.
NBR_INF = np.int32(np.iinfo(np.int32).max)

# proposal-tile budget per edge block, in elements (2**24 int32 = 64 MiB)
_BLOCK_ELEMS = 2**24

_LANE = 128  # TPU lane width: the scan's first level runs within rows of this many lanes
_WORD = 32   # frontier bits packed per int32 word


def _pow2_floor(n: int) -> int:
    return 1 << (max(n, 1).bit_length() - 1)


def edge_blocks(n_edges: int, n_src: int) -> tuple[int, int]:
    """(lanes per block, number of blocks) for a pass over ``n_edges`` edge
    lanes that carries an ``[block, n_src]`` tile: a power of two, at most
    ``_BLOCK_ELEMS`` elements per tile.  The ``n_edges`` lanes padded to
    whole blocks split into the same blocks."""
    block = min(_pow2_floor(_BLOCK_ELEMS // max(n_src, 1)), 1 << (max(n_edges, 1) - 1).bit_length())
    return block, -(-n_edges // block)


class PullView(NamedTuple):
    """The lanes sorted by destination, for ``n_src`` sources over ``C``
    columns; ``n_blocks * block`` lanes, the padding lanes (``src == dst ==
    C``) at the end."""

    src: jnp.ndarray       # i32[L] — source slot per lane, in destination order
    dst: jnp.ndarray       # i32[L] — destination slot per lane, non-decreasing
    end: jnp.ndarray       # i32[C] — each column's last lane in a live block, -1 where none
    n_blocks: jnp.ndarray  # i32[] — blocks that hold a live lane


def pull_view(
    src: jnp.ndarray,  # i32[Ce], values in [0, C)
    dst: jnp.ndarray,  # i32[Ce], values in [0, C)
    n_cols: int,
    n_src: int,
    n_live: Optional[jnp.ndarray] = None,
) -> PullView:
    """Sort the lanes by destination, the source as payload.

    One stable sort keyed by ``dst``: a CSR's lanes, sorted by source,
    come out sorted by ``(dst, src)``.  ``n_live`` (default: every lane)
    says that only the first ``n_live`` lanes of the *sorted* order may
    propose — a CSR's valid lanes, whose invalid ones (``dst == C - 1``
    with an always-empty frontier column) sort last."""
    n_lanes = src.shape[0]
    block, n_blk = edge_blocks(n_lanes, n_src)
    d, s = jax.lax.sort(
        (dst.astype(jnp.int32), src.astype(jnp.int32)), num_keys=1, is_stable=True
    )
    pad = jnp.full((n_blk * block - n_lanes,), n_cols, jnp.int32)
    d = jnp.concatenate([d, pad])
    s = jnp.concatenate([s, pad])
    last = jnp.concatenate([d[1:] != d[:-1], jnp.ones((1,), bool)]) & (d < n_cols)
    live = n_lanes if n_live is None else n_live
    n_blocks = (jnp.asarray(live, jnp.int32) + (block - 1)) // block

    def mark_ends(i, end):
        # one write per column whose segment ends in the block; every other
        # lane writes to a distinct negative index, which is dropped
        pos = i * block + jnp.arange(block, dtype=jnp.int32)
        at = jnp.where(jax.lax.dynamic_slice(last, (i * block,), (block,)),
                       jax.lax.dynamic_slice(d, (i * block,), (block,)), -1 - pos)
        return end.at[at].set(pos, mode="drop", unique_indices=True, wrap_negative_indices=False)

    end = jax.lax.fori_loop(0, n_blocks, mark_ends, jnp.full((n_cols,), -1, jnp.int32))
    return PullView(src=s, dst=d, end=end, n_blocks=n_blocks)


def _pack_rows(frontier: jnp.ndarray) -> jnp.ndarray:
    """bool[C + 1, n_words * width]: the transposed frontier, an all-False
    row C for the padding lanes, the sources padded to whole words."""
    n_src, c = frontier.shape
    width = min(n_src, _WORD)
    n_words = -(-n_src // width)
    rows = jnp.zeros((c + 1, n_words * width), bool)
    return rows.at[:c, :n_src].set(frontier.T)


def _hits(rows: jnp.ndarray, s: jnp.ndarray, n_src: int) -> jnp.ndarray:
    """bool[S, B]: the frontier bit of every source at each lane's ``s``."""
    width = min(n_src, _WORD)
    got = rows[s].astype(jnp.int32) << (jnp.arange(rows.shape[1], dtype=jnp.int32) % width)
    shift = jnp.arange(width, dtype=jnp.int32)[:, None]
    bits = []
    for w in range(rows.shape[1] // width):
        word = jnp.sum(got[:, w * width:(w + 1) * width], axis=1)  # i32[B]
        bits.append((word[None, :] >> shift) & 1)
    return jnp.concatenate(bits)[:n_src].astype(bool)


def _shift(x: jnp.ndarray, k: int, fill) -> jnp.ndarray:
    """``x`` moved ``k`` places along its last axis, ``fill`` shifted in."""
    widths = [(0, 0)] * (x.ndim - 1) + [(k, 0)]
    return jnp.pad(x[..., :-k], widths, constant_values=fill)


def _doubling_min(v: jnp.ndarray, d: jnp.ndarray) -> jnp.ndarray:
    """Inclusive min-scan of ``v`` along its last axis within runs of equal
    ``d`` (runs are contiguous): one shifted min per doubling step."""
    k = 1
    while k < v.shape[-1]:
        same = _shift(d, k, -1) == d
        v = jnp.where(same, jnp.minimum(v, _shift(v, k, NBR_INF)), v)
        k *= 2
    return v


def _segment_min(val, d, carry_val, carry_d):
    """Inclusive min-scan of ``val`` (i32[S, B]) along the lanes within runs
    of equal ``d``; the run that ``carry_d`` ends the previous block with
    continues from ``carry_val``.  Two levels, so every pass over the
    ``[S, B]`` tile stays elementwise: a doubling scan within rows of
    ``_LANE`` lanes, then one over the rows' last lanes, then a fix-up.
    Returns (scan, the carry for the next block)."""
    n_src, b = val.shape
    w = min(_LANE, b)
    v = _doubling_min(val.reshape(n_src, b // w, w), d.reshape(b // w, w)[None])
    d_rows = d.reshape(b // w, w)
    is_last = jnp.arange(w) == w - 1
    tail = jnp.min(jnp.where(is_last, v, NBR_INF), axis=2)  # [S, R]
    tail = jnp.concatenate([carry_val[:, None], tail], axis=1)
    d_tail = jnp.concatenate([carry_d[None], d_rows[:, w - 1]])
    tail = _doubling_min(tail, d_tail[None])
    before = (d_rows == d_tail[:-1, None])[None]  # the run began before the row
    v = jnp.where(before, jnp.minimum(v, tail[:, :-1, None]), v)
    return v.reshape(n_src, b), (tail[:, -1], d_tail[-1])


def frontier_expand_pull(frontier: jnp.ndarray, view: PullView) -> jnp.ndarray:
    """i32[S, C]: min frontier source slot over in-edges, NBR_INF where
    none, over the live blocks of ``view``."""
    n_src, c = frontier.shape
    block, _ = edge_blocks(view.src.shape[0], n_src)
    rows = _pack_rows(frontier)

    def body(i, carry):
        out, carry_val, carry_d = carry
        s = jax.lax.dynamic_slice(view.src, (i * block,), (block,))
        d = jax.lax.dynamic_slice(view.dst, (i * block,), (block,))
        val = jnp.where(_hits(rows, s, n_src), s[None, :], NBR_INF)
        val, (carry_val, carry_d) = _segment_min(val, d, carry_val, carry_d)
        # the columns whose segment ends in this block read their answer
        got = jnp.take(val, view.end - i * block, axis=1, mode="clip")
        ends_here = (view.end >= i * block) & (view.end < (i + 1) * block)
        return jnp.where(ends_here, got, out), carry_val, carry_d

    init = (
        jnp.full((n_src, c), NBR_INF, jnp.int32),
        jnp.full((n_src,), NBR_INF, jnp.int32),
        jnp.int32(-1),
    )
    return jax.lax.fori_loop(0, view.n_blocks, body, init)[0]


def frontier_expand_xla(
    frontier: jnp.ndarray,  # bool[S, C] — per-source frontier masks
    src: jnp.ndarray,       # i32[Ce] — edge source slots, values in [0, C)
    dst: jnp.ndarray,       # i32[Ce] — edge destination slots, values in [0, C)
) -> jnp.ndarray:
    """i32[S, C]: min frontier source slot over in-edges, NBR_INF where
    none: :func:`frontier_expand_pull` over a view built for this call."""
    n_src, c = frontier.shape
    return frontier_expand_pull(frontier, pull_view(src, dst, c, n_src))
