"""XLA (pure-jnp) frontier expansion — the implementation every backend runs.

One level of the traversal engine's batched BFS is a gather + scatter-min:
every edge lane whose *source* slot is on the frontier proposes its source
slot as the parent of its *destination* slot, and each destination keeps the
minimum proposer.  The scatter-min folds the papers' ``GetPath`` parent
pointer into the same pass that discovers the frontier: a column is newly
reached iff its min proposer is not :data:`NBR_INF`, and that proposer *is*
its BFS parent (deterministic — min is order-independent, so the Pallas
kernel tiling the same reduction matches bit-exactly).

Layout for the TPU: the reduction runs on the transposed ``[C, S]`` frontier,
so each edge lane gathers and scatters one contiguous row of ``S`` sources
(a lane-dense vector) instead of a strided column.  Edges stream through in
blocks of at most ``_BLOCK_ELEMS // S`` lanes, which bounds the
``[block, S]`` proposal tile: materialising all ``[Ce, S]`` proposals at
once is 32 GiB for 1,024 sources over 2²³ edge lanes.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# "no in-frontier neighbor" sentinel: larger than any slot index.
NBR_INF = np.int32(np.iinfo(np.int32).max)

# proposal-tile budget per edge block, in elements (2**24 int32 = 64 MiB)
_BLOCK_ELEMS = 2**24


def _pow2_floor(n: int) -> int:
    return 1 << (max(n, 1).bit_length() - 1)


def edge_blocks(n_edges: int, n_src: int) -> tuple[int, int]:
    """(lanes per block, number of blocks) for a pass over ``n_edges`` edge
    lanes that carries an ``[block, n_src]`` tile: a power of two, at most
    ``_BLOCK_ELEMS`` elements per tile."""
    block = min(_pow2_floor(_BLOCK_ELEMS // max(n_src, 1)), _pow2_floor(n_edges))
    return block, -(-n_edges // block)


def frontier_expand_xla(
    frontier: jnp.ndarray,  # bool[S, C] — per-source frontier masks
    src: jnp.ndarray,       # i32[Ce] — edge source slots, values in [0, C)
    dst: jnp.ndarray,       # i32[Ce] — edge destination slots, values in [0, C)
) -> jnp.ndarray:
    """i32[S, C]: min frontier source slot over in-edges, NBR_INF where none."""
    n_src, c = frontier.shape
    n_edges = src.shape[0]
    block, n_blocks = edge_blocks(n_edges, n_src)
    pad = n_blocks * block - n_edges
    # row C of the transposed frontier is all False: padding lanes park there
    ft = jnp.zeros((c + 1, n_src), bool).at[:c].set(frontier.T)
    src = jnp.concatenate([src.astype(jnp.int32), jnp.full((pad,), c, jnp.int32)])
    dst = jnp.concatenate([dst.astype(jnp.int32), jnp.full((pad,), c, jnp.int32)])

    def body(i, out):
        s = jax.lax.dynamic_slice(src, (i * block,), (block,))
        d = jax.lax.dynamic_slice(dst, (i * block,), (block,))
        cand = jnp.where(ft[s], s[:, None], NBR_INF)
        return out.at[d].min(cand)

    out = jnp.full((c + 1, n_src), NBR_INF, jnp.int32)
    out = jax.lax.fori_loop(0, n_blocks, body, out)
    return out[:c].T
