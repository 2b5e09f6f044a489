"""Pallas TPU frontier-expansion kernel — the traversal engine's hot loop.

Hardware adaptation (same playbook as ``repro.kernels.hash_probe``): the
CPU lowering of the BFS level step — gather edge sources against the
frontier, scatter-min into edge destinations — is near-serial, and it runs
once per BFS level for every query batch.  Here the boolean frontier tile
and the output row block stay resident in VMEM while the CSR edge arrays
stream through in ``block_e`` chunks:

    grid = (source tiles, edge tiles)

Per (i, j) step: gather the frontier block's values at the edge tile's
source slots (one vectorized VMEM gather), propose ``src`` as parent where
the gather hit, and fold the proposals into the output block with a
scatter-min.  The output block is revisited across the edge-tile axis
(initialised to NBR_INF at j == 0), so the full reduction over all edges
lands without ever leaving VMEM.  Min is associative and commutative, so
the tiled reduction is bit-identical to the XLA implementation regardless
of edge order — which is what lets one scatter serve both frontier
discovery (hit iff result < NBR_INF) and the papers' ``GetPath`` parent
pointers (the result *is* the parent slot).

The ``interpret=True`` path runs the identical kernel through the Pallas
interpreter (``tests/test_frontier_kernel.py`` and the ``kernels-interpret``
CI job).  The v5e compiler refuses the kernel (Mosaic lowers no scatter and
no 1-D gather; ``docs/KERNELS.md``), so the TPU dispatch never selects it.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .xla import NBR_INF

_LANE = 128  # TPU lane width: last-dim blocks are padded to multiples of this


def _expand_kernel(frontier_ref, src_ref, dst_ref, out_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.full(out_ref.shape, NBR_INF, jnp.int32)

    frontier = frontier_ref[...]     # bool[block_s, C_pad]
    src = src_ref[...]               # i32[block_e]
    dst = dst_ref[...]               # i32[block_e]
    on_edge = jnp.take(frontier, src, axis=1)           # vectorized VMEM gather
    cand = jnp.where(on_edge, src[None, :], NBR_INF)    # i32[block_s, block_e]
    out_ref[...] = out_ref[...].at[:, dst].min(cand)    # in-VMEM scatter-min


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


@functools.partial(jax.jit, static_argnames=("block_s", "block_e", "interpret"))
def frontier_expand(
    frontier: jnp.ndarray,  # bool[S, C]
    src: jnp.ndarray,       # i32[Ce], values in [0, C)
    dst: jnp.ndarray,       # i32[Ce], values in [0, C)
    *,
    block_s: int = 8,
    block_e: int = 1024,
    interpret: bool = False,
) -> jnp.ndarray:
    """i32[S, C]: min frontier source slot over in-edges, NBR_INF where none."""
    n_src, c = frontier.shape
    n_edges = src.shape[0]
    block_s = min(block_s, max(n_src, 1))
    block_e = min(block_e, max(n_edges, 1))

    s_pad = _round_up(max(n_src, 1), block_s)
    e_pad = _round_up(max(n_edges, 1), block_e)
    c_pad = _round_up(c, _LANE)
    if c_pad == c and e_pad != n_edges:
        # padded edge lanes park on an all-False padding column so their
        # gather misses; grow one lane block if no padding column exists
        c_pad += _LANE

    f = jnp.zeros((s_pad, c_pad), bool).at[:n_src, :c].set(frontier)
    sp = jnp.full((e_pad,), c_pad - 1, jnp.int32).at[:n_edges].set(src)
    dp = jnp.full((e_pad,), c_pad - 1, jnp.int32).at[:n_edges].set(dst)

    out = pl.pallas_call(
        _expand_kernel,
        grid=(s_pad // block_s, e_pad // block_e),
        in_specs=[
            pl.BlockSpec((block_s, c_pad), lambda i, j: (i, 0)),
            pl.BlockSpec((block_e,), lambda i, j: (j,)),
            pl.BlockSpec((block_e,), lambda i, j: (j,)),
        ],
        out_specs=pl.BlockSpec((block_s, c_pad), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((s_pad, c_pad), jnp.int32),
        interpret=interpret,
    )(f, sp, dp)
    return out[:n_src, :c]
