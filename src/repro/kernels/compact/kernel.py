"""Pallas TPU kernels for the state-maintenance compaction primitives.

Hardware adaptation (same playbook as ``hash_probe`` and ``frontier``):

* ``masked_compact`` — stable stream compaction is a prefix sum plus a
  scatter.  The mask/value arrays stream through VMEM in ``block_n``
  chunks along a sequential grid while the full output block stays
  resident; a running offset carried in the ``count`` output turns each
  chunk's local ``cumsum`` into global scatter positions.  Compaction is
  order-preserving, so the chunked result is bit-identical to the one-shot
  XLA path.

* ``probe_place`` — vectorized quadratic-probe placement.  The occupancy
  bitmap and the claim column live on-chip for the whole round loop (the
  same residency argument as ``hash_probe`` keeping the key column in
  VMEM: a 2²⁰-slot occupancy map is 1 MiB), and each round is one
  vectorized gather (first-empty probe) plus one scatter-min (claim).  The
  round loop itself is :func:`repro.kernels.compact.xla.probe_place_rounds`
  — shared verbatim with the XLA path, so kernel and XLA path
  are bit-identical by construction.

The ``interpret=True`` path runs the identical kernels through the Pallas
interpreter; CI forces it on CPU (the ``kernels-interpret`` job).  The v5e
compiler refuses both kernels (no ``cumsum`` and no 1-D gather in Mosaic;
``docs/KERNELS.md``), so the TPU dispatch never selects them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .xla import probe_place_rounds


def _compact_kernel(values_ref, mask_ref, out_ref, count_ref, *, n_pad: int, fill: int):
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.full(out_ref.shape, fill, out_ref.dtype)
        count_ref[...] = jnp.zeros((1,), jnp.int32)

    mask = mask_ref[...]             # bool[block_n]
    vals = values_ref[...]           # i32[R, block_n]
    offset = count_ref[0]            # survivors placed by earlier chunks
    local = jnp.cumsum(mask.astype(jnp.int32)) - 1
    idx = jnp.where(mask, offset + local, n_pad)   # dropped lanes: out of range
    out_ref[...] = out_ref[...].at[:, idx].set(vals, mode="drop")
    count_ref[...] = count_ref[...] + jnp.sum(mask.astype(jnp.int32))


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


@functools.partial(jax.jit, static_argnames=("fill", "block_n", "interpret"))
def masked_compact(
    values: jnp.ndarray,  # i32[R, N]
    mask: jnp.ndarray,    # bool[N]
    *,
    fill: int,
    block_n: int = 1024,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(out i32[R, N], count i32[]) — see the XLA path for the contract."""
    r, n = values.shape
    block_n = min(block_n, max(n, 1))
    n_pad = _round_up(max(n, 1), block_n)
    v = jnp.full((r, n_pad), fill, values.dtype).at[:, :n].set(values)
    m = jnp.zeros((n_pad,), bool).at[:n].set(mask)

    kernel = functools.partial(_compact_kernel, n_pad=n_pad, fill=fill)
    out, count = pl.pallas_call(
        kernel,
        grid=(n_pad // block_n,),
        in_specs=[
            pl.BlockSpec((r, block_n), lambda j: (0, j)),
            pl.BlockSpec((block_n,), lambda j: (j,)),
        ],
        out_specs=[
            pl.BlockSpec((r, n_pad), lambda j: (0, 0)),  # revisited: global scatter
            pl.BlockSpec((1,), lambda j: (0,)),          # running offset carry
        ],
        out_shape=[
            jax.ShapeDtypeStruct((r, n_pad), values.dtype),
            jax.ShapeDtypeStruct((1,), jnp.int32),
        ],
        interpret=interpret,
    )(v, m)
    return out[:, :n], count[0]


def _place_kernel(home_ref, active_ref, slots_ref, over_ref, *, capacity: int, max_probes: int):
    slots, overflow = probe_place_rounds(
        home_ref[...], active_ref[...], capacity=capacity, max_probes=max_probes
    )
    slots_ref[...] = slots
    over_ref[...] = overflow.reshape(1)


@functools.partial(jax.jit, static_argnames=("capacity", "max_probes", "interpret"))
def probe_place(
    home: jnp.ndarray,    # i32[m]
    active: jnp.ndarray,  # bool[m]
    *,
    capacity: int,
    max_probes: int,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(slots i32[m], overflow bool[]) — see the XLA path for the contract."""
    m = home.shape[0]
    kernel = functools.partial(_place_kernel, capacity=capacity, max_probes=max_probes)
    slots, over = pl.pallas_call(
        kernel,
        grid=(1,),
        in_specs=[
            pl.BlockSpec((m,), lambda i: (0,)),
            pl.BlockSpec((m,), lambda i: (0,)),
        ],
        out_specs=[
            pl.BlockSpec((m,), lambda i: (0,)),
            pl.BlockSpec((1,), lambda i: (0,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m,), jnp.int32),
            jax.ShapeDtypeStruct((1,), jnp.bool_),
        ],
        interpret=interpret,
    )(home, active)
    return slots, over[0]
