"""XLA (pure-jnp) compaction primitives — the implementation every backend runs.

The maintenance subsystem (``repro.core.maintenance``) is built from two
primitives that share one sort + prefix-sum core:

* :func:`masked_compact_xla` — stable stream compaction: keep the
  columns of ``values`` whose ``mask`` lane is set, in order, and push the
  rest off the end.  One ``cumsum`` (the prefix sum) turns the mask into
  scatter positions; the result is order-preserving, so every impl of it is
  bit-identical by construction.

* :func:`probe_place_xla` — vectorized quadratic-probe placement:
  insert a set of distinct pre-hashed keys into an empty power-of-two
  table.  The discipline is *priority-ordered claim rounds*, the same one
  :func:`repro.core.locate._claim_slots` uses for engine inserts: every
  pending lane probes its triangular chain for the first currently-empty
  slot, contended slots go to the lowest lane index (scatter-min), winners
  occupy, losers re-probe.  The lowest pending lane always wins its slot,
  so every round places at least one key and the loop is bounded by the
  lane count — placement is wait-free in the same sense as the engines'
  bounded locate.  The round/claim order is fully deterministic, which is
  what lets the host oracle (``maintenance.rehash_host``), this module,
  and the Pallas kernel produce bit-identical tables.

Placement is bounded by ``max_probes`` — callers pass ``MAX_PROBES`` so a
placement that the engines' bounded locate could never find again reports
``overflow`` instead (the caller grows the table and retries, exactly like
a failed engine pass).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_NO_SLOT = -1  # plain int: jnp constants would be captured consts in Pallas


def _probe_slot(home: jnp.ndarray, step: jnp.ndarray, capacity: int) -> jnp.ndarray:
    """Local replica of ``repro.core.hashing.probe_slot`` (triangular
    probing) — the kernel families stay import-free of ``repro.core`` so
    they can be imported standalone (same pattern as ``hash_probe``'s
    ``_mix32`` copy); ``tests/test_kernels.py`` pins the two against each
    other."""
    off = (step * (step + 1)) // 2
    return (home + off) & (capacity - 1)


def masked_compact_xla(
    values: jnp.ndarray,  # i32[R, N] — R payload rows sharing one mask
    mask: jnp.ndarray,    # bool[N]
    *,
    fill: int,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(out i32[R, N], count i32[]): ``out[:, :count]`` is ``values[:, mask]``
    in lane order; the tail is ``fill``."""
    n = mask.shape[0]
    pos = jnp.cumsum(mask.astype(jnp.int32)) - 1
    idx = jnp.where(mask, pos, n)  # dropped lanes scatter out of range
    out = jnp.full(values.shape, fill, values.dtype)
    out = out.at[:, idx].set(values, mode="drop")
    return out, jnp.sum(mask).astype(jnp.int32)


def probe_place_rounds(
    home: jnp.ndarray,    # i32[m] — pre-hashed home slots
    active: jnp.ndarray,  # bool[m] — lanes that carry a key to place
    *,
    capacity: int,
    max_probes: int,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The claim-round loop on values — shared verbatim by the XLA path and
    the Pallas kernel (which runs it on VMEM-resident blocks), so the two
    are bit-identical by construction.  Returns (slots i32[m], overflow
    bool[]); ``slots[i] == -1`` where inactive or unplaced."""
    m = home.shape[0]
    idx = jnp.arange(m, dtype=jnp.int32)
    int_max = jnp.iinfo(jnp.int32).max

    def first_empty(occ, pending):
        def body(step, cand):
            s = _probe_slot(home, jnp.int32(step), capacity)
            take = pending & (cand < 0) & ~occ[s]
            return jnp.where(take, s, cand)

        return jax.lax.fori_loop(0, max_probes, body, jnp.full((m,), _NO_SLOT, jnp.int32))

    def cond(carry):
        _, _, pending, stuck, rounds = carry
        return jnp.any(pending) & ~stuck & (rounds < m)

    def body(carry):
        occ, slots, pending, _, rounds = carry
        cand = first_empty(occ, pending)
        has = pending & (cand >= 0)
        safe = jnp.where(has, cand, 0)
        claim = jnp.full((capacity,), int_max, jnp.int32)
        claim = claim.at[safe].min(jnp.where(has, idx, int_max))
        winner = has & (claim[safe] == idx)
        occ = occ.at[jnp.where(winner, cand, capacity)].set(True, mode="drop")
        slots = jnp.where(winner, cand, slots)
        pending = pending & ~winner
        # no candidate anywhere => no winner can ever appear again: stop
        return occ, slots, pending, ~jnp.any(has), rounds + 1

    occ0 = jnp.zeros((capacity,), bool)
    slots0 = jnp.full((m,), _NO_SLOT, jnp.int32)
    init = (occ0, slots0, active, jnp.asarray(False), jnp.int32(0))
    _, slots, pending, _, _ = jax.lax.while_loop(cond, body, init)
    return slots, jnp.any(pending)


def probe_place_xla(
    home: jnp.ndarray,
    active: jnp.ndarray,
    *,
    capacity: int,
    max_probes: int,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """XLA placement: see :func:`probe_place_rounds`."""
    return probe_place_rounds(home, active, capacity=capacity, max_probes=max_probes)
