"""Device-side state maintenance: rehash, physical deletion, CSR delta-merge.

The paper keeps its graph *unbounded* by growing and compacting the
vertex/edge tables; its practicality rests on that maintenance never
stalling the mutation path.  This module is the device-resident analogue of
the physical-deletion/compaction discipline of arXiv 2310.02380's wait-free
snapshot graphs: three operations sharing one sort + prefix-sum core (the
:mod:`repro.kernels.compact` primitives).

1. **live-compact** (:func:`rehash`) — mask the live vertices and the
   incarnation-valid live edges, compact them in table-slot order
   (``masked_compact``), and bulk re-insert into the grown tables with the
   vectorized quadratic-probe placement kernel (``probe_place``).  This
   replaces the per-element Python loops the host rehash used to run.
   Placement is bounded by ``MAX_PROBES`` — the engines' own locate bound,
   so every placed key is locatable by construction; a placement that
   would exceed it reports ``ok=False`` and the caller grows further
   (exactly the transactional grow-and-retry the engines already use).

2. **snapshot-compact** (``rehash(..., with_csr=True)``) — the compaction
   already knows every surviving edge's endpoint slots in the *new* table
   (an old-slot → new-slot scatter), so the dense :class:`TraversalCSR`
   falls out of the same pass without re-probing anything: ``build_csr``
   after a growth event costs one argsort instead of a full bounded-probe
   relocate.  The result is bit-identical to ``build_csr`` on the new
   state.

3. **delta-merge** (:func:`delta_merge`) — the device half of
   :func:`repro.core.traversal.apply_delta`: drop the lanes invalidated by
   the batch, scatter the survivors and the O(batch)-sized delta back to
   edge-table lane order, and restore the rebuild's (source slot, lane)
   order with one stable sort — no host round-trip and no O(capacity)
   re-probe.  Bit-identical to a full rebuild by construction, at any
   table size (no composite key has to fit in int32).

Impl selection (the ``maintenance_impl`` flag on ``WaitFreeGraph``):

* ``"host"`` — the numpy oracle (:func:`rehash_host`, and the numpy splice
  of ``apply_delta``): vectorized claim rounds with the *identical*
  discipline, kept as the reference every device path must match
  bit-exactly.
* ``"device"`` — jitted device passes over the :mod:`repro.kernels.compact`
  primitives (their XLA implementation on every backend;
  ``REPRO_COMPACT_IMPL`` overrides).
* ``"device_interpret"`` — the same, with the compact Pallas kernels run
  through the interpreter (CI parity on CPU; the TPU compiler refuses
  them, see ``docs/KERNELS.md``).
* ``None`` — auto: ``"device"`` on TPU, ``"host"`` elsewhere (XLA CPU
  lowers the scatter/sort core near-serially, so the host oracle wins
  there).

All impls produce bit-identical tables: placement is priority-ordered
claim rounds (lowest compaction index wins each contended slot), which is
deterministic and order-independent of how the rounds are vectorized —
see ``repro.kernels.compact.xla`` and ``docs/KERNELS.md`` (the shared
``kernel/ops/xla`` contract).

**Linearization point** (mirroring the paper's growth argument): *a rehash
linearizes at the batch boundary that triggered it — the caller discards
the overflowing post-state and re-applies the same batch against the grown
pre-state, so no operation ever observes a half-compacted table, and the
abstract graph before and after the rehash is identical* (physical deletion
only reclaims tombstones and incarnation-stale edges, which are already
outside the abstract state).  A ``delta_merge`` inherits the linearization
point of the CSR it folds into (:mod:`repro.core.traversal`).  Under
hash-prefix sharding (:mod:`repro.core.sharding`) each shard rehashes its
own partitioned tables with this exact code — placement is per-shard by
construction — except that edge validity is judged against the *global*
sorted endpoint index (the ``endpoints`` override on :func:`rehash`):
an edge's endpoints generally live on other shards, and a shard-local
check would wrongly discard every cross-shard edge.

Telemetry: when an obs registry is active (``repro.obs``), host placement
records a ``maintenance.claim_rounds`` histogram and :func:`rehash` wraps
itself in a ``maintenance.rehash.<impl>`` span — catalogued in
``docs/OBSERVABILITY.md``.  None of it alters the computed tables.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.compact import masked_compact, probe_place
from repro.kernels.compact.ops import resolve as _resolve_compact_impl

# ambient telemetry (no-op unless a registry is active — see repro.obs;
# metrics imports nothing from repro.core, so this is cycle-free)
from ..obs import metrics as obsm
from .hashing import edge_hash32_np, hash_edge, hash_vertex, vertex_hash32_np
from .traversal import (
    TraversalCSR,
    _delta_probe_parts,
    _edge_validity,
    build_csr,
    csr_from_lanes,
)
from .types import ABSENT_INC, EMPTY_KEY, MAX_PROBES, GraphState

MAINTENANCE_IMPLS = (None, "host", "device", "device_interpret")


def resolve_impl(impl: Optional[str]) -> str:
    """``None`` -> the backend's best impl (device on TPU, host elsewhere)."""
    assert impl in MAINTENANCE_IMPLS, impl
    if impl is None:
        return "device" if jax.default_backend() == "tpu" else "host"
    return impl


def _primitive_impl(impl: Optional[str]) -> str:
    """Map a maintenance-level impl to a kernels.compact impl string
    (resolved eagerly so it is a static jit argument)."""
    if impl == "device_interpret":
        return "kernel_interpret"
    return _resolve_compact_impl(None)


# ---------------------------------------------------------------------------
# host oracle: vectorized numpy claim rounds (the bit-identity reference)
# ---------------------------------------------------------------------------


def _vhome_np(keys: np.ndarray, capacity: int) -> np.ndarray:
    # home slots from the shared numpy hash twins (repro.core.hashing keeps
    # them next to the jnp source so the oracle can never drift)
    return (vertex_hash32_np(keys) & np.uint32(capacity - 1)).astype(np.int32)


def _ehome_np(us: np.ndarray, vs: np.ndarray, capacity: int) -> np.ndarray:
    return (edge_hash32_np(us, vs) & np.uint32(capacity - 1)).astype(np.int32)


def _probe_place_host(
    home: np.ndarray, capacity: int, max_probes: int
) -> Tuple[np.ndarray, bool]:
    """numpy mirror of ``repro.kernels.compact.probe_place_rounds`` for
    all-active lanes: identical rounds, claims, and tie-breaks, so the
    resulting placement is bit-identical to the device paths."""
    m = home.shape[0]
    occ = np.zeros(capacity, bool)
    slots = np.full(m, -1, np.int32)
    pending = np.ones(m, bool)
    idx = np.arange(m, dtype=np.int64)
    int_max = np.iinfo(np.int32).max
    rounds = 0
    while pending.any() and rounds < m:
        cand = np.full(m, -1, np.int32)
        for step in range(max_probes):
            s = (home + step * (step + 1) // 2) & (capacity - 1)
            take = pending & (cand < 0) & ~occ[s]
            cand[take] = s[take]
        has = pending & (cand >= 0)
        if not has.any():
            break  # no candidate anywhere: overflow
        claim = np.full(capacity, int_max, np.int64)
        np.minimum.at(claim, cand[has], idx[has])
        safe = np.where(has, cand, 0)
        winner = has & (claim[safe] == idx)
        occ[cand[winner]] = True
        slots[winner] = cand[winner]
        pending &= ~winner
        rounds += 1
    # rounds-per-placement is the helping bound's maintenance-side twin;
    # the loop counts them regardless — obs just files the number
    obsm.hist("maintenance.claim_rounds", rounds)
    return slots, bool(pending.any())


def rehash_host(
    state: GraphState,
    new_vcap: int,
    new_ecap: int,
    endpoints: Optional[Tuple[np.ndarray, np.ndarray]] = None,
) -> Tuple[GraphState, bool]:
    """Grow + compact on the host (numpy): keep live vertices (with
    incarnations) and incarnation-valid live edges only — Harris physical
    deletion, batched.  This is the oracle the device paths are tested
    bit-identical against; it is vectorized numpy throughout (the
    per-element Python loops it replaced live only in git history).

    ``endpoints``, when given, is the sorted global ``(keys, incs)`` live
    vertex index edge validity is judged against instead of this state's
    own vertex table — the partitioned-shard case, where an edge's
    endpoints generally live on *other* shards
    (:func:`repro.core.sharding.gather_live_vertices`)."""
    v_key = np.asarray(state.v_key)
    v_live = np.asarray(state.v_live)
    v_inc = np.asarray(state.v_inc)

    v_sel = np.flatnonzero(v_live)  # compaction order = table-slot order
    keys = v_key[v_sel]
    incs = v_inc[v_sel]
    vslots, v_over = _probe_place_host(_vhome_np(keys, new_vcap), new_vcap, MAX_PROBES)

    n_vkey = np.full(new_vcap, EMPTY_KEY, np.int32)
    n_vlive = np.zeros(new_vcap, bool)
    n_vinc = np.full(new_vcap, ABSENT_INC, np.int32)
    placed = vslots >= 0
    n_vkey[vslots[placed]] = keys[placed]
    n_vinc[vslots[placed]] = incs[placed]
    n_vlive[vslots[placed]] = True

    # edge validity: live lane AND both endpoints live at the bound
    # incarnation (the Fig. 3 hazard mask, numpy edition: binary search over
    # the sorted live-key column replaces the device's bounded-probe locate)
    e_ku = np.asarray(state.e_key_u)
    e_kv = np.asarray(state.e_key_v)
    e_live = np.asarray(state.e_live)
    e_bu = np.asarray(state.e_inc_u)
    e_bv = np.asarray(state.e_inc_v)

    if endpoints is None:
        order = np.argsort(keys, kind="stable")
        sk, si = keys[order], incs[order]
    else:
        sk, si = endpoints

    def inc_now(qs: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
        if sk.size == 0:
            return np.zeros(qs.shape, bool), np.zeros(qs.shape, np.int32)
        pos = np.searchsorted(sk, qs)
        pos_c = np.minimum(pos, sk.size - 1)
        found = (pos < sk.size) & (sk[pos_c] == qs)
        return found, si[pos_c]

    e_sel = np.flatnonzero(e_live)
    fu, iu = inc_now(e_ku[e_sel])
    fv, iv = inc_now(e_kv[e_sel])
    valid = fu & fv & (iu == e_bu[e_sel]) & (iv == e_bv[e_sel])
    e_sel = e_sel[valid]  # stale edges: physical deletion

    eslots, e_over = _probe_place_host(
        _ehome_np(e_ku[e_sel], e_kv[e_sel], new_ecap), new_ecap, MAX_PROBES
    )
    n_eku = np.full(new_ecap, EMPTY_KEY, np.int32)
    n_ekv = np.full(new_ecap, EMPTY_KEY, np.int32)
    n_elive = np.zeros(new_ecap, bool)
    n_ebu = np.full(new_ecap, ABSENT_INC, np.int32)
    n_ebv = np.full(new_ecap, ABSENT_INC, np.int32)
    eplaced = eslots >= 0
    n_eku[eslots[eplaced]] = e_ku[e_sel][eplaced]
    n_ekv[eslots[eplaced]] = e_kv[e_sel][eplaced]
    n_ebu[eslots[eplaced]] = e_bu[e_sel][eplaced]
    n_ebv[eslots[eplaced]] = e_bv[e_sel][eplaced]
    n_elive[eslots[eplaced]] = True

    new_state = GraphState(
        v_key=jnp.asarray(n_vkey),
        v_live=jnp.asarray(n_vlive),
        v_inc=jnp.asarray(n_vinc),
        e_key_u=jnp.asarray(n_eku),
        e_key_v=jnp.asarray(n_ekv),
        e_live=jnp.asarray(n_elive),
        e_inc_u=jnp.asarray(n_ebu),
        e_inc_v=jnp.asarray(n_ebv),
    )
    return new_state, not (v_over or e_over)


# ---------------------------------------------------------------------------
# device live-compact (+ snapshot-compact)
# ---------------------------------------------------------------------------


def _edge_validity_sorted(
    state: GraphState, sorted_key: jnp.ndarray, sorted_inc: jnp.ndarray
) -> jnp.ndarray:
    """Edge validity against an external sorted (key, inc) endpoint index —
    the device twin of ``rehash_host``'s ``inc_now`` closure under an
    ``endpoints`` override (partitioned shards: endpoints live elsewhere).
    Padding lanes carry INT32_MAX keys / ABSENT_INC incs and can never
    validate a real edge."""
    n = sorted_key.shape[0]
    if n == 0:
        return jnp.zeros(state.e_capacity, bool)

    def look(q):
        pos = jnp.searchsorted(sorted_key, q)
        pc = jnp.minimum(pos, n - 1)
        found = (pos < n) & (sorted_key[pc] == q)
        return found, sorted_inc[pc]

    fu, iu = look(state.e_key_u)
    fv, iv = look(state.e_key_v)
    return (
        state.e_live
        & fu
        & fv
        & (iu == state.e_inc_u)
        & (iv == state.e_inc_v)
    )


@functools.partial(
    jax.jit, static_argnames=("new_vcap", "new_ecap", "prim", "with_csr")
)
def _rehash_device(
    state: GraphState,
    new_vcap: int,
    new_ecap: int,
    prim: str,
    with_csr: bool,
    endpoints=None,
):
    cv_old = state.v_capacity
    ce_old = state.e_capacity
    i32 = jnp.int32

    # --- vertices: compact live lanes in slot order, place into new table
    vvals = jnp.stack(
        [state.v_key, state.v_inc, jnp.arange(cv_old, dtype=i32)]
    )
    vcomp, n_v = masked_compact(vvals, state.v_live, fill=-1, impl=prim)
    keys_c, inc_c, oldslot_c = vcomp
    v_active = jnp.arange(cv_old, dtype=i32) < n_v
    vhome = jnp.where(v_active, hash_vertex(keys_c, new_vcap), 0)
    vslots, v_over = probe_place(
        vhome, v_active, capacity=new_vcap, max_probes=MAX_PROBES, impl=prim
    )
    wv = jnp.where(v_active & (vslots >= 0), vslots, new_vcap)
    n_vkey = jnp.full(new_vcap, EMPTY_KEY, i32).at[wv].set(keys_c, mode="drop")
    n_vinc = jnp.full(new_vcap, ABSENT_INC, i32).at[wv].set(inc_c, mode="drop")
    n_vlive = jnp.zeros(new_vcap, bool).at[wv].set(True, mode="drop")

    # old slot -> new slot (consumed by the snapshot-compact below)
    old2new = jnp.full(cv_old + 1, new_vcap, i32)
    old2new = old2new.at[jnp.where(v_active, oldslot_c, cv_old + 1)].set(
        vslots, mode="drop"
    )

    # --- edges: mask stale bindings, compact, place
    if endpoints is None:
        su_old, sv_old, valid = _edge_validity(state)
    else:
        # partitioned shard: endpoints judged against the global sorted
        # index (old endpoint slots are meaningless here — snapshot-compact
        # requires local endpoints, enforced by rehash())
        valid = _edge_validity_sorted(state, *endpoints)
        su_old = sv_old = jnp.zeros(ce_old, i32)
    evals = jnp.stack(
        [
            state.e_key_u,
            state.e_key_v,
            state.e_inc_u,
            state.e_inc_v,
            su_old.astype(i32),
            sv_old.astype(i32),
        ]
    )
    ecomp, n_e = masked_compact(evals, valid, fill=-1, impl=prim)
    eu_c, ev_c, ebu_c, ebv_c, esu_c, esv_c = ecomp
    e_active = jnp.arange(ce_old, dtype=i32) < n_e
    ehome = jnp.where(e_active, hash_edge(eu_c, ev_c, new_ecap), 0)
    eslots, e_over = probe_place(
        ehome, e_active, capacity=new_ecap, max_probes=MAX_PROBES, impl=prim
    )
    we = jnp.where(e_active & (eslots >= 0), eslots, new_ecap)
    n_eku = jnp.full(new_ecap, EMPTY_KEY, i32).at[we].set(eu_c, mode="drop")
    n_ekv = jnp.full(new_ecap, EMPTY_KEY, i32).at[we].set(ev_c, mode="drop")
    n_ebu = jnp.full(new_ecap, ABSENT_INC, i32).at[we].set(ebu_c, mode="drop")
    n_ebv = jnp.full(new_ecap, ABSENT_INC, i32).at[we].set(ebv_c, mode="drop")
    n_elive = jnp.zeros(new_ecap, bool).at[we].set(True, mode="drop")

    new_state = GraphState(
        v_key=n_vkey,
        v_live=n_vlive,
        v_inc=n_vinc,
        e_key_u=n_eku,
        e_key_v=n_ekv,
        e_live=n_elive,
        e_inc_u=n_ebu,
        e_inc_v=n_ebv,
    )
    ok = ~(v_over | e_over)
    if not with_csr:
        return new_state, None, ok

    # --- snapshot-compact: the CSR of the new state without re-probing.
    # Every compacted edge knows its endpoints' old slots; old2new turns
    # them into new slots, so only build_csr's argsort remains.
    safe_su = jnp.where(e_active, esu_c, cv_old)
    safe_sv = jnp.where(e_active, esv_c, cv_old)
    src_lane = jnp.full(new_ecap, new_vcap, i32).at[we].set(
        old2new[safe_su], mode="drop"
    )
    dst_lane = jnp.full(new_ecap, new_vcap, i32).at[we].set(
        old2new[safe_sv], mode="drop"
    )
    csr = csr_from_lanes(new_state, src_lane, dst_lane, n_v, n_e)
    return new_state, csr, ok


def rehash(
    state: GraphState,
    new_vcap: int,
    new_ecap: int,
    *,
    impl: Optional[str] = None,
    with_csr: bool = False,
    endpoints: Optional[Tuple[np.ndarray, np.ndarray]] = None,
) -> Tuple[GraphState, Optional[TraversalCSR], bool]:
    """Grow + compact into fresh ``(new_vcap, new_ecap)`` tables.

    Returns ``(new_state, csr, ok)``.  ``csr`` is the ready-made
    :class:`TraversalCSR` of the new state when ``with_csr`` (bit-identical
    to ``build_csr(new_state)``), else ``None``.  ``ok=False`` means a
    probe chain would have exceeded ``MAX_PROBES`` — the new state must be
    discarded and the caller should grow further, exactly like a failed
    engine pass.  All impls are bit-identical; see the module docstring.

    ``endpoints`` — sorted global ``(keys, incs)`` numpy arrays — replaces
    the state's own vertex table as the edge-validity reference: the
    partitioned-shard case, where an edge's endpoints generally live on
    other shards.  Incompatible with ``with_csr`` (the snapshot-compact's
    slot remap is local by construction; the fused snapshot is rebuilt via
    :func:`repro.core.sharding.fuse_partitioned` instead).
    """
    impl = resolve_impl(impl)
    assert endpoints is None or not with_csr, (
        "snapshot-compact requires local endpoints"
    )
    with obsm.span(f"maintenance.rehash.{impl}"):
        obsm.counter("maintenance.rehash")
        if impl == "host":
            new_state, ok = rehash_host(state, new_vcap, new_ecap, endpoints)
            csr = build_csr(new_state) if (with_csr and ok) else None
            return new_state, csr, ok
        prim = _primitive_impl(impl)
        ep = None
        if endpoints is not None:
            # pow2-pad the sorted index so the jitted rehash compiles once per
            # bucket (INT32_MAX keys sort to the tail and never match)
            sk, si = endpoints
            m = sk.shape[0]
            bucket = max(16, 1 << max(m - 1, 1).bit_length())
            skp = np.full(bucket, np.iinfo(np.int32).max, np.int32)
            sip = np.full(bucket, ABSENT_INC, np.int32)
            skp[:m] = sk
            sip[:m] = si
            ep = (jnp.asarray(skp), jnp.asarray(sip))
        new_state, csr, ok = _rehash_device(
            state, new_vcap, new_ecap, prim, with_csr, ep
        )
        return new_state, csr, bool(ok)


# ---------------------------------------------------------------------------
# device delta-merge (the device splice of apply_delta)
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("nv", "ne"))
def _delta_merge_device(
    csr: TraversalCSR,
    state: GraphState,
    pack: jnp.ndarray,
    nv: int,
    ne: int,
):
    cv = csr.v_capacity
    ce = csr.e_capacity
    p = _delta_probe_parts(state, pack[:nv], pack[nv:nv + ne], pack[nv + ne:])

    # vertices whose (live, inc) changed invalidate every lane bound to them
    v_safe = jnp.where(p.v_found, p.v_slot, 0)
    changed = p.v_found & (
        (csr.v_live[v_safe] != p.v_live_now) | (csr.v_inc[v_safe] != p.v_inc_now)
    )
    hit = jnp.zeros(cv + 1, bool)
    hit = hit.at[jnp.where(changed, p.v_slot, cv + 1)].set(True, mode="drop")

    # every touched edge key is re-derived from the post state: drop its old
    # entry (if any) so the delta below is the single source
    ltouch = jnp.zeros(ce, bool)
    ltouch = ltouch.at[jnp.where(p.e_found, p.e_lane, ce)].set(True, mode="drop")

    in_prefix = jnp.arange(ce, dtype=jnp.int32) < csr.n_edges
    keep = in_prefix & ~(hit[csr.src] | hit[csr.dst]) & ~ltouch[csr.lane]
    ins = p.e_found & p.e_valid

    # post-batch endpoint slots of every edge-table lane (Cv = invalid): the
    # surviving entries scatter back to their lanes, the delta on top
    kl = jnp.where(keep, csr.lane, ce)
    dl = jnp.where(ins, p.e_lane, ce)
    src_lane = jnp.full(ce, cv, jnp.int32).at[kl].set(csr.src, mode="drop")
    dst_lane = jnp.full(ce, cv, jnp.int32).at[kl].set(csr.dst, mode="drop")
    src_lane = src_lane.at[dl].set(p.e_su, mode="drop")
    dst_lane = dst_lane.at[dl].set(p.e_sv, mode="drop")
    n_edges = (jnp.sum(keep) + jnp.sum(ins)).astype(jnp.int32)
    return csr_from_lanes(state, src_lane, dst_lane, p.n_live, n_edges)


def delta_merge(
    csr: TraversalCSR,
    state: GraphState,
    pack: np.ndarray,
    nv: int,
    ne: int,
) -> TraversalCSR:
    """Fold the (deduplicated, bucket-padded, packed ``vkeys | e_us | e_vs``)
    touched keys into ``csr`` entirely on device — the device splice of
    :func:`repro.core.traversal.apply_delta`, one host-to-device transfer and
    zero device-to-host ones.  Callers are responsible for the fallback
    guards (capacity change, delta footprint); bit-identity to
    ``build_csr(state)`` holds by construction: the surviving entries and the
    delta are scattered back to edge-table lane order and sorted by the
    rebuild's own two-key order (source slot, then lane — a stable sort of
    lane order), so no composite key has to fit in int32."""
    return _delta_merge_device(csr, state, pack, nv, ne)
