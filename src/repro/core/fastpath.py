"""Fast-path-slow-path (paper §3.4, after Kogan–Petrank / Timnat et al.).

The paper's fast path runs the Harris lock-free op and falls back to the
helped (wait-free) path after MAX_FAIL CAS failures; the observation is that
contention is rare, so the slow machinery is almost never paid.

Dataflow analogue: the cost the wait-free engine pays per batch is the
(key, phase) sorts and scans.  An op needs none of that if nothing else in
the batch can interfere with it:

  * vertex op on key u — no other op in the batch touches u (as a vertex op
    or as an edge endpoint);
  * edge op on (u, v) — (u, v) is unique among edge ops AND neither endpoint
    has any vertex op in the batch (Fig. 3: a concurrent vertex op is exactly
    what moves an edge op's linearization point).

Such ops are resolved directly from the table (one gather + one scatter,
sort-free): the fast path.  The conflicted remainder — typically a tiny
fraction, mirroring the paper's "very less number of failures" — is resolved
by the full wait-free engine with the fast ops masked to NOPs.  Both paths
are bounded, so the hybrid is still wait-free, and `lax.cond` skips the slow
pass entirely when a batch is conflict-free.

Under hash-prefix sharding (:mod:`repro.core.sharding`) each shard's
sub-batch holds only its owned ops, and endpoint liveness arrives from the
cross-shard stabbing wave instead of the local table; the partitioned FPSP
entry point is :func:`settle_edges_fpsp`, whose conflict mask reduces to
duplicate ``(u, v)`` detection because the stab answers already fold in
every concurrent vertex op.  The fast path's vertex half, its endpoint
reads and its edge half carry the engine's wave names
(``engine.vertex_wave``, ``engine.stab_wave``, ``engine.edge_wave``) on the
device.  Paper-to-code map: ``docs/ARCHITECTURE.md``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import engine, types
from .locate import claim_edge_slots, claim_vertex_slots, locate_edges, locate_vertices
from .types import (
    ABSENT_INC,
    OP_ADD_EDGE,
    OP_ADD_VERTEX,
    OP_CONTAINS_EDGE,
    OP_CONTAINS_VERTEX,
    OP_NOP,
    OP_REMOVE_EDGE,
    OP_REMOVE_VERTEX,
    ApplyResult,
    GraphState,
    OpBatch,
)

_INT32_MAX = jnp.iinfo(jnp.int32).max


def _dup_mask(keys: jnp.ndarray, active: jnp.ndarray) -> jnp.ndarray:
    """Exact: True where ``keys[i]`` appears more than once among active
    lanes.  One stable sort + neighbour compare; inactive lanes carry the
    INT32_MAX sentinel and are masked out."""
    k = jnp.where(active, keys, _INT32_MAX)
    order = jnp.argsort(k)
    ks, act_s = k[order], active[order]
    eq = ks[1:] == ks[:-1]
    false1 = jnp.zeros((1,), bool)
    dup_s = (jnp.concatenate([false1, eq]) | jnp.concatenate([eq, false1])) & act_s
    return jnp.zeros_like(dup_s).at[order].set(dup_s)


def _edge_dup_mask(u: jnp.ndarray, v: jnp.ndarray, active: jnp.ndarray) -> jnp.ndarray:
    """Exact duplicate-(u,v) detection via a lexicographic (two-pass stable)
    sort + neighbour compare."""
    uu = jnp.where(active, u, _INT32_MAX)
    vv = jnp.where(active, v, _INT32_MAX)
    p1 = jnp.argsort(vv)
    perm = p1[jnp.argsort(uu[p1])]
    us, vs = uu[perm], vv[perm]
    eq = (us[1:] == us[:-1]) & (vs[1:] == vs[:-1])
    false1 = jnp.zeros((1,), bool)
    dup_s = (jnp.concatenate([false1, eq]) | jnp.concatenate([eq, false1])) & active[perm]
    return jnp.zeros_like(dup_s).at[perm].set(dup_s)


def _membership_count(query: jnp.ndarray, ref: jnp.ndarray, ref_active: jnp.ndarray):
    """Exact count of each ``query`` key among active ``ref`` keys
    (searchsorted over the sorted reference; sentinels sort to the top and
    never match real keys)."""
    r = jnp.sort(jnp.where(ref_active, ref, _INT32_MAX))
    lo = jnp.searchsorted(r, query, side="left")
    hi = jnp.searchsorted(r, query, side="right")
    return (hi - lo).astype(jnp.int32)


def _conflict_mask(batch: OpBatch):
    """True where an op may interact with another op in the same batch.

    Exact (sort/searchsorted based, no hashing): a false positive here only
    costs throughput, but an earlier count-min-hash version demoted ~25% of a
    conflict-free batch to the slow path from birthday collisions alone —
    the paper's whole FPSP premise is that the slow path is rare, so the
    detector must not manufacture conflicts."""
    op, u, v = batch.op, batch.u, batch.v

    is_vop = (op == OP_ADD_VERTEX) | (op == OP_REMOVE_VERTEX) | (op == OP_CONTAINS_VERTEX)
    is_eop = (op == OP_ADD_EDGE) | (op == OP_REMOVE_EDGE) | (op == OP_CONTAINS_EDGE)

    # vertex op conflicts: another vertex op on u, or any edge op touching u
    e_endpoints = jnp.concatenate([u, v])
    e_ep_active = jnp.concatenate([is_eop, is_eop])
    v_conf = is_vop & (
        _dup_mask(u, is_vop)
        | (_membership_count(u, e_endpoints, e_ep_active) > 0)
    )
    # edge op conflicts: duplicate (u,v), or any vertex op on either endpoint
    # (paper Fig. 3: a concurrent vertex op moves the edge op's linearization
    # point, so those must go through the phase-ordered slow path)
    edge_dup = is_eop & _edge_dup_mask(u, v, is_eop)
    e_conf = edge_dup | (
        is_eop
        & ((_membership_count(u, u, is_vop) > 0) | (_membership_count(v, u, is_vop) > 0))
    )
    # the per-reason masks (v_conf / e_conf / edge_dup) feed the stats
    # vector: the obs layer splits the slow-path trigger count by cause
    return (v_conf | e_conf) & (is_vop | is_eop), is_vop, is_eop, v_conf, e_conf, edge_dup


@jax.named_scope("engine.vertex_wave")
def _fast_vertices(state: GraphState, batch: OpBatch, fv: jnp.ndarray):
    """The vertex half of the fast path: conflict-free vertex ops resolved
    straight from the table.  Returns ``(state', success, overflow,
    n_inserted, claim_rounds)``."""
    op, u = batch.op, batch.u
    vloc = locate_vertices(state.v_key, jnp.where(fv, u, _INT32_MAX), fv)
    vsafe = jnp.where(vloc.found, vloc.slot, 0)
    vlive = jnp.where(vloc.found, state.v_live[vsafe], False)
    vinc = jnp.where(vloc.found, state.v_inc[vsafe], ABSENT_INC)

    addv = fv & (op == OP_ADD_VERTEX)
    remv = fv & (op == OP_REMOVE_VERTEX)
    conv = fv & (op == OP_CONTAINS_VERTEX)
    v_success = (addv & ~vlive) | ((remv | conv) & vlive)

    cap = state.v_key.shape[0]
    # revive/insert on successful add; mark dead on successful remove
    wr = (addv | remv) & v_success & vloc.found
    wslot = jnp.where(wr, vloc.slot, cap)
    v_live_new = state.v_live.at[wslot].set(addv & v_success, mode="drop")
    v_inc_new = state.v_inc.at[wslot].set(
        jnp.where(addv, vinc + 1, vinc), mode="drop"
    )
    # brand-new keys (not found): insert via scatter-claim (keys unique by
    # construction of the fast set)
    need_ins = addv & v_success & ~vloc.found
    v_key_new, new_slots, v_over, v_rounds = claim_vertex_slots(
        state.v_key, jnp.where(need_ins, u, _INT32_MAX), need_ins
    )
    islot = jnp.where(need_ins & (new_slots >= 0), new_slots, cap)
    v_live_new = v_live_new.at[islot].set(True, mode="drop")
    v_inc_new = v_inc_new.at[islot].set(0, mode="drop")

    state = state._replace(v_key=v_key_new, v_live=v_live_new, v_inc=v_inc_new)
    n_ins = jnp.sum(need_ins & (new_slots >= 0)).astype(jnp.int32)
    return state, v_success, vloc.overflow | v_over, n_ins, v_rounds


@jax.named_scope("engine.stab_wave")
def _table_endpoints(state: GraphState, batch: OpBatch, fe: jnp.ndarray):
    """Endpoint (live, inc) of the fast edge lanes, read from the table:
    the table is authoritative for them (no concurrent vertex ops on their
    endpoints — that is the fast-path precondition).  Returns ``((u_live,
    u_inc, v_live, v_inc), overflow)``."""
    u, v = batch.u, batch.v
    uloc = locate_vertices(state.v_key, jnp.where(fe, u, _INT32_MAX), fe)
    vloc = locate_vertices(state.v_key, jnp.where(fe, v, _INT32_MAX), fe)
    usafe = jnp.where(uloc.found, uloc.slot, 0)
    vsafe = jnp.where(vloc.found, vloc.slot, 0)
    u_live = jnp.where(uloc.found, state.v_live[usafe], False)
    v_live = jnp.where(vloc.found, state.v_live[vsafe], False)
    u_inc = jnp.where(uloc.found, state.v_inc[usafe], ABSENT_INC)
    v_inc = jnp.where(vloc.found, state.v_inc[vsafe], ABSENT_INC)
    return (u_live, u_inc, v_live, v_inc), uloc.overflow | vloc.overflow


def _fast_apply(state: GraphState, batch: OpBatch, fast: jnp.ndarray):
    """Resolve conflict-free ops straight from the table: the vertex ops,
    then the edge ops against the post-vertex table."""
    op = batch.op
    is_vop = (op == OP_ADD_VERTEX) | (op == OP_REMOVE_VERTEX) | (op == OP_CONTAINS_VERTEX)
    is_eop = ~is_vop & (op != OP_NOP)
    fv = fast & is_vop
    fe = fast & is_eop

    state, v_success, v_over, v_ins, v_rounds = _fast_vertices(state, batch, fv)
    endpoint, s_over = _table_endpoints(state, batch, fe)
    state, e_success, e_over, e_ins, e_rounds = _fast_apply_edges(state, batch, fe, endpoint)

    success = jnp.where(fv, v_success, jnp.where(fe, e_success, False))
    return state, success, v_over | s_over | e_over, v_ins + e_ins, v_rounds + e_rounds


@jax.named_scope("engine.edge_wave")
def _fast_apply_edges(state: GraphState, batch: OpBatch, fe, endpoint):
    """The edge half of the fast path, fed endpoint (live, inc)-at-phase
    answers: the table's (:func:`_table_endpoints`) in :func:`_fast_apply`,
    the stabbing wave's in :func:`settle_edges_fpsp`.

    Under vertex partitioning (:mod:`repro.core.sharding`) a shard cannot
    read non-owned endpoints from its local table — the stabbing wave's
    answers replace that read, and they are exact *at each op's phase*, so
    the fast-path precondition shrinks to "``(u, v)`` unique among this
    shard's edge ops" (concurrent vertex ops no longer disqualify a lane:
    their effect is already folded into the answers)."""
    op, u, v = batch.op, batch.u, batch.v
    u_live, u_inc, v_live, v_inc = endpoint
    eligible = u_live & v_live & fe

    eloc = locate_edges(
        state.e_key_u, state.e_key_v,
        jnp.where(fe, u, _INT32_MAX), jnp.where(fe, v, _INT32_MAX), fe,
    )
    esafe = jnp.where(eloc.found, eloc.slot, 0)
    e_valid = (
        eloc.found
        & state.e_live[esafe]
        & (state.e_inc_u[esafe] == u_inc)
        & (state.e_inc_v[esafe] == v_inc)
        & eligible
    )

    adde = fe & (op == OP_ADD_EDGE)
    reme = fe & (op == OP_REMOVE_EDGE)
    cone = fe & (op == OP_CONTAINS_EDGE)
    e_success = (adde & eligible & ~e_valid) | ((reme | cone) & e_valid)

    ecap = state.e_key_u.shape[0]
    ewr = (adde | reme) & e_success & eloc.found
    ewslot = jnp.where(ewr, eloc.slot, ecap)
    e_live_new = state.e_live.at[ewslot].set(adde & e_success, mode="drop")
    e_bu_new = state.e_inc_u.at[ewslot].set(u_inc, mode="drop")
    e_bv_new = state.e_inc_v.at[ewslot].set(v_inc, mode="drop")

    e_need_ins = adde & e_success & ~eloc.found
    e_ku_new, e_kv_new, e_new_slots, e_over, e_rounds = claim_edge_slots(
        state.e_key_u, state.e_key_v,
        jnp.where(e_need_ins, u, _INT32_MAX), jnp.where(e_need_ins, v, _INT32_MAX),
        e_need_ins,
    )
    eislot = jnp.where(e_need_ins & (e_new_slots >= 0), e_new_slots, ecap)
    e_live_new = e_live_new.at[eislot].set(True, mode="drop")
    e_bu_new = e_bu_new.at[eislot].set(u_inc, mode="drop")
    e_bv_new = e_bv_new.at[eislot].set(v_inc, mode="drop")

    state = state._replace(
        e_key_u=e_ku_new, e_key_v=e_kv_new,
        e_live=e_live_new, e_inc_u=e_bu_new, e_inc_v=e_bv_new,
    )
    n_ins = jnp.sum(e_need_ins & (e_new_slots >= 0)).astype(jnp.int32)
    return state, e_success, eloc.overflow | e_over, n_ins, e_rounds


@jax.jit
def settle_edges_fpsp(
    state: GraphState,
    batch: OpBatch,
    u_live: jnp.ndarray,
    u_inc: jnp.ndarray,
    v_live: jnp.ndarray,
    v_inc: jnp.ndarray,
):
    """FPSP twin of :func:`repro.core.engine.settle_edges` for the
    partitioned pipeline: edge ops whose ``(u, v)`` is unique in this
    shard's sub-batch take the sort-free direct path (the stab answers
    stand in for the endpoint table reads), and only duplicate-key groups
    pay the phase-ordered epoch scan.  Returns ``(state', results,
    overflow, stats)`` with ``stats`` = ``i32[4]: [n_edge_dup, n_inserted,
    claim_rounds, n_eops]`` (same layout as
    :func:`repro.core.engine.settle_edges`, so the sharded pipeline unpacks
    both identically) — exactly the FPSP conflict semantics on the
    sub-batch."""
    op = batch.op
    is_eop = (op == OP_ADD_EDGE) | (op == OP_REMOVE_EDGE) | (op == OP_CONTAINS_EDGE)
    conflicted = is_eop & _edge_dup_mask(batch.u, batch.v, is_eop)
    fast = is_eop & ~conflicted
    endpoint = (u_live, u_inc, v_live, v_inc)

    state, fast_success, fast_over, fast_ins, fast_rounds = _fast_apply_edges(
        state, batch, fast, endpoint
    )

    n_conf = jnp.sum(conflicted).astype(jnp.int32)

    def slow(st):
        masked = batch._replace(op=jnp.where(conflicted, batch.op, OP_NOP))
        is_eop_m = conflicted
        return engine._edge_wave(st, masked, is_eop_m, endpoint)

    def skip(st):
        return (
            st,
            jnp.zeros((batch.size,), bool),
            jnp.array(False),
            jnp.int32(0),
            jnp.int32(0),
        )

    state, slow_success, slow_over, slow_ins, slow_rounds = jax.lax.cond(
        n_conf > 0, slow, skip, state
    )
    success = jnp.where(fast, fast_success, slow_success)
    stats = jnp.stack(
        [
            n_conf,
            fast_ins + slow_ins,
            fast_rounds + slow_rounds,
            jnp.sum(is_eop).astype(jnp.int32),
        ]
    )
    return state, success, fast_over | slow_over, stats


@jax.jit
def apply_batch_fpsp(state: GraphState, batch: OpBatch) -> ApplyResult:
    """Fast-path-slow-path: vectorized direct apply for conflict-free ops,
    full wait-free engine only for the conflicted remainder."""
    conflicted, is_vop, is_eop, v_conf, e_conf, edge_dup = _conflict_mask(batch)
    fast = (is_vop | is_eop) & ~conflicted

    state, fast_success, fast_over, fast_ins, fast_rounds = _fast_apply(
        state, batch, fast
    )

    # slow path: mask fast ops to NOP; cond skips it when nothing conflicts
    n_conf = jnp.sum(conflicted).astype(jnp.int32)

    def slow(state_and_batch):
        st, b = state_and_batch
        masked = b._replace(op=jnp.where(conflicted, b.op, OP_NOP))
        return engine.apply_batch(st, masked)

    def skip(state_and_batch):
        st, b = state_and_batch
        return ApplyResult(
            state=st,
            success=jnp.zeros((b.size,), bool),
            ok=jnp.array(True),
            stats=jnp.zeros((types.N_STATS,), jnp.int32),
        )

    res = jax.lax.cond(n_conf > 0, slow, skip, (state, batch))

    success = jnp.where(fast, fast_success, res.success)
    # stats (see types.STAT_*): the slow engine's inserted/rounds counters
    # accumulate with the fast lane's; the conflict split and the lane
    # totals are full-batch quantities, so they overwrite the masked-batch
    # values the slow pass saw
    stats = res.stats
    stats = stats.at[types.STAT_CONFLICTED].set(n_conf)
    stats = stats.at[types.STAT_V_CONFLICTS].set(jnp.sum(v_conf).astype(jnp.int32))
    stats = stats.at[types.STAT_E_CONFLICTS].set(jnp.sum(e_conf).astype(jnp.int32))
    stats = stats.at[types.STAT_INSERTED].add(fast_ins)
    stats = stats.at[types.STAT_EDGE_DUP].set(jnp.sum(edge_dup).astype(jnp.int32))
    stats = stats.at[types.STAT_VOPS].set(jnp.sum(is_vop).astype(jnp.int32))
    stats = stats.at[types.STAT_EOPS].set(jnp.sum(is_eop).astype(jnp.int32))
    stats = stats.at[types.STAT_CLAIM_ROUNDS].add(fast_rounds)
    return ApplyResult(
        state=res.state, success=success, ok=res.ok & ~fast_over, stats=stats
    )
