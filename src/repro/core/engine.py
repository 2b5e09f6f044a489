"""The wait-free batch-combine engine — the paper's contribution, in dataflow.

``apply_batch(state, batch)`` resolves an entire ODA (a batch of published
operation descriptors) in one bounded-depth pass, producing exactly the
results of applying the ops sequentially in phase order (validated op-by-op
against ``repro.core.oracle``).  Structure:

  A. **Vertex wave** — locate every vertex key; sort vertex ops by
     (key, phase); the liveness evolution of one key under its ops is a
     2-state DFA whose transitions (const/id function pairs) compose
     associatively, so one ``associative_scan`` resolves *all* keys' op
     groups simultaneously.  This is the helping mechanism: every lane
     computes the outcome of every conflicting op — in O(log n) depth
     regardless of contention (the wait-free bound).

  B. **Stabbing wave** — edge ops must observe endpoint liveness *at their
     own phase* (the paper's Fig. 3 subtlety: edge linearization points lie
     outside the edge method, determined by concurrent vertex ops).  A merged
     (key, phase)-sorted scan over vertex transitions + per-edge-op endpoint
     queries answers "was u live, and at which incarnation, at phase p?" for
     all 2n endpoint queries at once.

  C. **Edge wave** — edge ops sorted by (u, v, phase) split into *epochs*:
     maximal runs where both endpoints are continuously live at fixed
     incarnations (epochs are provably contiguous in phase order because
     incarnations only grow).  Within an epoch, edge validity is a 1-bit DFA
     — again const/id transitions, again one associative_scan.  Stored
     bindings only match an epoch seed when both stored incarnations equal
     the epoch's (physical stale-edge cleanup falls out for free).

  D. Scatter results back to original batch order; write back final table
     states; insert brand-new keys via deterministic scatter-claim.

Everything is int32/bool — results are asserted *exactly* equal to the
oracle, not allclose.

Each wave's device ops carry its name (``jax.named_scope``):
``engine.vertex_wave``, ``engine.stab_wave`` and ``engine.edge_wave``, so a
profiler trace splits the pass's device time by wave.

Each op linearizes at its phase stamp: a batch's results are exactly those
of the phase-ordered sequential execution.  Where this engine sits in the
paper-to-code map — and how sharding runs it unchanged per shard — is
``docs/ARCHITECTURE.md``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .locate import claim_edge_slots, claim_vertex_slots, locate_edges, locate_vertices
from .scanutils import scan_fnpairs, scan_last_set, seg_cumsum_exclusive, shift_right
from .types import (
    ABSENT_INC,
    OP_ADD_EDGE,
    OP_ADD_VERTEX,
    OP_CONTAINS_EDGE,
    OP_CONTAINS_VERTEX,
    OP_REMOVE_EDGE,
    OP_REMOVE_VERTEX,
    ApplyResult,
    GraphState,
    OpBatch,
)

_INT32_MAX = jnp.iinfo(jnp.int32).max


def _sort_by(keys, *arrays):
    """Stable sort of arrays by key tuple (major first); returns perm + sorted.

    Multi-key lexsort avoids packing composite keys into int64 (JAX runs with
    x64 disabled by default, which would silently truncate the pack).
    """
    perm = jnp.lexsort(tuple(reversed(keys)))
    return perm, tuple(a[perm] for a in arrays)


# ---------------------------------------------------------------------------
# A. vertex wave
# ---------------------------------------------------------------------------

@jax.named_scope("engine.vertex_wave")
def _vertex_wave(state: GraphState, batch: OpBatch):
    op, u, phase = batch.op, batch.u, batch.phase
    n = op.shape[0]

    is_vop = (op == OP_ADD_VERTEX) | (op == OP_REMOVE_VERTEX) | (op == OP_CONTAINS_VERTEX)
    vkey = jnp.where(is_vop, u, _INT32_MAX)

    loc = locate_vertices(state.v_key, vkey, is_vop)
    init_live = jnp.where(loc.found, state.v_live[jnp.where(loc.found, loc.slot, 0)], False)
    init_inc = jnp.where(loc.found, state.v_inc[jnp.where(loc.found, loc.slot, 0)], ABSENT_INC)

    perm, (s_op, s_key, s_init_live, s_init_inc, s_slot, s_found, s_isv) = _sort_by(
        (vkey, phase), op, vkey, init_live, init_inc, loc.slot, loc.found, is_vop
    )
    head = jnp.concatenate([jnp.ones((1,), bool), s_key[1:] != s_key[:-1]])

    # 2-state DFA transition (f(dead), f(live)) per op:
    #   AddVertex  -> const live   (dead: insert/revive; live: fail, stays live)
    #   RemVertex  -> const dead
    #   Contains   -> identity
    is_add = s_op == OP_ADD_VERTEX
    is_rem = s_op == OP_REMOVE_VERTEX
    f0 = jnp.where(is_add, 1, 0).astype(jnp.int32)          # id/rem: 0, add: 1
    f1 = jnp.where(is_rem, 0, 1).astype(jnp.int32)          # id/add: 1, rem: 0
    # head elements become f ∘ const(init): a constant function — this makes
    # plain associative_scan segment-safe (constants absorb everything left).
    init01 = s_init_live.astype(jnp.int32)
    hf = jnp.where(init01 == 1, f1, f0)
    f0 = jnp.where(head, hf, f0)
    f1 = jnp.where(head, hf, f1)

    after0, _ = scan_fnpairs(f0, f1)           # after head-collapse, f0 == f1
    live_after = after0.astype(bool)
    live_before = jnp.where(head, s_init_live, shift_right(live_after, False))

    success = jnp.where(
        is_add,
        ~live_before,
        jnp.where(is_rem, live_before, live_before),  # contains: live_before
    ) & s_isv

    # incarnation: bumps on every successful Add (dead -> live transition)
    revive = (is_add & success).astype(jnp.int32)
    inc_before = s_init_inc + seg_cumsum_exclusive(revive, head)
    inc_after = inc_before + revive

    # group-final state at segment last positions
    last = jnp.concatenate([head[1:], jnp.ones((1,), bool)])

    # --- write-back -------------------------------------------------------
    v_live, v_inc, v_key_col = state.v_live, state.v_inc, state.v_key
    upd = last & s_isv & s_found
    wslot = jnp.where(upd, s_slot, v_key_col.shape[0])
    v_live = v_live.at[wslot].set(live_after, mode="drop")
    v_inc = v_inc.at[wslot].set(inc_after, mode="drop")

    # brand-new keys: insert if the key was ever successfully added (inc >= 0)
    # even when finally dead — the tombstone pins the incarnation so stale
    # edges bound during this batch can never be revived by a later AddVertex.
    need_insert = last & s_isv & ~s_found & (inc_after >= 0)
    v_key_col, new_slots, ins_overflow, rounds = claim_vertex_slots(
        v_key_col, s_key, need_insert
    )
    islot = jnp.where(need_insert & (new_slots >= 0), new_slots, v_key_col.shape[0])
    v_live = v_live.at[islot].set(live_after, mode="drop")
    v_inc = v_inc.at[islot].set(inc_after, mode="drop")

    state = state._replace(v_key=v_key_col, v_live=v_live, v_inc=v_inc)

    # results back to original order
    results = jnp.zeros((n,), bool).at[perm].set(success)

    # transition events for the stabbing wave, in original batch order
    ev_live = jnp.zeros((n,), bool).at[perm].set(live_after)
    ev_inc = jnp.zeros((n,), jnp.int32).at[perm].set(inc_after)

    overflow = loc.overflow | ins_overflow
    n_inserted = jnp.sum(need_insert & (new_slots >= 0)).astype(jnp.int32)
    return state, results, (ev_live, ev_inc), overflow, n_inserted, rounds


# ---------------------------------------------------------------------------
# B. stabbing wave: endpoint (live, inc) at each edge op's phase
# ---------------------------------------------------------------------------

def _stab_scan(state: GraphState, tkeys, tphases, t_set, ev_live, ev_inc, qkeys, qphases):
    """The core stabbing scan: merge vertex-transition events ``(tkeys,
    tphases)`` carrying post-op payloads ``(ev_live, ev_inc)`` with endpoint
    queries ``(qkeys, qphases)``, sort by (key, phase), and answer every
    query with its key's (live, inc) *at its phase* via one head-seeded
    last-set scan.  Inert lanes carry the INT32_MAX key sentinel.  Returns
    ``(q_live, q_inc, overflow)`` aligned with the query arrays.

    This is the paper's Fig. 3 stabbing discipline as a standalone pass: the
    monolithic :func:`apply_batch` feeds it the batch's own endpoint queries,
    and the partitioned pipeline (:mod:`repro.core.sharding`) feeds the owner
    shard's transitions with *remote* shards' endpoint queries — same scan,
    same semantics, so cross-shard answers match the replicated ones.
    """
    nt = tkeys.shape[0]
    nq = qkeys.shape[0]
    ekey = jnp.concatenate([tkeys, qkeys])
    ephase = jnp.concatenate([tphases, qphases])
    is_set = jnp.concatenate([t_set, jnp.zeros((nq,), bool)])

    # every event knows its key's initial table state (for segment heads)
    loc = locate_vertices(state.v_key, ekey, ekey != _INT32_MAX)
    init_live = jnp.where(loc.found, state.v_live[jnp.where(loc.found, loc.slot, 0)], False)
    init_inc = jnp.where(loc.found, state.v_inc[jnp.where(loc.found, loc.slot, 0)], ABSENT_INC)

    pay_live = jnp.concatenate([ev_live, jnp.zeros((nq,), bool)])
    pay_inc = jnp.concatenate([ev_inc, jnp.zeros((nq,), jnp.int32)])

    perm, (s_key, s_set, s_pl, s_pi, s_il, s_ii) = _sort_by(
        (ekey, ephase), ekey, is_set, pay_live, pay_inc, init_live, init_inc
    )
    head = jnp.concatenate([jnp.ones((1,), bool), s_key[1:] != s_key[:-1]])

    # head elements are always "set": a head transition keeps its own payload,
    # a head query seeds the segment with the table's initial state.
    val_live = jnp.where(head & ~s_set, s_il, s_pl)
    val_inc = jnp.where(head & ~s_set, s_ii, s_pi)
    val_set = head | s_set

    (scan_live, scan_inc), _ = scan_last_set((val_live, val_inc), val_set)

    # read back query results in original order
    out_live = jnp.zeros((nt + nq,), bool).at[perm].set(scan_live)
    out_inc = jnp.zeros((nt + nq,), jnp.int32).at[perm].set(scan_inc)
    return out_live[nt:], out_inc[nt:], loc.overflow


@jax.named_scope("engine.stab_wave")
def _stabbing_wave(state: GraphState, batch: OpBatch, is_eop, ev_live, ev_inc, is_vop):
    op, u, v, phase = batch.op, batch.u, batch.v, batch.phase
    n = op.shape[0]

    # Event list (3n): vertex transitions + u-queries + v-queries of edge ops
    # (the concat order is load-bearing: the stable lexsort's tie-breaks — and
    # therefore the 1-shard bit-identity — depend on it).
    tkey = jnp.where(is_vop, u, _INT32_MAX)
    qukey = jnp.where(is_eop, u, _INT32_MAX)
    qvkey = jnp.where(is_eop, v, _INT32_MAX)
    qkeys = jnp.concatenate([qukey, qvkey])
    qphases = jnp.concatenate([phase, phase])

    # note: the locate inside _stab_scan re-walks chains after the vertex
    # wave may have inserted keys — init state must reflect the *pre-batch*
    # table (head queries precede all in-batch transitions of their key), so
    # apply_batch passes the pre-wave table into this function.
    q_live, q_inc, overflow = _stab_scan(
        state, tkey, phase, is_vop, ev_live, ev_inc, qkeys, qphases
    )
    u_live, u_inc = q_live[:n], q_inc[:n]
    v_live, v_inc = q_live[n:], q_inc[n:]
    return (u_live, u_inc, v_live, v_inc), overflow


# ---------------------------------------------------------------------------
# C. edge wave
# ---------------------------------------------------------------------------

@jax.named_scope("engine.edge_wave")
def _edge_wave(state: GraphState, batch: OpBatch, is_eop, endpoint):
    op, u, v, phase = batch.op, batch.u, batch.v, batch.phase
    n = op.shape[0]
    u_live, u_inc, v_live, v_inc = endpoint

    eku = jnp.where(is_eop, u, _INT32_MAX)
    ekv = jnp.where(is_eop, v, _INT32_MAX)
    loc = locate_edges(state.e_key_u, state.e_key_v, eku, ekv, is_eop)
    safe = jnp.where(loc.found, loc.slot, 0)
    init_live = jnp.where(loc.found, state.e_live[safe], False)
    init_bu = jnp.where(loc.found, state.e_inc_u[safe], ABSENT_INC)
    init_bv = jnp.where(loc.found, state.e_inc_v[safe], ABSENT_INC)

    # sort by (u, v, phase)
    perm, (s_op, s_ku, s_kv, s_ul, s_ui, s_vl, s_vi, s_il, s_ibu, s_ibv,
           s_slot, s_found, s_ise) = _sort_by(
        (eku, ekv, phase), op, eku, ekv, u_live, u_inc, v_live, v_inc,
        init_live, init_bu, init_bv, loc.slot, loc.found, is_eop,
    )
    head = jnp.concatenate(
        [jnp.ones((1,), bool), (s_ku[1:] != s_ku[:-1]) | (s_kv[1:] != s_kv[:-1])]
    )

    eligible = s_ul & s_vl & s_ise
    # epoch id changes at group heads and whenever (eligibility, incs) changes
    prev_elig = shift_right(eligible, False)
    prev_ui = shift_right(s_ui, jnp.int32(-2))
    prev_vi = shift_right(s_vi, jnp.int32(-2))
    epoch_change = head | (eligible != prev_elig) | (
        eligible & ((s_ui != prev_ui) | (s_vi != prev_vi))
    )

    # epoch seed: stored binding is valid iff it matches this epoch exactly
    seed = s_il & (s_ibu == s_ui) & (s_ibv == s_vi) & eligible
    # only the group's first epoch can possibly match the stored binding
    # (incarnations grow), but evaluating at every epoch head is harmless.

    # 1-bit validity DFA: AddE -> const 1, RemE -> const 0, Contains/⊥ -> id
    is_adde = (s_op == OP_ADD_EDGE) & eligible
    is_reme = (s_op == OP_REMOVE_EDGE) & eligible
    f0 = jnp.where(is_adde, 1, 0).astype(jnp.int32)
    f1 = jnp.where(is_reme, 0, 1).astype(jnp.int32)
    seed01 = seed.astype(jnp.int32)
    hf = jnp.where(seed01 == 1, f1, f0)
    f0 = jnp.where(epoch_change, hf, f0)
    f1 = jnp.where(epoch_change, hf, f1)

    after0, _ = scan_fnpairs(f0, f1)
    valid_after = after0.astype(bool)
    valid_before = jnp.where(epoch_change, seed, shift_right(valid_after, False))

    is_cone = s_op == OP_CONTAINS_EDGE
    success = jnp.where(
        is_adde, ~valid_before,
        jnp.where(is_reme, valid_before, eligible & is_cone & valid_before),
    ) & s_ise

    # group-final state
    last = jnp.concatenate([head[1:], jnp.ones((1,), bool)])
    fin_valid = valid_after
    fin_bu = s_ui
    fin_bv = s_vi

    # --- write-back -------------------------------------------------------
    e_live, e_bu, e_bv = state.e_live, state.e_inc_u, state.e_inc_v
    e_ku_col, e_kv_col = state.e_key_u, state.e_key_v
    cap = e_ku_col.shape[0]

    upd = last & s_ise & s_found
    wslot = jnp.where(upd, s_slot, cap)
    e_live = e_live.at[wslot].set(fin_valid, mode="drop")
    e_bu = e_bu.at[wslot].set(fin_bu, mode="drop")
    e_bv = e_bv.at[wslot].set(fin_bv, mode="drop")

    need_insert = last & s_ise & ~s_found & fin_valid
    e_ku_col, e_kv_col, new_slots, ins_overflow, rounds = claim_edge_slots(
        e_ku_col, e_kv_col, s_ku, s_kv, need_insert
    )
    islot = jnp.where(need_insert & (new_slots >= 0), new_slots, cap)
    e_live = e_live.at[islot].set(fin_valid, mode="drop")
    e_bu = e_bu.at[islot].set(fin_bu, mode="drop")
    e_bv = e_bv.at[islot].set(fin_bv, mode="drop")

    state = state._replace(
        e_key_u=e_ku_col, e_key_v=e_kv_col, e_live=e_live, e_inc_u=e_bu, e_inc_v=e_bv
    )
    results = jnp.zeros((n,), bool).at[perm].set(success)
    overflow = loc.overflow | ins_overflow
    n_inserted = jnp.sum(need_insert & (new_slots >= 0)).astype(jnp.int32)
    return state, results, overflow, n_inserted, rounds


# ---------------------------------------------------------------------------
# full pass
# ---------------------------------------------------------------------------

@jax.jit
def apply_batch(state: GraphState, batch: OpBatch) -> ApplyResult:
    # NOTE: no buffer donation — the host wrapper keeps the pre-state alive
    # for transactional growth-and-retry (see WaitFreeGraph.apply).
    """Resolve a whole op batch in phase order; bounded depth (wait-free)."""
    op = batch.op
    is_vop = (op == OP_ADD_VERTEX) | (op == OP_REMOVE_VERTEX) | (op == OP_CONTAINS_VERTEX)
    is_eop = (op == OP_ADD_EDGE) | (op == OP_REMOVE_EDGE) | (op == OP_CONTAINS_EDGE)

    pre_state = state
    state, v_results, (ev_live, ev_inc), v_over, v_ins, v_rounds = _vertex_wave(
        state, batch
    )
    # stabbing wave must read *pre-batch* init states (head queries precede
    # all in-batch transitions of their key), so pass the pre-wave table.
    endpoint, s_over = _stabbing_wave(pre_state, batch, is_eop, ev_live, ev_inc, is_vop)
    state, e_results, e_over, e_ins, e_rounds = _edge_wave(state, batch, is_eop, endpoint)

    success = jnp.where(is_vop, v_results, jnp.where(is_eop, e_results, False))
    ok = ~(v_over | s_over | e_over)

    # stats the waves compute anyway (see types.STAT_*); the obs layer reads
    # them host-side — slots 0-2 (conflict split) are FPSP-only and stay 0
    zero = jnp.int32(0)
    stats = jnp.stack(
        [
            zero,
            zero,
            zero,
            (v_ins + e_ins).astype(jnp.int32),
            zero,
            jnp.sum(is_vop).astype(jnp.int32),
            jnp.sum(is_eop).astype(jnp.int32),
            (v_rounds + e_rounds).astype(jnp.int32),
        ]
    )
    return ApplyResult(state=state, success=success, ok=ok, stats=stats)


# ---------------------------------------------------------------------------
# phase entry points for the partitioned (cross-shard) pipeline
# ---------------------------------------------------------------------------
#
# The sharded graph (repro.core.sharding / WaitFreeGraph n_shards > 1) runs
# the same three waves as apply_batch, but split across shards with a
# host-gathered stab exchange in the middle:
#
#   settle_vertices  — per shard, over its *owned* vertex ops only;
#   answer_stabs     — per endpoint-owner shard, answering remote shards'
#                      (endpoint, phase) queries against its own transitions;
#   settle_edges     — per shard, over its owned edge ops, fed the gathered
#                      endpoint answers.
#
# Each is an independently jitted pass so per-shard sub-batches (different
# bucket sizes per shard) compile once per bucket, exactly like apply_batch.


@jax.jit
def settle_vertices(state: GraphState, batch: OpBatch):
    """Vertex wave as a standalone pass.  Returns ``(state', results,
    ev_live, ev_inc, overflow, stats)`` — the ev arrays are the per-lane
    post-op (live, inc) transition payloads the stabbing wave consumes;
    ``stats`` is ``i32[3]: [n_inserted, claim_rounds, n_vops]`` (the obs
    layer's per-shard vertex-wave counters)."""
    op = batch.op
    is_vop = (op == OP_ADD_VERTEX) | (op == OP_REMOVE_VERTEX) | (op == OP_CONTAINS_VERTEX)
    state, results, (ev_live, ev_inc), overflow, n_ins, rounds = _vertex_wave(
        state, batch
    )
    stats = jnp.stack([n_ins, rounds, jnp.sum(is_vop).astype(jnp.int32)])
    return state, results, ev_live, ev_inc, overflow, stats


@jax.jit
def answer_stabs(
    pre_state: GraphState,
    batch: OpBatch,
    ev_live: jnp.ndarray,
    ev_inc: jnp.ndarray,
    qkeys: jnp.ndarray,
    qphases: jnp.ndarray,
):
    """Answer endpoint (live, inc)-at-phase queries against this shard's
    vertex transitions.

    ``pre_state`` must be the shard's *pre-vertex-wave* table (head queries
    precede every in-batch transition of their key, so their seed is the
    pre-batch state); ``batch``/``ev_live``/``ev_inc`` are the shard's own
    sub-batch and the transition payloads :func:`settle_vertices` returned
    for it.  ``qkeys``/``qphases`` are the gathered queries (INT32_MAX lanes
    are inert padding).  Returns ``(live, inc, overflow)`` per query."""
    op, u = batch.op, batch.u
    is_vop = (op == OP_ADD_VERTEX) | (op == OP_REMOVE_VERTEX) | (op == OP_CONTAINS_VERTEX)
    tkey = jnp.where(is_vop, u, _INT32_MAX)
    return _stab_scan(
        pre_state, tkey, batch.phase, is_vop, ev_live, ev_inc, qkeys, qphases
    )


@jax.jit
def settle_edges(
    state: GraphState,
    batch: OpBatch,
    u_live: jnp.ndarray,
    u_inc: jnp.ndarray,
    v_live: jnp.ndarray,
    v_inc: jnp.ndarray,
):
    """Edge wave as a standalone pass, fed externally gathered endpoint
    answers.  Returns ``(state', results, overflow, stats)`` with ``stats``
    = ``i32[4]: [n_edge_dup, n_inserted, claim_rounds, n_eops]`` (dup is
    FPSP-only and stays 0 here — same layout as the FPSP twin so the
    sharded pipeline unpacks both identically)."""
    op = batch.op
    is_eop = (op == OP_ADD_EDGE) | (op == OP_REMOVE_EDGE) | (op == OP_CONTAINS_EDGE)
    state, results, overflow, n_ins, rounds = _edge_wave(
        state, batch, is_eop, (u_live, u_inc, v_live, v_inc)
    )
    stats = jnp.stack([jnp.int32(0), n_ins, rounds, jnp.sum(is_eop).astype(jnp.int32)])
    return state, results, overflow, stats
