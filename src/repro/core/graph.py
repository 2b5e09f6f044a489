"""Host-side wrapper: the *unbounded* wait-free graph.

``WaitFreeGraph`` owns the functional :class:`GraphState` plus the global
phase counter (the paper's ``maxPhase`` fetch-and-add — here a host-side
monotone counter; each batch gets ``counter + iota`` stamps, and the counter
advances by the batch size).  "Unbounded" is realised exactly as the paper's
``new VNode(...)``: amortized growth.  Every engine pass is *transactional* —
if any bounded probe chain or insert round tripped its cap (``ok == False``),
the post-state is discarded, the tables are grown (rehash = Harris physical
deletion: tombstones and stale edges are dropped), and the same batch is
re-applied against the grown pre-state.  Results are therefore exact
regardless of when growth happens.

Deterministic by construction: given the same op stream, every host/device
computes the identical table — this is what the serving engine relies on for
coordination-free multi-host page tables.

Telemetry (``obs=`` / ``REPRO_OBS``) hangs off every public entry point:
per-phase spans, fast-path/claim-round counters, growth events — all derived
from stats the jitted passes compute anyway, so enabling it never perturbs
results.  Metric catalog and overhead contract: ``docs/OBSERVABILITY.md``.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# obs.metrics imports nothing from repro.core, so this is cycle-free even
# though repro.core.__init__ imports this module (see repro.obs docstring)
from ..obs import metrics as obsm
from . import engine, fastpath, maintenance, sharding, traversal
from .types import (
    EDGE_OPS,
    EMPTY_KEY,
    GROW_LOAD_FACTOR,
    OP_ADD_EDGE,
    OP_ADD_VERTEX,
    OP_CONTAINS_EDGE,
    OP_CONTAINS_VERTEX,
    OP_REMOVE_EDGE,
    OP_REMOVE_VERTEX,
    STAT_CLAIM_ROUNDS,
    STAT_CONFLICTED,
    STAT_E_CONFLICTS,
    STAT_EDGE_DUP,
    STAT_EOPS,
    STAT_INSERTED,
    STAT_V_CONFLICTS,
    STAT_VOPS,
    GraphState,
    OpBatch,
    is_pow2,
    make_batch,
    make_state,
)

_INT32_MAX = np.iinfo(np.int32).max

_MAX_GROW_ATTEMPTS = 12

_MUTATING_OPS = (OP_ADD_VERTEX, OP_REMOVE_VERTEX, OP_ADD_EDGE, OP_REMOVE_EDGE)


def _bucket_size(n: int) -> int:
    """Power-of-two batch bucket (floor 64), shared by ``apply`` and its
    sharded twin: the sharded-vs-1-shard byte-identity contract requires
    identical padding and phase stamps in both paths, so there is exactly
    one definition of the bucket rule."""
    return max(64, 1 << max(n - 1, 1).bit_length())


def _as_int32(ops, us, vs) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """A batch's op codes and endpoints as int32 host arrays (``vs`` zeros
    when the batch has no second endpoint)."""
    us0 = np.asarray(us, np.int32)
    vs0 = np.zeros_like(us0) if vs is None else np.asarray(vs, np.int32)
    return np.asarray(ops, np.int32), us0, vs0


@jax.jit
def _live_counts(state: GraphState):
    v = jnp.sum(state.v_live)
    e = jnp.sum(state.e_live)
    v_used = jnp.sum(state.v_key != EMPTY_KEY)
    e_used = jnp.sum(state.e_key_u != EMPTY_KEY)
    return v, e, v_used, e_used


def _rehash_escalating(
    state: GraphState,
    new_vcap: int,
    new_ecap: int,
    impl: Optional[str] = None,
    with_csr: bool = False,
):
    """The grow-and-retry discipline shared by :func:`_rehash` and
    ``WaitFreeGraph._grow``: placement is bounded by the engines' own
    ``MAX_PROBES``, so should a chain overflow it (a key the engines could
    never locate again), the capacities double and the compaction retries.
    Returns ``(new_state, csr_or_None)``."""
    for attempt in range(_MAX_GROW_ATTEMPTS):
        new_state, csr, ok = maintenance.rehash(
            state, new_vcap, new_ecap, impl=impl, with_csr=with_csr
        )
        if ok:
            return new_state, csr
        # escalation: placement overflowed even at the doubled capacity —
        # rare enough to log as a structured event, not just a counter
        obsm.counter("growth.escalations")
        obsm.event(
            "growth.escalation",
            attempt=attempt,
            v_capacity=new_vcap,
            e_capacity=new_ecap,
        )
        new_vcap *= 2
        new_ecap *= 2
    raise RuntimeError("rehash placement did not converge")


def _rehash(
    state: GraphState, new_vcap: int, new_ecap: int, impl: Optional[str] = None
) -> GraphState:
    """Grow + compact: keep live vertices (with incarnations) and valid live
    edges only — the batched analogue of Harris physical deletion.

    Stable entry point over :func:`repro.core.maintenance.rehash` (which
    owns the host/device implementations), with capacity escalation on
    placement overflow."""
    return _rehash_escalating(state, new_vcap, new_ecap, impl)[0]


class WaitFreeGraph:
    """The unbounded concurrent graph: the paper's public API, batched.

    ``mode`` selects the engine:
      * ``"waitfree"`` — full phase-ordered helping pass (paper §3).
      * ``"fpsp"``     — fast-path-slow-path (paper §3.4): conflict-free ops
        take a sort-free vectorized path; only conflicted ops pay the scans.

    ``traversal_impl`` selects the frontier-expansion backend for every
    traversal query (``None`` = the XLA implementation on every backend;
    ``"kernel_interpret"`` runs the Pallas kernel through the interpreter
    — the TPU compiler refuses it, see :mod:`repro.kernels.frontier`).

    ``csr_maintenance`` picks what happens to a cached traversal snapshot
    when an update batch lands: ``"delta"`` folds the batch into it with
    :func:`repro.core.traversal.apply_delta` (bit-identical to a rebuild,
    no O(capacity) re-probe — the win for update-light query-heavy
    mixes), ``"rebuild"`` discards it and recompacts lazily on next query.

    ``maintenance_impl`` selects where table maintenance (growth rehash and
    the ``apply_delta`` splice) runs: ``"device"`` routes both through
    jitted device passes (:mod:`repro.core.maintenance` over the
    :mod:`repro.kernels.compact` primitives; a growth rehash also
    pre-compacts the traversal snapshot so the post-growth ``build_csr`` is
    one delta fold), ``"device_interpret"`` swaps in the compact Pallas
    kernels through the interpreter, ``"host"`` keeps the vectorized-numpy
    oracle.  ``None`` = auto: device on TPU, host elsewhere.  All impls
    produce bit-identical tables at every size.

    ``obs`` enables wait-free telemetry (:mod:`repro.obs`): ``None`` defers
    to the ``REPRO_OBS`` env var, ``True`` attaches a fresh
    :class:`repro.obs.Registry`, ``False`` forces the no-op registry
    (whose spans are profiler annotations only), and a registry instance
    is shared as-is.  Every metric is derived from
    arrays the jitted programs compute regardless, so the flag never
    changes graph state or query answers (bit-identity pinned by
    ``tests/test_obs.py``); catalog in ``docs/OBSERVABILITY.md``.

    ``n_shards`` hash-prefix-partitions *both* tables into that many
    per-shard states — each shard owns ``1/n_shards`` of the vertex key
    space and of the edge key space (O(N/S) memory per shard), with ops
    routed by the prefix of the hash the probe sequence already uses and a
    cross-shard stabbing wave answering endpoint liveness between the
    vertex and edge settlement phases (see :mod:`repro.core.sharding`) —
    round-robined over ``mesh`` (default: a host-local
    :func:`repro.core.sharding.host_local_mesh`).  ``n_shards=1`` (the
    default) bypasses the routing layer entirely; any shard count produces
    identical query answers (pinned by ``tests/test_sharding.py``), so the
    flag is a pure scaling knob.  The incremental ``csr_maintenance=
    "delta"`` fold applies to 1-shard graphs only; sharded snapshots are
    rebuilt via :func:`repro.core.sharding.fuse_partitioned` on demand.
    """

    def __init__(
        self,
        v_capacity: int = 1024,
        e_capacity: int = 4096,
        mode: str = "waitfree",
        traversal_impl: Optional[str] = None,
        csr_maintenance: str = "delta",
        maintenance_impl: Optional[str] = None,
        n_shards: int = 1,
        mesh=None,
        obs=None,
    ):
        assert mode in ("waitfree", "fpsp")
        assert csr_maintenance in ("delta", "rebuild")
        assert maintenance_impl in maintenance.MAINTENANCE_IMPLS
        assert is_pow2(n_shards), "n_shards must be a power of two"
        self._csr: Optional[traversal.TraversalCSR] = None  # cached snapshot
        self._grow_csr: Optional[traversal.TraversalCSR] = None
        self.n_shards = n_shards
        self._mesh = None
        if n_shards == 1:
            self.state = make_state(v_capacity, e_capacity)
        else:
            assert e_capacity % n_shards == 0 and is_pow2(e_capacity // n_shards), (
                "e_capacity must split into power-of-two per-shard capacities"
            )
            assert v_capacity % n_shards == 0 and is_pow2(v_capacity // n_shards), (
                "v_capacity must split into power-of-two per-shard capacities"
            )
            self._mesh = mesh if mesh is not None else sharding.host_local_mesh()
            self.shards = sharding.place_shards(
                sharding.make_shard_states(
                    v_capacity // n_shards, e_capacity // n_shards, n_shards
                ),
                self._mesh,
            )
        self.mode = mode
        self.traversal_impl = traversal_impl
        self.csr_maintenance = csr_maintenance
        self.maintenance_impl = maintenance_impl
        self.obs = obsm.resolve(obs)
        self._phase = 0  # the paper's maxPhase counter

    @property
    def state(self) -> GraphState:
        if self.n_shards > 1:
            raise AttributeError(
                "sharded graph: per-shard states live on .shards "
                "(both tables are hash-prefix partitions)"
            )
        return self._state

    @state.setter
    def state(self, value: GraphState) -> None:
        # any state swap (apply, growth, or a caller installing a rehashed
        # state directly) invalidates the cached traversal snapshot AND any
        # pending delta queue (its base snapshot no longer matches the state)
        self._state = value
        self._csr = None
        self._delta_base = None
        self._delta_batches = []

    @property
    def shards(self) -> List[GraphState]:
        return self._shards

    @shards.setter
    def shards(self, value) -> None:
        # same invalidation contract as the ``state`` setter (the fused
        # snapshot is rebuilt from scratch — the delta fold is 1-shard only)
        self._shards = list(value)
        self._csr = None
        self._delta_base = None
        self._delta_batches = []

    # -- batched API ------------------------------------------------------
    def apply(self, ops, us, vs=None) -> np.ndarray:
        """Apply a batch; returns bool[n] success per op (phase order = batch
        order).

        Batches are padded to power-of-two buckets with NOP lanes: the jitted
        engines specialize on batch size, and a serving workload publishes a
        different op count every step — unbucketed, that is a recompile per
        step (measured 1.09 s/step vs ~ms after bucketing)."""
        n = len(ops)
        if n == 0:
            # nothing to resolve: skip the padded engine dispatch entirely
            return np.zeros(0, bool)
        reg = self.obs
        with obsm.use(reg):
            reg.counter("apply.batches")
            reg.counter("apply.ops", n)
            reg.hist("apply.batch_size", n)
            if self.n_shards > 1:
                with reg.span("graph.apply_sharded"):
                    return self._apply_sharded(*_as_int32(ops, us, vs))
            with reg.span("graph.apply"):
                return self._apply_dense(ops, us, vs)

    def _apply_dense(self, ops, us, vs) -> np.ndarray:
        """The ``n_shards == 1`` engine dispatch behind :meth:`apply` (runs
        inside the obs ``use`` scope and the ``graph.apply`` span the
        wrapper opened).  Its child spans follow one another and cover the
        call: ``prepare``, then per attempt ``dispatch``, ``wait`` and
        ``growth_check``, then ``readback``, or ``grow`` and the next
        attempt."""
        reg = self.obs
        with reg.span("graph.apply.prepare"):
            ops0, us0, vs0 = _as_int32(ops, us, vs)
            n = ops0.shape[0]
            # read-only batches (contains/NOP only) leave the abstract graph
            # unchanged, so the cached traversal snapshot stays valid — keep
            # it across the state swap instead of forcing a CSR rebuild.
            mutating = bool(np.isin(ops0, _MUTATING_OPS).any())
            bucket = _bucket_size(n)
            ops, us, vs = ops0, us0, vs0
            if bucket != n:
                pad = np.zeros(bucket - n, np.int32)  # OP_NOP = 0
                ops = np.concatenate([ops0, pad])
                us = np.concatenate([us0, pad])
                vs = np.concatenate([vs0, pad])
            batch = make_batch(ops, us, vs, phase_base=self._phase)
            self._phase += batch.size
            apply_fn = (
                engine.apply_batch if self.mode == "waitfree" else fastpath.apply_batch_fpsp
            )
        self._grow_csr = None
        for attempt in range(_MAX_GROW_ATTEMPTS):
            # keep the pre-state alive for transactional retry
            pre = self.state
            with reg.span("graph.apply.dispatch"):
                res = apply_fn(pre, batch)
            with reg.span("graph.apply.wait"):
                ok = bool(res.ok)
            if ok:
                with reg.span("graph.apply.growth_check"):
                    ok = not self._needs_growth(res.state)
            if ok:
                with reg.span("graph.apply.readback"):
                    success = np.asarray(res.success)[:n]
                    self._install(res, attempt, mutating, (ops0, us0, vs0))
                return success
            # discard post-state; grow from pre-state; retry the same batch
            with reg.span("graph.apply.grow"):
                self.state = self._grow(pre)
        raise RuntimeError("graph growth did not converge")

    def _install(self, res, attempt: int, mutating: bool, batch0) -> None:
        """Install a successful attempt's post-state and carry the cached
        snapshot and the pending-delta queue across the swap.

        The queue (base snapshot + unpadded batches since the last query)
        survives the swap: read-only batches carry it unchanged, mutating
        batches append to it so the next query folds the whole queue in one
        apply_delta (lazy: an update-heavy stream between queries pays
        nothing per batch, one fold per query epoch)."""
        saved_csr = None if mutating else self._csr
        delta_base, delta_batches = self._delta_base, self._delta_batches
        if mutating and self.csr_maintenance == "delta" and self._csr is not None:
            delta_base, delta_batches = self._csr, []
        # the successful attempt alone feeds the obs counters — discarded
        # growth attempts re-run the same lanes and would double-count them
        if self.obs.enabled:
            self._record_engine_stats(self.obs, res.stats)
        grow_csr = self._grow_csr
        self.state = res.state
        if attempt > 0:
            # growth rehashed the tables: every slot moved, so both the
            # saved snapshot's and the queue's bases are void — the state
            # setter already dropped them.  The rehash pre-compacted the
            # grown state's snapshot, though (maintenance
            # "snapshot-compact"): queue this batch against it so the next
            # query pays one delta fold, not a full rebuild.
            if mutating and grow_csr is not None and self.csr_maintenance == "delta":
                self._delta_base = grow_csr
                self._delta_batches = [batch0]
            return
        if not mutating:
            # abstractly identical pre/post state: the saved snapshot (own
            # references to the old tables) and any pending queue stay
            # exactly as valid as before the batch
            self._csr = saved_csr
            self._delta_base = delta_base
            self._delta_batches = delta_batches
        elif delta_base is not None and self.csr_maintenance == "delta":
            # queue the batch against the remembered base snapshot;
            # traversal_csr() folds the queue on the next query.  A queue
            # past the fold's own fallback threshold would rebuild anyway —
            # drop it and stop accumulating.
            delta_batches = delta_batches + [batch0]
            if sum(b[0].size for b in delta_batches) > delta_base.e_capacity // 4:
                delta_base, delta_batches = None, []
            self._delta_base = delta_base
            self._delta_batches = delta_batches

    def _record_engine_stats(self, reg, stats) -> None:
        """Fold one successful engine pass's stats vector (types.STAT_*)
        into the registry — the single host-side device read obs adds, and
        only when a live registry is attached."""
        s = [int(x) for x in np.asarray(stats)]
        reg.counter("engine.inserted", s[STAT_INSERTED])
        reg.counter("engine.vops", s[STAT_VOPS])
        reg.counter("engine.eops", s[STAT_EOPS])
        reg.hist("engine.claim_rounds", s[STAT_CLAIM_ROUNDS])
        if self.mode == "fpsp":
            reg.counter("fastpath.ops", s[STAT_VOPS] + s[STAT_EOPS])
            reg.counter("fastpath.vops", s[STAT_VOPS])
            reg.counter("fastpath.eops", s[STAT_EOPS])
            reg.counter("fastpath.conflicted", s[STAT_CONFLICTED])
            reg.counter("fastpath.vertex_conflicts", s[STAT_V_CONFLICTS])
            reg.counter("fastpath.edge_conflicts", s[STAT_E_CONFLICTS])
            reg.counter("fastpath.edge_dup", s[STAT_EDGE_DUP])
            reg.counter(
                "fastpath.slow_batches" if s[STAT_CONFLICTED] else "fastpath.fast_batches"
            )

    def _record_sharded_stats(self, reg, v_stats, e_stats) -> None:
        """Per-shard twin of :meth:`_record_engine_stats`: fold the
        ``settle_vertices``/``settle_edges`` stats vectors of one successful
        sharded attempt.  The edge-lane fastpath counters sum to the same
        totals for any shard count (duplicate ``(u, v)`` lanes co-locate on
        one shard) — the shard-invariance ``tests/test_obs.py`` pins."""
        for v_st, e_st in zip(v_stats, e_stats):
            v_ins, v_rounds, n_vops = (int(x) for x in np.asarray(v_st))
            e_dup, e_ins, e_rounds, n_eops = (int(x) for x in np.asarray(e_st))
            reg.counter("engine.inserted", v_ins + e_ins)
            reg.counter("engine.vops", n_vops)
            reg.counter("engine.eops", n_eops)
            reg.hist("engine.claim_rounds", v_rounds + e_rounds)
            if self.mode == "fpsp":
                reg.counter("fastpath.eops", n_eops)
                reg.counter("fastpath.edge_dup", e_dup)
                reg.counter(
                    "fastpath.slow_batches" if e_dup else "fastpath.fast_batches"
                )

    def _needs_growth(self, state: GraphState) -> bool:
        v, e, v_used, e_used = _live_counts(state)
        return bool(v_used > GROW_LOAD_FACTOR * state.v_capacity) or bool(
            e_used > GROW_LOAD_FACTOR * state.e_capacity
        )

    def _grow(self, state: GraphState) -> GraphState:
        v, e, v_used, e_used = _live_counts(state)
        new_vcap = state.v_capacity
        new_ecap = state.e_capacity
        # grow whichever table is crowded (or both); compaction alone can be
        # enough when tombstones dominate, but doubling keeps it simple and
        # amortized-O(1).
        if int(v_used) > GROW_LOAD_FACTOR * state.v_capacity / 2:
            new_vcap *= 2
        if int(e_used) > GROW_LOAD_FACTOR * state.e_capacity / 2:
            new_ecap *= 2
        if new_vcap == state.v_capacity and new_ecap == state.e_capacity:
            new_vcap *= 2
            new_ecap *= 2
        impl = maintenance.resolve_impl(self.maintenance_impl)
        if self.obs.enabled:
            self.obs.counter("growth.events")
            self.obs.event(
                "growth.grow",
                v_before=state.v_capacity,
                v_after=new_vcap,
                e_before=state.e_capacity,
                e_after=new_ecap,
                v_live=int(v),
                e_live=int(e),
            )
        # snapshot-compact rides the device pass nearly free; on the host it
        # would be an eager build_csr per grow attempt — leave that lazy
        with_csr = impl != "host" and self.csr_maintenance == "delta"
        new_state, csr = _rehash_escalating(state, new_vcap, new_ecap, impl, with_csr)
        # stashed for apply(): becomes the delta base of the retried batch
        # (the state setter must not clear it — the grown state is installed
        # right after this returns)
        self._grow_csr = csr
        return new_state

    # -- hash-prefix sharded apply (see repro.core.sharding) ----------------

    @staticmethod
    def _sub_batch(ops0, us0, vs0, phases0, idx) -> OpBatch:
        """Compact one shard's owned lanes into a pow2-bucketed sub-batch.
        Lanes keep their *global* phase stamps (linearization = batch
        order, shard-count-independent); padding lanes are NOPs, inert in
        every wave (their keys sort to the INT32_MAX sentinel)."""
        m = idx.size
        bucket = _bucket_size(m)
        op = np.zeros(bucket, np.int32)
        u = np.zeros(bucket, np.int32)
        v = np.zeros(bucket, np.int32)
        ph = np.zeros(bucket, np.int32)
        op[:m] = ops0[idx]
        u[:m] = us0[idx]
        v[:m] = vs0[idx]
        ph[:m] = phases0[idx]
        return OpBatch(
            op=jnp.asarray(op), u=jnp.asarray(u), v=jnp.asarray(v),
            phase=jnp.asarray(ph),
        )

    def _apply_sharded(self, ops0, us0, vs0) -> np.ndarray:
        """The n_shards > 1 twin of ``apply``: the partitioned three-phase
        pipeline (route → vertex settle → stab → gather → edge claim).

        Each shard receives only its owned lanes (O(batch/S) sub-batches —
        no silhouette replication), so the phases are explicit:

          A. ``settle_vertices`` per shard — each shard's vertex wave over
             its owned vertex ops, returning per-lane transition payloads;
          B. ``answer_stabs`` per endpoint-owner shard — every edge lane's
             two (endpoint, phase) queries are routed to the endpoint's
             owner, answered against its transitions + pre-batch table,
             and gathered host-side (the all-to-all exchange);
          C. ``settle_edges`` (or its FPSP twin) per shard — the unchanged
             edge wave over owned edge ops, fed the gathered answers.

        Linearization is unchanged: lanes carry globally unique phase
        stamps, every vertex op on a key lives on one shard (so its
        transition sequence is complete there), and the stab answers are
        exactly what the monolithic engine's in-batch stabbing wave would
        have computed.  Growth is transactional per attempt, as in
        ``apply``: any overflow discards the post-states, grows from the
        pre-states, and re-runs the same batch at the same phases."""
        n = ops0.shape[0]
        S = self.n_shards
        reg = self.obs
        mutating = bool(np.isin(ops0, _MUTATING_OPS).any())
        saved_csr = None if mutating else self._csr
        with reg.span("phase.route"):
            shard_idx, _ = sharding.route_ops(ops0, us0, vs0, S)
            phases0 = (self._phase + np.arange(n)).astype(np.int32)
            self._phase += n
            batches = [
                self._sub_batch(ops0, us0, vs0, phases0, idx) for idx in shard_idx
            ]
        if reg.enabled:
            sizes = [int(idx.size) for idx in shard_idx]
            reg.hist("shard.subbatch_size", sizes)
            if sum(sizes):
                # max-over-mean routed load: 1.0 = perfectly balanced
                reg.gauge("shard.balance", max(sizes) * S / sum(sizes))

        # stab queries: two (endpoint, phase) probes per edge lane, routed
        # to the endpoint's owner shard (fixed across growth attempts —
        # growth preserves the abstract graph, so answers are identical)
        eidx = np.flatnonzero(np.isin(ops0, EDGE_OPS))
        ne = eidx.size
        q_keys = np.concatenate([us0[eidx], vs0[eidx]]).astype(np.int32)
        q_phases = np.concatenate([phases0[eidx], phases0[eidx]])
        q_owner = sharding.shard_of_vertices(q_keys, S)
        q_sel = [np.flatnonzero(q_owner == t) for t in range(S)]
        if reg.enabled:
            reg.counter("stab.queries", 2 * ne)
            reg.hist("shard.stab_fanout", [int(sel.size) for sel in q_sel])
        q_pads = [
            (
                traversal._pad_pow2(q_keys[sel], _INT32_MAX),
                traversal._pad_pow2(q_phases[sel], 0),
            )
            for sel in q_sel
        ]
        settle_edges_fn = (
            engine.settle_edges if self.mode == "waitfree"
            else fastpath.settle_edges_fpsp
        )

        for _attempt in range(_MAX_GROW_ATTEMPTS):
            pre = self._shards  # kept alive for transactional retry
            ok = True

            # A. vertex settlement per shard
            with reg.span("phase.settle_vertices"):
                states_a, v_res, evs, v_stats = [], [], [], []
                for s in range(S):
                    st, res, ev_l, ev_i, over, v_st = engine.settle_vertices(
                        pre[s], batches[s]
                    )
                    ok &= not bool(over)
                    states_a.append(st)
                    v_res.append(res)
                    evs.append((ev_l, ev_i))
                    v_stats.append(v_st)

            # B. stabbing wave: owner shards answer, host gathers
            with reg.span("phase.answer_stabs"):
                q_live = np.zeros(2 * ne, bool)
                q_inc = np.zeros(2 * ne, np.int32)
                for t in range(S):
                    sel = q_sel[t]
                    if sel.size == 0:
                        continue
                    qk, qp = q_pads[t]
                    live, inc, over = engine.answer_stabs(
                        pre[t], batches[t], evs[t][0], evs[t][1],
                        jnp.asarray(qk), jnp.asarray(qp),
                    )
                    ok &= not bool(over)
                    q_live[sel] = np.asarray(live)[: sel.size]
                    q_inc[sel] = np.asarray(inc)[: sel.size]
            with reg.span("phase.gather"):
                u_live = np.zeros(n, bool)
                u_inc = np.zeros(n, np.int32)
                v_live = np.zeros(n, bool)
                v_inc = np.zeros(n, np.int32)
                u_live[eidx] = q_live[:ne]
                u_inc[eidx] = q_inc[:ne]
                v_live[eidx] = q_live[ne:]
                v_inc[eidx] = q_inc[ne:]

            # C. edge settlement per shard, fed the gathered answers
            with reg.span("phase.settle_edges"):
                out = np.zeros(n, bool)
                states_c, e_stats = [], []
                for s in range(S):
                    idx = shard_idx[s]
                    m = idx.size
                    bucket = batches[s].size
                    ul = np.zeros(bucket, bool)
                    ui = np.zeros(bucket, np.int32)
                    vl = np.zeros(bucket, bool)
                    vi = np.zeros(bucket, np.int32)
                    ul[:m] = u_live[idx]
                    ui[:m] = u_inc[idx]
                    vl[:m] = v_live[idx]
                    vi[:m] = v_inc[idx]
                    st, e_res, over, e_st = settle_edges_fn(
                        states_a[s], batches[s],
                        jnp.asarray(ul), jnp.asarray(ui),
                        jnp.asarray(vl), jnp.asarray(vi),
                    )
                    ok &= not bool(over)
                    states_c.append(st)
                    e_stats.append(e_st)
                    if m:
                        out[idx] = (
                            np.asarray(v_res[s])[:m] | np.asarray(e_res)[:m]
                        )

            if ok and not self._needs_growth_sharded(states_c):
                self.shards = states_c
                # successful attempt only — retried attempts would
                # double-count lanes (see _apply_dense)
                if reg.enabled:
                    self._record_sharded_stats(reg, v_stats, e_stats)
                if not mutating:
                    # abstractly identical pre/post state: the cached fused
                    # snapshot stays exactly as valid as before the batch
                    self._csr = saved_csr
                return out
            with reg.span("phase.compact"):
                self.shards = self._grow_shards(pre)
        raise RuntimeError("graph growth did not converge")

    def _needs_growth_sharded(self, states: List[GraphState]) -> bool:
        counts = [_live_counts(st) for st in states]
        return any(
            bool(c[2] > GROW_LOAD_FACTOR * st.v_capacity)
            or bool(c[3] > GROW_LOAD_FACTOR * st.e_capacity)
            for c, st in zip(counts, states)
        )

    def _grow_shards(self, states: List[GraphState]) -> List[GraphState]:
        """Per-shard capacity policy: each shard doubles whichever of its
        tables is crowded (both key spaces are partitioned, so decisions
        are independent — no lockstep-replica constraint).  Edge validity
        during each rehash is judged against the *global* endpoint index
        (an edge's endpoints generally live on other shards); the
        escalation loop re-doubles only the shards whose placement
        overflowed."""
        counts = [_live_counts(st) for st in states]
        new_vcaps, new_ecaps = [], []
        for st, c in zip(states, counts):
            v_crowd = int(c[2]) > GROW_LOAD_FACTOR * st.v_capacity / 2
            e_crowd = int(c[3]) > GROW_LOAD_FACTOR * st.e_capacity / 2
            new_vcaps.append(2 * st.v_capacity if v_crowd else st.v_capacity)
            new_ecaps.append(2 * st.e_capacity if e_crowd else st.e_capacity)
        if all(vc == st.v_capacity for vc, st in zip(new_vcaps, states)) and all(
            ec == st.e_capacity for ec, st in zip(new_ecaps, states)
        ):
            # an engine-pass overflow with no crowded table: a pathological
            # probe chain somewhere — double everything, same as 1-shard
            new_vcaps = [2 * vc for vc in new_vcaps]
            new_ecaps = [2 * ec for ec in new_ecaps]
        impl = maintenance.resolve_impl(self.maintenance_impl)
        if self.obs.enabled:
            self.obs.counter("growth.events")
            self.obs.event(
                "growth.grow_shards",
                v_before=[st.v_capacity for st in states],
                v_after=list(new_vcaps),
                e_before=[st.e_capacity for st in states],
                e_after=list(new_ecaps),
            )
        endpoints = sharding.gather_live_vertices(states)
        for _ in range(_MAX_GROW_ATTEMPTS):
            outs = [
                maintenance.rehash(
                    st, vc, ec, impl=impl, with_csr=False, endpoints=endpoints
                )
                for st, vc, ec in zip(states, new_vcaps, new_ecaps)
            ]
            oks = [bool(ok) for _, _, ok in outs]
            if all(oks):
                return sharding.place_shards([s for s, _, _ in outs], self._mesh)
            self.obs.counter("growth.escalations")
            new_vcaps = [2 * vc if not ok else vc for vc, ok in zip(new_vcaps, oks)]
            new_ecaps = [2 * ec if not ok else ec for ec, ok in zip(new_ecaps, oks)]
        raise RuntimeError("rehash placement did not converge")

    # -- the paper's six-operation convenience API -------------------------
    def add_vertex(self, u: int) -> bool:
        return bool(self.apply([OP_ADD_VERTEX], [u])[0])

    def remove_vertex(self, u: int) -> bool:
        return bool(self.apply([OP_REMOVE_VERTEX], [u])[0])

    def contains_vertex(self, u: int) -> bool:
        return bool(self.apply([OP_CONTAINS_VERTEX], [u])[0])

    def add_edge(self, u: int, v: int) -> bool:
        return bool(self.apply([OP_ADD_EDGE], [u], [v])[0])

    def remove_edge(self, u: int, v: int) -> bool:
        return bool(self.apply([OP_REMOVE_EDGE], [u], [v])[0])

    def contains_edge(self, u: int, v: int) -> bool:
        return bool(self.apply([OP_CONTAINS_EDGE], [u], [v])[0])

    # -- traversal queries (batched wait-free reachability) -----------------
    #
    # All queries run against one cached TraversalCSR snapshot — a compacted,
    # consistent view of the post-batch state.  The snapshot is rebuilt lazily
    # after any ``apply`` (the linearization point of every query in between
    # is that batch boundary, like the related papers' wait-free snapshots).

    def traversal_csr(self) -> traversal.TraversalCSR:
        """The cached consistent snapshot all queries linearize against.

        With ``csr_maintenance="delta"``, update batches queued since the
        last query are folded into the previous snapshot in one
        :func:`repro.core.traversal.apply_delta` call (result-blind
        reconciliation re-probes the union of touched keys against the
        *current* state, so one fold over many batches is exact); otherwise
        the snapshot is recompacted from scratch.

        Sharded graphs (``n_shards > 1``) rebuild the global snapshot from
        the partitioned shard states
        (:func:`repro.core.sharding.fuse_partitioned`): per-shard edge
        lanes are validated against the canonical global vertex directory
        and sorted into the one CSR every query linearizes against.  The
        incremental delta fold does not apply — per-shard slot spaces are
        private, so the directory (and with it every fused slot) can move
        on any vertex churn."""
        reg = self.obs
        if self.n_shards > 1:
            if self._csr is None:
                with obsm.use(reg), reg.span("csr.fuse"):
                    reg.counter("csr.fuse")
                    self._csr = sharding.fuse_partitioned(self._shards)
            return self._csr
        if self._csr is None:
            with obsm.use(reg):
                if self._delta_base is not None and self._delta_batches:
                    with reg.span("csr.delta_fold"):
                        reg.counter("csr.delta_fold")
                        self._csr = traversal.apply_delta(
                            self._delta_base,
                            self.state,
                            np.concatenate([b[0] for b in self._delta_batches]),
                            np.concatenate([b[1] for b in self._delta_batches]),
                            np.concatenate([b[2] for b in self._delta_batches]),
                            impl=self.maintenance_impl,
                        )
                else:
                    with reg.span("csr.build"):
                        reg.counter("csr.build")
                        self._csr = traversal.build_csr(self.state)
            self._delta_base = None
            self._delta_batches = []
        return self._csr

    @staticmethod
    def _pad_keys(keys: Sequence[int]) -> Tuple[np.ndarray, int]:
        """Pad a query key batch to a power-of-two bucket with EMPTY_KEY lanes
        (same recompile-avoidance trick as ``apply``'s NOP padding)."""
        arr = np.asarray(keys, np.int32)
        return traversal._pad_pow2(arr, int(EMPTY_KEY)), arr.shape[0]

    def reachable(self, us, vs) -> np.ndarray:
        """Batched directed reachability: bool[n], ``us[i] ↝ vs[i]``.

        False when either endpoint is absent; ``u ↝ u`` is True iff u exists
        (the empty path).  Scalars are accepted and return a plain bool."""
        scalar = np.isscalar(us)
        if scalar:
            us, vs = [us], [vs]
        if len(us) != len(vs):
            raise ValueError(f"reachable: {len(us)} sources vs {len(vs)} targets")
        pu, n = self._pad_keys(us)
        pv, _ = self._pad_keys(vs)
        self.obs.counter("query.reachable", n)
        out = np.asarray(
            traversal.reachable(self.traversal_csr(), pu, pv, impl=self.traversal_impl)
        )[:n]
        return bool(out[0]) if scalar else out

    def bfs(self, u: int) -> Dict[int, int]:
        """BFS level map from ``u``: {vertex_key: hop_distance}, ``u`` at 0.
        Empty when ``u`` is absent."""
        return self.bfs_batch([u])[0]

    def bfs_batch(self, sources: Sequence[int]) -> List[Dict[int, int]]:
        """Batched BFS: one level map per source, all against one snapshot.

        Its child spans follow one another and cover the call: the
        snapshot, the dispatch of the level loop, the wait for the level
        maps, and the maps turned into dicts."""
        reg = self.obs
        with obsm.use(reg), reg.span("graph.bfs_batch"):
            with reg.span("graph.bfs_batch.snapshot"):
                pk, n = self._pad_keys(sources)
                csr = self.traversal_csr()
            with reg.span("graph.bfs_batch.dispatch"):
                levels = traversal.bfs_levels(csr, pk, impl=self.traversal_impl)
            with reg.span("graph.bfs_batch.readback"):
                levels = np.asarray(levels)[:n]
                if reg.enabled:
                    # frontier iterations per source = deepest reached level
                    # (the level map is computed regardless — obs only
                    # reduces it)
                    reg.counter("query.bfs", n)
                    reg.hist(
                        "bfs.depth", [int(max(row.max(initial=0), 0)) for row in levels]
                    )
                    reg.counter(
                        "frontier.lanes_streamed",
                        traversal.lanes_streamed(csr, len(pk), self.traversal_impl),
                    )
                    reg.counter("frontier.lane_capacity", csr.e_capacity)
            with reg.span("graph.bfs_batch.to_dicts"):
                v_key = np.asarray(csr.v_key)
                out = []
                for row in levels:
                    hit = np.nonzero(row >= 0)[0]
                    out.append({int(v_key[j]): int(row[j]) for j in hit})
        return out

    def khop(self, u: int, k: int) -> Set[int]:
        """Vertex keys within ≤k directed hops of ``u`` (including ``u``)."""
        return self.khop_batch([u], k)[0]

    def khop_batch(self, sources: Sequence[int], k: int) -> List[Set[int]]:
        """Batched k-hop: one key set per source, all against one snapshot."""
        pk, n = self._pad_keys(sources)
        csr = self.traversal_csr()
        self.obs.counter("query.khop", n)
        mask = np.asarray(
            traversal.khop_mask(csr, pk, np.int32(k), impl=self.traversal_impl)
        )[:n]
        v_key = np.asarray(csr.v_key)
        return [{int(v_key[j]) for j in np.nonzero(row)[0]} for row in mask]

    def get_path(self, u: int, v: int) -> Optional[List[int]]:
        """A shortest directed path ``u ↝ v`` as an explicit key list
        (``[u, ..., v]``; ``[u]`` when u == v), or ``None`` when unreachable
        or either endpoint is absent — the papers' ``GetPath``."""
        return self.get_path_batch([u], [v])[0]

    def get_path_batch(self, us, vs) -> List[Optional[List[int]]]:
        """Batched ``GetPath``: one shortest path (or None) per (u, v) pair,
        all answered against one snapshot.

        The device half (:func:`repro.core.traversal.path_probe`) records a
        parent slot per reached vertex as one extra scatter in the BFS level
        loop; the host walks the parent chain back from each target — at
        most one step per level, so reconstruction is O(path length)."""
        if len(us) != len(vs):
            raise ValueError(f"get_path_batch: {len(us)} sources vs {len(vs)} targets")
        pu, n = self._pad_keys(us)
        pv, _ = self._pad_keys(vs)
        csr = self.traversal_csr()
        self.obs.counter("query.get_path", n)
        levels, parents, vslot, vlive = (
            np.asarray(x)
            for x in traversal.path_probe(csr, pu, pv, impl=self.traversal_impl)
        )
        v_key = np.asarray(csr.v_key)
        out: List[Optional[List[int]]] = []
        for i in range(n):
            if not vlive[i] or levels[i, vslot[i]] < 0:
                out.append(None)
                continue
            chain = [int(vslot[i])]
            while levels[i, chain[-1]] > 0:
                chain.append(int(parents[i, chain[-1]]))
            out.append([int(v_key[s]) for s in reversed(chain)])
        return out

    # -- introspection ------------------------------------------------------
    def probe_health(self) -> Dict[str, Dict[int, int]]:
        """Physical probe-chain-length histograms over both hash tables
        (all shards), recorded into the graph's registry as ``probe.vertex``
        / ``probe.edge`` and returned — see :mod:`repro.obs.probes` for the
        derivation and its invariance properties."""
        from ..obs import probes

        return probes.record(self.obs, self)

    def snapshot(self) -> Tuple[set, set]:
        """Abstract (V, E) — for oracle comparison in tests.

        Vectorized: one device pass computes the live-vertex and
        incarnation-valid-edge masks (shared with the traversal engine's CSR
        validity predicate); host work is O(live), not O(capacity).

        Sharded graphs union the per-shard live-vertex partitions and
        validate every shard's edge lanes against the global sorted
        endpoint index (an edge's endpoints generally live on other
        shards)."""
        if self.n_shards > 1:
            sk, si = sharding.gather_live_vertices(self._shards)
            verts = set(sk.tolist())
            edges = set()
            if sk.size == 0:
                return verts, edges  # no live endpoints -> no valid edges
            for st in self._shards:
                e_live = np.asarray(st.e_live)
                eu = np.asarray(st.e_key_u)
                ev = np.asarray(st.e_key_v)
                fu, pu = sharding._lookup_sorted(sk, eu)
                fv, pv = sharding._lookup_sorted(sk, ev)
                valid = (
                    e_live
                    & fu
                    & fv
                    & (si[pu] == np.asarray(st.e_inc_u))
                    & (si[pv] == np.asarray(st.e_inc_v))
                )
                edges |= set(zip(eu[valid].tolist(), ev[valid].tolist()))
            return verts, edges
        v_mask, e_mask = traversal.snapshot_live(self.state)
        v_mask = np.asarray(v_mask)
        e_mask = np.asarray(e_mask)
        verts = set(np.asarray(self.state.v_key)[v_mask].tolist())
        eu = np.asarray(self.state.e_key_u)[e_mask].tolist()
        ev = np.asarray(self.state.e_key_v)[e_mask].tolist()
        return verts, set(zip(eu, ev))
