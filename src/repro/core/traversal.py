"""Batched wait-free reachability + snapshot traversal engine.

The paper's graph answers the six membership operations; its lineage —
Chatterjee et al. (arXiv 1809.00896, non-blocking graph with reachability
queries) and Bhardwaj et al. (arXiv 2310.02380, wait-free snapshots) — shows
that *traversal* queries over a consistent snapshot are what real workloads
run on top.  This module is the dataflow analogue of their wait-free
``GetPath``/snapshot:

1. **Snapshot compaction** (:func:`build_csr`) — one jitted pass compacts the
   live, incarnation-valid edge set of a :class:`GraphState` into CSR form.
   Vertex identity is the *table slot* (stable within a state), so no key
   remapping is needed: edges resolve their endpoint slots via the same
   bounded-probe :func:`~repro.core.locate.locate_vertices` the engines use,
   stale bindings (incarnation mismatch — the Fig. 3 hazard) are masked out,
   survivors are sorted by source slot, and row offsets fall out of two
   ``searchsorted`` calls.  The CSR is a pure value: queries against it are
   trivially linearizable at the batch boundary of the state it was built
   from — every query in a batch observes the *same* post-batch graph.

2. **Incremental maintenance** (:func:`apply_delta`) — instead of throwing
   the CSR away after every update batch, fold the batch's effects into it:
   re-probe only the touched keys (one jitted locate over the batch, not the
   table), drop lanes invalidated by vertex churn, splice in the new edge
   lanes, and re-sort the O(batch)-sized delta into the surviving runs.  The
   result is bit-identical to ``build_csr`` on the post state; when a rehash
   moved the tables or the delta is a large fraction of the edge set, it
   falls back to the full rebuild automatically.

3. **Batched frontier BFS** (:func:`bfs_levels` / :func:`bfs_parents`) — a
   jitted ``lax.while_loop`` expands all S source frontiers simultaneously.
   Each level is one :func:`repro.kernels.frontier.frontier_expand` call —
   every edge whose source is on the frontier proposes its source slot to
   its destination, which keeps the *min* — so the same pass yields both
   the new frontier (hit iff min proposer < NBR_INF) and the BFS *parent*
   of every newly reached slot (the papers' ``GetPath`` pointer).  The XLA
   implementation (the default on every backend) pulls: it sorts the
   snapshot's lanes by destination once per call, before the loop
   (:func:`repro.kernels.frontier.pull_view`), and each level reduces every
   destination's in-edge segment with a segmented min-scan over the blocks
   that hold a valid lane.  ``impl="kernel_interpret"`` runs the Pallas
   kernel over the raw lanes instead; the two are bit-identical.  The
   iteration count is bounded by the live vertex count (no path is
   longer), so the loop is bounded-depth — the traversal analogue of the
   engines' wait-free locate bound — and an edge-free snapshot skips the
   loop entirely.

4. **Query forms** — :func:`reachable` (pairwise u↝v for a whole batch),
   :func:`bfs_levels` (full level maps), :func:`bfs_parents` (levels +
   parent slots), :func:`path_probe` (everything ``GetPath`` reconstruction
   needs), :func:`khop_mask` (bounded-depth neighborhoods).  All are exact
   against :class:`repro.core.oracle` (see ``tests/test_traversal.py``).

**Linearization point** (the dataflow mirror of the related papers'
snapshot theorems): *every query against a ``TraversalCSR`` linearizes at
the boundary of the update batch whose post-state the CSR was built (or
delta-folded) from; all queries sharing one CSR observe the same abstract
graph, and no query observes a partially applied batch.*  This holds
because a CSR is a pure value compacted from one installed
:class:`~repro.core.types.GraphState` — there is no interleaving to
observe.  Under hash-prefix sharding the same statement holds for the
*fused* CSR (:func:`repro.core.sharding.fuse_partitioned`): every shard
installed its post-batch state before fusion, and shards partition both
key spaces disjointly, so the fusion — per-shard edge lanes validated
against the canonical global vertex directory — is a consistent cut at
the same batch boundary.

Host-side convenience wrappers (key-space in/out, batch bucketing, path
reconstruction) live on :class:`repro.core.graph.WaitFreeGraph`.  The
paper-to-code map for this module is ``docs/ARCHITECTURE.md``; the kernel
family contract behind :func:`frontier_expand` is ``docs/KERNELS.md``.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.frontier import NBR_INF, frontier_expand, pull_view
from repro.kernels.frontier import ops as frontier_ops
from repro.kernels.frontier.xla import edge_blocks

# ambient telemetry (no-op unless a registry is active — see repro.obs and
# docs/OBSERVABILITY.md; metrics imports nothing from repro.core)
from ..obs import metrics as obsm
from .locate import locate_edges, locate_vertices
from .types import (
    EMPTY_KEY,
    OP_ADD_EDGE,
    OP_ADD_VERTEX,
    OP_REMOVE_EDGE,
    OP_REMOVE_VERTEX,
    GraphState,
)

_NO_LEVEL = jnp.int32(-1)
_NO_PARENT = jnp.int32(-1)


class TraversalCSR(NamedTuple):
    """A compacted, consistent snapshot of one :class:`GraphState`.

    Vertices are identified by their slot in the originating vertex table
    (``0 .. Cv-1``); ``Cv`` itself is the sentinel slot for "no vertex".
    Edge arrays are sorted by ``src`` with invalid lanes pushed to the end
    (``src == dst == Cv``), so ``row_start/row_end`` delimit each slot's
    out-neighbor run.  ``lane`` records each entry's pre-sort edge-table
    lane — the provenance :func:`apply_delta` needs to splice update batches
    into the sorted arrays bit-identically to a full rebuild.
    """

    v_key: jnp.ndarray      # i32[Cv] — table keys (EMPTY_KEY where unused)
    v_live: jnp.ndarray     # bool[Cv]
    v_inc: jnp.ndarray      # i32[Cv] — incarnations (delta churn detection)
    n_live: jnp.ndarray     # i32[] — live vertex count (BFS depth bound)
    src: jnp.ndarray        # i32[Ce] — source slot per edge lane, sorted; Cv = invalid
    dst: jnp.ndarray        # i32[Ce] — destination slot, aligned with src
    lane: jnp.ndarray       # i32[Ce] — originating edge-table lane per entry
    row_start: jnp.ndarray  # i32[Cv] — CSR offsets into src/dst
    row_end: jnp.ndarray    # i32[Cv]
    n_edges: jnp.ndarray    # i32[] — valid edge count

    @property
    def v_capacity(self) -> int:
        return self.v_key.shape[0]

    @property
    def e_capacity(self) -> int:
        return self.src.shape[0]


def _edge_validity(state: GraphState):
    """Per-edge-lane validity — the Fig. 3 hazard mask shared by the CSR
    build and the snapshot: an edge lane is valid iff it is live, both
    endpoint keys locate to table slots, both endpoints are live, and both
    stored incarnations equal the endpoints' current incarnations (stale
    bindings from removed-and-re-added vertices are exactly the lanes this
    masks out).  Returns (src_slot, dst_slot, valid)."""
    has_edge = state.e_key_u != EMPTY_KEY
    loc_u = locate_vertices(state.v_key, state.e_key_u, has_edge & state.e_live)
    loc_v = locate_vertices(state.v_key, state.e_key_v, has_edge & state.e_live)
    su = jnp.where(loc_u.found, loc_u.slot, 0)
    sv = jnp.where(loc_v.found, loc_v.slot, 0)
    valid = (
        state.e_live
        & loc_u.found
        & loc_v.found
        & state.v_live[su]
        & state.v_live[sv]
        & (state.v_inc[su] == state.e_inc_u)
        & (state.v_inc[sv] == state.e_inc_v)
    )
    return su, sv, valid


def csr_from_lanes(
    state: GraphState,
    src_lane: jnp.ndarray,
    dst_lane: jnp.ndarray,
    n_live: jnp.ndarray,
    n_edges: jnp.ndarray,
) -> TraversalCSR:
    """Sort per-lane endpoint slots (``Cv`` marks an invalid lane) into CSR
    form.  The order is two keys, source slot then edge-table lane: a stable
    sort of lane order by source.  Every snapshot path (rebuild, device delta
    fold, snapshot-compact) ends here, which is what makes them
    bit-identical."""
    cv = state.v_key.shape[0]
    order = jnp.argsort(src_lane, stable=True).astype(jnp.int32)
    src = src_lane[order]
    rows = jnp.arange(cv, dtype=jnp.int32)
    return TraversalCSR(
        v_key=state.v_key,
        v_live=state.v_live,
        v_inc=state.v_inc,
        n_live=n_live,
        src=src,
        dst=dst_lane[order],
        lane=order,
        row_start=jnp.searchsorted(src, rows, side="left").astype(jnp.int32),
        row_end=jnp.searchsorted(src, rows, side="right").astype(jnp.int32),
        n_edges=n_edges,
    )


@jax.jit
def build_csr(state: GraphState) -> TraversalCSR:
    """Compact the live, incarnation-valid edge set into CSR form
    (validity per :func:`_edge_validity`)."""
    cv = state.v_key.shape[0]
    su, sv, valid = _edge_validity(state)
    return csr_from_lanes(
        state,
        jnp.where(valid, su, cv).astype(jnp.int32),
        jnp.where(valid, sv, cv).astype(jnp.int32),
        jnp.sum(state.v_live).astype(jnp.int32),
        jnp.sum(valid).astype(jnp.int32),
    )


# ---------------------------------------------------------------------------
# incremental CSR maintenance
# ---------------------------------------------------------------------------

def _pad_pow2(a: np.ndarray, fill: int, floor: int = 16) -> np.ndarray:
    """Pad to a power-of-two bucket so the jitted delta probe compiles once
    per bucket, not once per batch size (same trick as the engines)."""
    n = a.shape[0]
    bucket = max(floor, 1 << max(n - 1, 1).bit_length())
    out = np.full(bucket, fill, a.dtype)
    out[:n] = a
    return out


class DeltaProbe(NamedTuple):
    """Everything a delta fold needs to know about the touched keys, as
    resolved against the *post* state (all device arrays)."""

    v_found: jnp.ndarray     # bool[nv] — touched vertex key present (live or tomb)
    v_slot: jnp.ndarray      # i32[nv]
    v_live_now: jnp.ndarray  # bool[nv]
    v_inc_now: jnp.ndarray   # i32[nv]
    e_found: jnp.ndarray     # bool[ne] — touched edge key has a table lane
    e_lane: jnp.ndarray      # i32[ne]
    e_valid: jnp.ndarray     # bool[ne] — lane live + incarnation-valid now
    e_su: jnp.ndarray        # i32[ne] — endpoint slots (where e_found)
    e_sv: jnp.ndarray        # i32[ne]
    n_live: jnp.ndarray      # i32[] — post-state live vertex count


def _delta_probe_parts(
    state: GraphState, vkeys: jnp.ndarray, eus: jnp.ndarray, evs: jnp.ndarray
) -> DeltaProbe:
    """Resolve the touched keys against the post state: vertex slots +
    liveness + incarnations, edge lanes + endpoint slots + validity, and the
    new live count.  O(batch) probes instead of ``build_csr``'s O(capacity).
    Shared by the packed host transfer (:func:`_delta_probe`) and the fused
    device merge (:func:`repro.core.maintenance.delta_merge`)."""
    vloc = locate_vertices(state.v_key, vkeys, vkeys != EMPTY_KEY)
    v_safe = jnp.where(vloc.found, vloc.slot, 0)

    e_active = eus != EMPTY_KEY
    eloc = locate_edges(state.e_key_u, state.e_key_v, eus, evs, e_active)
    e_safe = jnp.where(eloc.found, eloc.slot, 0)
    lu = locate_vertices(state.v_key, eus, eloc.found)
    lv = locate_vertices(state.v_key, evs, eloc.found)
    su = jnp.where(lu.found, lu.slot, 0)
    sv = jnp.where(lv.found, lv.slot, 0)
    e_valid = (
        eloc.found
        & state.e_live[e_safe]
        & lu.found
        & lv.found
        & state.v_live[su]
        & state.v_live[sv]
        & (state.v_inc[su] == state.e_inc_u[e_safe])
        & (state.v_inc[sv] == state.e_inc_v[e_safe])
    )
    return DeltaProbe(
        v_found=vloc.found,
        v_slot=v_safe.astype(jnp.int32),
        v_live_now=state.v_live[v_safe],
        v_inc_now=state.v_inc[v_safe],
        e_found=eloc.found,
        e_lane=e_safe.astype(jnp.int32),
        e_valid=e_valid,
        e_su=su.astype(jnp.int32),
        e_sv=sv.astype(jnp.int32),
        n_live=jnp.sum(state.v_live).astype(jnp.int32),
    )


@functools.partial(jax.jit, static_argnames=("nv", "ne"))
def _delta_probe(state: GraphState, pack: jnp.ndarray, nv: int, ne: int):
    """Packed-transfer wrapper around :func:`_delta_probe_parts` for the host
    splice path.  The touched keys arrive as one packed i32 buffer
    (vkeys | e_us | e_vs, each padded to a power-of-two bucket) — a single
    host-to-device transfer; per-array device_puts were the dominant cost of
    the delta path on CPU."""
    p = _delta_probe_parts(state, pack[:nv], pack[nv:nv + ne], pack[nv + ne:])
    # one packed i32 result (bools widened) = one device-to-host transfer;
    # n_live stays a device scalar — it goes straight back into the CSR
    out = jnp.concatenate(
        [
            p.v_found.astype(jnp.int32),
            p.v_slot,
            p.v_live_now.astype(jnp.int32),
            p.v_inc_now,
            p.e_found.astype(jnp.int32),
            p.e_lane,
            p.e_valid.astype(jnp.int32),
            p.e_su,
            p.e_sv,
        ]
    )
    return out, p.n_live


@functools.partial(jax.jit, static_argnames=("ce", "cv"))
def _delta_splice(pack: jnp.ndarray, ce: int, cv: int):
    """Unpack the host-assembled sorted edge arrays (one transfer) and derive
    the row offsets on device — the same ``searchsorted`` calls as
    :func:`build_csr`, so the delta result is bit-identical by construction."""
    src = pack[:ce]
    dst = pack[ce:2 * ce]
    lane = pack[2 * ce:3 * ce]
    n_edges = pack[3 * ce]
    rows = jnp.arange(cv, dtype=jnp.int32)
    row_start = jnp.searchsorted(src, rows, side="left").astype(jnp.int32)
    row_end = jnp.searchsorted(src, rows, side="right").astype(jnp.int32)
    return src, dst, lane, row_start, row_end, n_edges


def apply_delta(
    csr: TraversalCSR,
    state: GraphState,
    ops,
    us,
    vs=None,
    *,
    max_delta_frac: float = 0.25,
    impl: Optional[str] = None,
) -> TraversalCSR:
    """Fold one applied update batch into an existing snapshot.

    ``csr`` must be the snapshot of the pre-batch state and ``state`` the
    post-batch state the engine returned for ``(ops, us, vs)``.  The result
    is **bit-identical** to ``build_csr(state)`` — same sorted edge arrays,
    same lane provenance, same offsets.  The probe side is O(batch) (one
    jitted locate over the touched keys instead of the whole table).

    ``impl`` picks the splice side (``None`` = auto: device on TPU, host
    elsewhere — ``maintenance.resolve_impl``):

    * ``"device"`` / ``"device_interpret"`` — the whole fold is one fused
      jitted pass (:func:`repro.core.maintenance.delta_merge`): the O(batch)
      touched keys are re-probed, the surviving entries and the delta are
      scattered back to lane order, and one stable sort by source slot
      restores the rebuild's (source, lane) order — no O(capacity) re-probe
      of the table.  One host-to-device transfer (the packed touched keys),
      zero transfers back.
    * ``"host"`` — the numpy splice: mask updates and a lexsort over the
      surviving lanes on the host (O(valid edges) with small vectorized
      constants).  Kept as the oracle the device merge is tested
      bit-identical against.

    Falls back to :func:`build_csr` automatically when

    * either table capacity changed (a growth rehash moved every slot), or
    * the touched-key footprint exceeds ``max_delta_frac`` of the edge
      capacity (re-sorting the delta would approach the full rebuild).

    The reconciliation is *result-blind*: it re-probes the touched keys
    against the post state rather than trusting per-op success bits, so
    duplicate ops, failed ops, and within-batch remove/re-add churn are all
    handled by construction.
    """
    ce = csr.e_capacity
    if state.v_capacity != csr.v_capacity or state.e_capacity != ce:
        obsm.counter("csr.delta.rebuild_capacity_changed")
        return build_csr(state)  # rehash: every slot moved

    ops = np.asarray(ops, np.int32)
    us = np.asarray(us, np.int32)
    vs = np.zeros_like(us) if vs is None else np.asarray(vs, np.int32)

    # dedup touched keys (cheap int64 codes beat np.unique(axis=1) here)
    v_touch = np.unique(us[(ops == OP_ADD_VERTEX) | (ops == OP_REMOVE_VERTEX)])
    e_mask = (ops == OP_ADD_EDGE) | (ops == OP_REMOVE_EDGE)
    e_code = np.unique(
        (us[e_mask].astype(np.int64) << 32) | (vs[e_mask].astype(np.int64) & 0xFFFFFFFF)
    )
    e_tu = (e_code >> 32).astype(np.int32)
    e_tv = e_code.astype(np.int32)
    if v_touch.size == 0 and e_code.size == 0:
        obsm.counter("csr.delta.readonly")
        return csr  # read-only batch: the snapshot is still exact
    if v_touch.size + e_code.size > max(32, int(max_delta_frac * ce)):
        obsm.counter("csr.delta.rebuild_too_large")
        return build_csr(state)  # delta too large to beat the rebuild
    obsm.counter("csr.delta.folded")
    obsm.hist("csr.delta.touched", int(v_touch.size + e_code.size))

    v_pad = _pad_pow2(v_touch.astype(np.int32), int(EMPTY_KEY))
    eu_pad = _pad_pow2(e_tu, int(EMPTY_KEY))
    ev_pad = _pad_pow2(e_tv, 0)
    nvp, nep = v_pad.shape[0], eu_pad.shape[0]

    from . import maintenance  # deferred: maintenance imports this module

    if maintenance.resolve_impl(impl) != "host":
        return maintenance.delta_merge(
            csr, state, np.concatenate([v_pad, eu_pad, ev_pad]), nvp, nep
        )

    packed, n_live = _delta_probe(
        state, np.concatenate([v_pad, eu_pad, ev_pad]), nvp, nep
    )
    packed = np.asarray(packed)
    nv, ne = v_touch.size, e_code.size
    v_found = packed[:nv].astype(bool)
    v_slot = packed[nvp:nvp + nv]
    v_live_now = packed[2 * nvp:2 * nvp + nv].astype(bool)
    v_inc_now = packed[3 * nvp:3 * nvp + nv]
    eoff = 4 * nvp
    e_found = packed[eoff:eoff + ne].astype(bool)
    e_lane = packed[eoff + nep:eoff + nep + ne]
    e_valid = packed[eoff + 2 * nep:eoff + 2 * nep + ne].astype(bool)
    e_su = packed[eoff + 3 * nep:eoff + 3 * nep + ne]
    e_sv = packed[eoff + 4 * nep:eoff + 4 * nep + ne]

    # vertices whose (live, inc) changed invalidate every lane bound to them
    pre_live = np.asarray(csr.v_live)
    pre_inc = np.asarray(csr.v_inc)
    vsl = v_slot[v_found]
    changed = vsl[(pre_live[vsl] != v_live_now[v_found])
                  | (pre_inc[vsl] != v_inc_now[v_found])]

    n_e = int(csr.n_edges)
    src_v = np.asarray(csr.src)[:n_e]
    dst_v = np.asarray(csr.dst)[:n_e]
    lane_v = np.asarray(csr.lane)[:n_e]

    keep = np.ones(n_e, bool)
    if changed.size:
        hit = np.zeros(csr.v_capacity + 1, bool)
        hit[changed] = True
        keep &= ~(hit[src_v] | hit[dst_v])
    touched_lanes = e_lane[e_found]
    if touched_lanes.size:
        # every touched edge key is re-derived from the post state below;
        # drop its old entry (if any) so the splice is the single source
        lhit = np.zeros(ce, bool)
        lhit[touched_lanes] = True
        keep &= ~lhit[lane_v]

    ins = e_found & e_valid
    new_src = e_su[ins].astype(np.int32)
    new_dst = e_sv[ins].astype(np.int32)
    new_lane = e_lane[ins].astype(np.int32)

    src_all = np.concatenate([src_v[keep], new_src])
    dst_all = np.concatenate([dst_v[keep], new_dst])
    lane_all = np.concatenate([lane_v[keep], new_lane])
    order = np.lexsort((lane_all, src_all))  # == build_csr's stable sort by src
    src_all, dst_all, lane_all = src_all[order], dst_all[order], lane_all[order]

    cv = csr.v_capacity
    n_valid = src_all.shape[0]
    lane_used = np.zeros(ce, bool)
    lane_used[lane_all] = True
    tail_lane = np.nonzero(~lane_used)[0].astype(np.int32)  # ascending, as argsort leaves it
    invalid = np.full(ce - n_valid, cv, np.int32)
    pack = np.concatenate(
        [src_all, invalid, dst_all, invalid, lane_all, tail_lane,
         np.asarray([n_valid], np.int32)]
    )
    src, dst, lane, row_start, row_end, n_edges = _delta_splice(pack, ce, cv)

    return TraversalCSR(
        v_key=state.v_key,
        v_live=state.v_live,
        v_inc=state.v_inc,
        n_live=n_live,
        src=src,
        dst=dst,
        lane=lane,
        row_start=row_start,
        row_end=row_end,
        n_edges=n_edges,
    )


# ---------------------------------------------------------------------------
# batched frontier BFS
# ---------------------------------------------------------------------------


def _locate_live_slots(csr: TraversalCSR, keys: jnp.ndarray):
    """Map query keys to live slots; returns (slot, is_live) with slot=Cv when
    absent/dead.  EMPTY_KEY query lanes (batch padding) resolve to dead."""
    active = keys != EMPTY_KEY
    loc = locate_vertices(csr.v_key, keys, active)
    safe = jnp.where(loc.found, loc.slot, 0)
    live = loc.found & csr.v_live[safe]
    slot = jnp.where(live, loc.slot, csr.v_capacity).astype(jnp.int32)
    return slot, live


def _bfs_from_slots(
    csr: TraversalCSR,
    slot: jnp.ndarray,
    live: jnp.ndarray,
    impl: Optional[str],
    max_depth: Optional[jnp.ndarray] = None,
):
    """The frontier loop, from already-located source slots (callers resolve
    each endpoint set exactly once — see :func:`reachable`).  Returns
    (levels, parents): i32[S, Cv] each, -1 for unreached / no parent.
    ``max_depth`` stops the expansion after that many levels (k-hop).

    One :func:`frontier_expand` per level: its min proposer is both the
    discovery mask (min < NBR_INF) and the parent pointer of every newly
    reached slot.  The XLA expansion runs over a destination-sorted
    :func:`~repro.kernels.frontier.pull_view` of the CSR, sorted once here,
    before the loop, and shared by every level and source; it streams only
    the lane blocks that hold one of the ``n_edges`` valid lanes.  On the
    device the sort's ops carry the name ``traversal.pull_view``, a level's
    ``traversal.frontier_expand`` (the expansion) and
    ``traversal.level_update`` (the rest of the level).  An
    ``n_edges == 0`` snapshot returns the source-only maps without
    sorting or entering the loop at all.
    """
    cv = csr.v_capacity
    n_src = slot.shape[0]

    # one extra column absorbs sentinel slot Cv (invalid edges / dead sources)
    frontier = jnp.zeros((n_src, cv + 1), bool)
    frontier = frontier.at[jnp.arange(n_src), slot].set(live)
    levels = jnp.full((n_src, cv + 1), _NO_LEVEL)
    levels = jnp.where(frontier, 0, levels)
    parents = jnp.full((n_src, cv + 1), _NO_PARENT)
    bound = csr.n_live if max_depth is None else jnp.minimum(csr.n_live, max_depth)

    def cond(carry):
        _, _, frontier, depth = carry
        return jnp.any(frontier[:, :cv]) & (depth < bound)

    def expand_all(carry):
        view = None
        if frontier_ops.resolve(impl) == "xla":
            with jax.named_scope("traversal.pull_view"):
                view = pull_view(csr.src, csr.dst, cv + 1, n_src, n_live=csr.n_edges)

        def body(carry):
            levels, parents, frontier, depth = carry
            with jax.named_scope("traversal.frontier_expand"):
                nbr = frontier_expand(frontier, csr.src, csr.dst, impl=impl, view=view)
            with jax.named_scope("traversal.level_update"):
                new = (nbr != NBR_INF) & (levels == _NO_LEVEL)
                new = new.at[:, cv].set(False)
                levels = jnp.where(new, depth + 1, levels)
                parents = jnp.where(new, nbr, parents)
                return levels, parents, new, depth + 1

        return jax.lax.while_loop(cond, body, carry)

    init = (levels, parents, frontier, jnp.int32(0))
    levels, parents, _, _ = jax.lax.cond(
        csr.n_edges == 0,
        lambda c: c,  # edge-free snapshot: sources are the whole answer
        expand_all,
        init,
    )
    return levels[:, :cv], parents[:, :cv]


def lanes_streamed(csr: TraversalCSR, n_src: int, impl: Optional[str] = None) -> int:
    """Edge lanes one expansion of an ``n_src``-source query streams: the
    XLA path's blocks that hold a valid lane, or the kernel's whole lane
    capacity.  Reads ``n_edges`` back from the device."""
    if frontier_ops.resolve(impl) != "xla":
        return csr.e_capacity
    block, _ = edge_blocks(csr.e_capacity, n_src)
    return -(-int(csr.n_edges) // block) * block


@functools.partial(jax.jit, static_argnames=("impl",))
def bfs_parents(csr: TraversalCSR, src_keys: jnp.ndarray, impl: Optional[str] = None):
    """Batched BFS with parent pointers: (levels, parents), i32[S, Cv] each.

    ``levels[s, j]`` is the hop distance from ``src_keys[s]`` to the vertex
    in slot ``j`` (0 for the source itself, -1 unreachable); ``parents[s, j]``
    is the slot the BFS reached ``j`` from (-1 for sources and unreached
    slots).  Parents are deterministic: the minimum frontier source slot
    among ``j``'s in-edges, identical across the XLA and kernel impls.
    """
    slot, live = _locate_live_slots(csr, src_keys)
    return _bfs_from_slots(csr, slot, live, impl)


@functools.partial(jax.jit, static_argnames=("impl",))
def bfs_levels(
    csr: TraversalCSR, src_keys: jnp.ndarray, impl: Optional[str] = None
) -> jnp.ndarray:
    """Batched BFS level map: i32[S, Cv], -1 = unreachable.

    Sources that are absent, dead, or EMPTY_KEY padding yield all -1 rows.
    """
    return bfs_parents(csr, src_keys, impl=impl)[0]


@functools.partial(jax.jit, static_argnames=("impl",))
def reachable(
    csr: TraversalCSR, us: jnp.ndarray, vs: jnp.ndarray, impl: Optional[str] = None
) -> jnp.ndarray:
    """Batched reachability: bool[B], ``us[i] ↝ vs[i]`` by directed paths.

    False when either endpoint is absent/dead; ``u ↝ u`` is True iff u is
    live (the empty path).  Every pair is answered against the same snapshot.
    Each endpoint set is located exactly once: sources feed the frontier
    loop directly, targets only index the finished level map.
    """
    uslot, ulive = _locate_live_slots(csr, us)
    vslot, vlive = _locate_live_slots(csr, vs)
    levels, _ = _bfs_from_slots(csr, uslot, ulive, impl)
    safe = jnp.where(vlive, vslot, 0)
    return vlive & (levels[jnp.arange(us.shape[0]), safe] >= 0)


def _canonical_parents(csr: TraversalCSR, levels: jnp.ndarray) -> jnp.ndarray:
    """Rewrite BFS parents to the minimum-*key* predecessor on a shortest
    path (one scatter-min over the edge list).

    ``_bfs_from_slots``'s parents are the minimum frontier *slot*, which is
    layout-dependent: the same abstract graph held at different shard
    counts (or after a rehash) numbers slots differently, so when several
    shortest paths exist the reconstructed path would differ.  Keys are
    layout-invariant, so min-key parents make ``GetPath`` canonical —
    identical key sequences for ``n_shards ∈ {1, 2, 4}`` by construction."""
    cv = csr.v_capacity
    i32 = jnp.int32
    big = jnp.iinfo(jnp.int32).max
    n_src = levels.shape[0]

    # rank slots by key (live keys are unique; dead slots sort to the tail)
    order = jnp.argsort(jnp.where(csr.v_live, csr.v_key, big)).astype(i32)
    rank = jnp.zeros(cv, i32).at[order].set(jnp.arange(cv, dtype=i32))

    # transposed [Cv+1, S] level map, streamed over edge blocks like the
    # frontier expansion (:mod:`repro.kernels.frontier.xla`); sentinel row
    # cv absorbs invalid edge lanes (src == dst == cv) and block padding
    lt = jnp.concatenate([levels.T, jnp.full((1, n_src), _NO_LEVEL)], axis=0)
    rank = jnp.concatenate([rank, jnp.full((1,), big, i32)])
    n_edges = csr.src.shape[0]
    block, n_blocks = edge_blocks(n_edges, n_src)
    pad = jnp.full((n_blocks * block - n_edges,), cv, i32)
    src = jnp.concatenate([csr.src, pad])
    dst = jnp.concatenate([csr.dst, pad])

    def body(i, best):
        s = jax.lax.dynamic_slice(src, (i * block,), (block,))
        d = jax.lax.dynamic_slice(dst, (i * block,), (block,))
        ls, ld = lt[s], lt[d]
        on_path = (ls >= 0) & (ld == ls + 1)
        return best.at[d].min(jnp.where(on_path, rank[s][:, None], big))

    best = jax.lax.fori_loop(
        0, n_blocks, body, jnp.full((cv + 1, n_src), big, i32)
    )[:cv].T
    parent = jnp.where(
        (best < big) & (levels > 0), order[jnp.clip(best, 0, cv - 1)], _NO_PARENT
    )
    return parent


@functools.partial(jax.jit, static_argnames=("impl",))
def path_probe(
    csr: TraversalCSR, us: jnp.ndarray, vs: jnp.ndarray, impl: Optional[str] = None
):
    """Device half of ``GetPath``: (levels, parents, target_slot, target_live).

    One locate per endpoint set, one BFS for the whole batch; the host walks
    ``parents`` back from ``target_slot`` to reconstruct explicit key-space
    paths (:meth:`repro.core.graph.WaitFreeGraph.get_path`).  Parents are
    canonicalized to the minimum-key shortest-path predecessor
    (:func:`_canonical_parents`), so the reconstructed path is identical
    across table layouts — in particular across shard counts."""
    uslot, ulive = _locate_live_slots(csr, us)
    vslot, vlive = _locate_live_slots(csr, vs)
    levels, _ = _bfs_from_slots(csr, uslot, ulive, impl)
    return levels, _canonical_parents(csr, levels), vslot, vlive


@functools.partial(jax.jit, static_argnames=("impl",))
def khop_mask(
    csr: TraversalCSR, src_keys: jnp.ndarray, k: jnp.ndarray, impl: Optional[str] = None
) -> jnp.ndarray:
    """bool[S, Cv]: slots within ≤k directed hops of each source (incl. self);
    the expansion stops after k levels."""
    slot, live = _locate_live_slots(csr, src_keys)
    k = jnp.asarray(k, jnp.int32)
    levels, _ = _bfs_from_slots(csr, slot, live, impl, max_depth=k)
    return (levels >= 0) & (levels <= k)


@jax.jit
def snapshot_live(state: GraphState):
    """Device-side snapshot masks: (v_live_mask, e_valid_mask).

    ``e_valid_mask`` marks edge lanes that are live AND bound to both
    endpoints' current incarnations — the same :func:`_edge_validity`
    predicate the CSR build uses, exposed for vectorized host snapshots."""
    _, _, e_valid = _edge_validity(state)
    return state.v_live, e_valid
