"""Device-side state maintenance: rehash, snapshot-compact, delta-merge.

The acceptance bar for ``repro.core.maintenance`` is *bit-identity*: every
impl ("host" numpy oracle, "device" jnp/Pallas, "device_interpret") must
produce byte-for-byte the same tables and the same CSR as the references,
over ≥50 randomized graphs with deletion and incarnation churn, plus a
stress workload that forces repeated growth mid-stream."""

import numpy as np
import pytest

from repro.core import SequentialGraph, WaitFreeGraph, build_csr, run_sequential
from repro.core import maintenance, traversal
from repro.core.graph import _rehash
from repro.core.types import (
    EMPTY_KEY,
    OP_ADD_EDGE,
    OP_ADD_VERTEX,
    OP_REMOVE_VERTEX,
)
from repro.core.workloads import (
    initial_vertices,
    sample_batch,
    sample_query_pairs,
    sample_update_batch,
)

KEY_SPACE = 24

DEVICE_IMPLS = ("device", "device_interpret")


def _assert_same_fields(got, want, ctx=""):
    for name in want._fields:
        a, b = np.asarray(getattr(got, name)), np.asarray(getattr(want, name))
        assert a.dtype == b.dtype, (ctx, name, a.dtype, b.dtype)
        assert np.array_equal(a, b), (ctx, name)


def _apply_both(g: WaitFreeGraph, oracle: SequentialGraph, ops, us, vs):
    got = g.apply(ops, us, vs)
    exp, _ = run_sequential(ops, us, vs, graph=oracle)
    assert got.tolist() == exp


def _build_churned(seed: int, mode: str = "waitfree") -> tuple:
    """A randomized graph with tombstones and incarnation churn — the same
    recipe as test_traversal's ``_build_random`` (Fig. 3 hazards included)."""
    rng = np.random.default_rng(seed)
    g = WaitFreeGraph(256, 1024, mode=mode, maintenance_impl="host")
    oracle = SequentialGraph()
    for _ in range(2):
        ops, us, vs = sample_batch(rng, 192, "traversal", key_space=KEY_SPACE)
        _apply_both(g, oracle, ops, us, vs)
    kill = rng.choice(KEY_SPACE, size=8, replace=False).astype(np.int32)
    _apply_both(g, oracle, np.full(8, OP_REMOVE_VERTEX, np.int32), kill,
                np.zeros(8, np.int32))
    revive = kill[:4]
    _apply_both(g, oracle, np.full(4, OP_ADD_VERTEX, np.int32), revive,
                np.zeros(4, np.int32))
    ops, us, vs = sample_batch(rng, 96, "traversal", key_space=KEY_SPACE)
    _apply_both(g, oracle, ops, us, vs)
    return g, oracle, rng


# ---------------------------------------------------------------------------
# rehash: device vs host oracle, bit-identical (the acceptance criterion)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", ["waitfree", "fpsp"])
@pytest.mark.parametrize("seed", range(25))
def test_rehash_device_bit_identical_to_host_oracle(mode, seed):
    """2 modes × 25 seeds = 50 randomized churned graphs: the device rehash
    (jnp reference primitives) matches the numpy host oracle byte-for-byte,
    at growth capacities and at same-capacity pure compaction."""
    g, oracle, _ = _build_churned(seed, mode)
    state = g.state
    cases = [
        (2 * state.v_capacity, 2 * state.e_capacity),
        (state.v_capacity, state.e_capacity),  # pure compaction
    ]
    for new_vcap, new_ecap in cases:
        ref, _, ok_h = maintenance.rehash(state, new_vcap, new_ecap, impl="host")
        dev, _, ok_d = maintenance.rehash(state, new_vcap, new_ecap, impl="device")
        assert ok_h and ok_d
        _assert_same_fields(dev, ref, f"caps {new_vcap}x{new_ecap}")
        # the compacted state still represents the oracle's abstract graph
        g2 = WaitFreeGraph()
        g2.state = dev
        assert g2.snapshot() == (oracle.vertices, oracle.edges)


@pytest.mark.parametrize("seed", range(5))
def test_rehash_interpret_kernel_matches_host(seed):
    """The Pallas kernels through the interpreter produce the same tables
    and the same ready-made CSR (deep sweep lives in the device leg above;
    this pins the kernel path itself)."""
    g, _, _ = _build_churned(seed)
    state = g.state
    ref, csr_h, _ = maintenance.rehash(
        state, 2 * state.v_capacity, 2 * state.e_capacity, impl="host", with_csr=True
    )
    ker, csr_k, _ = maintenance.rehash(
        state, 2 * state.v_capacity, 2 * state.e_capacity,
        impl="device_interpret", with_csr=True,
    )
    _assert_same_fields(ker, ref, "state")
    _assert_same_fields(csr_k, csr_h, "csr")


@pytest.mark.parametrize("impl", ["host", *DEVICE_IMPLS])
def test_rehash_snapshot_compact_matches_build_csr(impl):
    """``with_csr=True`` hands back exactly ``build_csr`` of the new state —
    the "free" post-growth snapshot."""
    g, _, _ = _build_churned(99)
    state = g.state
    new_state, csr, ok = maintenance.rehash(
        state, 2 * state.v_capacity, 2 * state.e_capacity, impl=impl, with_csr=True
    )
    assert ok and csr is not None
    _assert_same_fields(csr, build_csr(new_state), impl)


def test_rehash_physical_deletion_invariants():
    """Device rehash obeys the Harris physical-deletion contract: every
    occupied slot is live, every surviving edge is bound to both endpoints'
    current incarnations (mirrors TestRehashPhysicalDeletion for the host)."""
    g, oracle, _ = _build_churned(7)
    state, _, ok = maintenance.rehash(
        g.state, g.state.v_capacity, g.state.e_capacity, impl="device"
    )
    assert ok
    v_key = np.asarray(state.v_key)
    v_live = np.asarray(state.v_live)
    occupied = v_key != EMPTY_KEY
    assert (v_live == occupied).all()
    inc_of = {int(k): int(i) for k, i in
              zip(v_key[occupied], np.asarray(state.v_inc)[occupied])}
    e_occ = np.asarray(state.e_key_u) != EMPTY_KEY
    assert (np.asarray(state.e_live) == e_occ).all()
    for u, v, bu, bv in zip(
        np.asarray(state.e_key_u)[e_occ],
        np.asarray(state.e_key_v)[e_occ],
        np.asarray(state.e_inc_u)[e_occ],
        np.asarray(state.e_inc_v)[e_occ],
    ):
        assert inc_of.get(int(u)) == int(bu)
        assert inc_of.get(int(v)) == int(bv)


def test_rehash_empty_and_vertex_only_states():
    """Degenerate inputs: empty tables and edge-free graphs compact cleanly
    on every impl."""
    for impl in ("host", *DEVICE_IMPLS):
        g = WaitFreeGraph(64, 64)
        st, _, ok = maintenance.rehash(g.state, 128, 128, impl=impl)
        assert ok
        assert int((np.asarray(st.v_key) != EMPTY_KEY).sum()) == 0
        g.apply(*initial_vertices(10))
        st2, _, ok2 = maintenance.rehash(g.state, 128, 128, impl=impl)
        assert ok2
        assert int(np.asarray(st2.v_live).sum()) == 10


def test_rehash_wrapper_escalates_capacity():
    """graph._rehash keeps its 3-arg contract and always returns a state
    whose placement the engines can locate (MAX_PROBES bound)."""
    g, oracle, _ = _build_churned(3)
    out = _rehash(g.state, g.state.v_capacity, g.state.e_capacity)
    g2 = WaitFreeGraph()
    g2.state = out
    assert g2.snapshot() == (oracle.vertices, oracle.edges)


# ---------------------------------------------------------------------------
# growth under churn: repeated mid-workload doublings on the device path
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("impl", ["host", *DEVICE_IMPLS])
@pytest.mark.parametrize("mode", ["waitfree", "fpsp"])
def test_growth_stress_mid_workload(mode, impl):
    """Tiny initial tables + key space far beyond them: every few batches
    trips another doubling while deletions keep churning incarnations.
    Oracle equivalence and snapshot/CSR consistency must hold at every
    step, for every maintenance impl."""
    # deterministic per-param seed (string hash() is salted per process —
    # a hash-derived seed would make failures unreproducible)
    seed = ["waitfree", "fpsp"].index(mode) * 3 + ["host", *DEVICE_IMPLS].index(impl)
    rng = np.random.default_rng(1000 + seed)
    g = WaitFreeGraph(32, 32, mode=mode, maintenance_impl=impl)
    oracle = SequentialGraph()
    for wave in range(4):
        lo = 60 * wave
        keys = np.arange(lo, lo + 60, dtype=np.int32)
        _apply_both(g, oracle, np.full(60, OP_ADD_VERTEX, np.int32), keys,
                    np.zeros(60, np.int32))
        kill = keys[rng.choice(60, 20, replace=False)]
        _apply_both(g, oracle, np.full(20, OP_REMOVE_VERTEX, np.int32), kill,
                    np.zeros(20, np.int32))
        eu = rng.integers(lo, lo + 60, 50).astype(np.int32)
        ev = rng.integers(0, lo + 60, 50).astype(np.int32)
        _apply_both(g, oracle, np.full(50, OP_ADD_EDGE, np.int32), eu, ev)
        # queries + snapshot stay exact right after each growth wave
        assert g.snapshot() == (oracle.vertices, oracle.edges)
        _assert_same_fields(g.traversal_csr(), build_csr(g.state), f"wave {wave}")
    assert g.state.v_capacity >= 32 * 4  # >= 2 doublings actually happened


def test_growth_seeds_delta_queue_with_snapshot_compact():
    """After a growth retry, the pre-compacted grown snapshot becomes the
    delta base and the retried batch its queue — the next query folds one
    batch instead of rebuilding."""
    g = WaitFreeGraph(64, 64, maintenance_impl="device")
    g.traversal_csr()  # prime the cache
    ops, us, vs = initial_vertices(300)  # forces growth mid-apply
    g.apply(ops, us, vs)
    assert g.state.v_capacity > 64
    assert g._csr is None and g._delta_base is not None
    assert len(g._delta_batches) == 1
    _assert_same_fields(g.traversal_csr(), build_csr(g.state), "folded")


# ---------------------------------------------------------------------------
# delta-merge: the device splice
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("impl", DEVICE_IMPLS)
def test_delta_merge_deterministic_sequence(impl):
    """The deterministic churn sequence from test_traversal, through the
    device merge: inserts, deletes, vertex removal (incident-edge
    invalidation), re-add (incarnation bump), tombstone revive."""
    g = WaitFreeGraph(64, 128, csr_maintenance="rebuild")
    o = SequentialGraph()
    seq = [(OP_ADD_VERTEX, k, 0) for k in (1, 2, 3, 4)]
    seq += [(OP_ADD_EDGE, k, k + 1) for k in (1, 2, 3)]
    ops, us, vs = (np.asarray(c, np.int32) for c in zip(*seq))
    _apply_both(g, o, ops, us, vs)
    csr = build_csr(g.state)
    batches = [
        ([OP_ADD_EDGE, OP_ADD_EDGE], [1, 4], [3, 1]),
        ([5, OP_ADD_EDGE], [1, 2], [2, 4]),       # OP_REMOVE_EDGE + insert
        ([OP_REMOVE_VERTEX], [3], [0]),
        ([OP_ADD_VERTEX, OP_ADD_EDGE], [3, 3], [0, 4]),
        ([OP_ADD_EDGE], [1], [2]),
    ]
    for i, (ops, us, vs) in enumerate(batches):
        _apply_both(g, o, ops, us, vs)
        csr = traversal.apply_delta(csr, g.state, ops, us, vs, impl=impl)
        _assert_same_fields(csr, build_csr(g.state), f"batch {i}")


@pytest.mark.parametrize("mode", ["waitfree", "fpsp"])
@pytest.mark.parametrize("seed", range(25))
def test_delta_merge_randomized_churn_matches_rebuild(mode, seed):
    """50 randomized churned graphs: the device merge chained across update
    batches stays bit-identical to a fresh rebuild, and host and device
    folds agree with each other at every step."""
    g, oracle, rng = _build_churned(seed, mode)
    csr_dev = build_csr(g.state)
    csr_host = csr_dev
    for _ in range(4):
        ops, us, vs = sample_update_batch(rng, 16, key_space=KEY_SPACE)
        _apply_both(g, oracle, ops, us, vs)
        csr_dev = traversal.apply_delta(csr_dev, g.state, ops, us, vs, impl="device")
        csr_host = traversal.apply_delta(csr_host, g.state, ops, us, vs, impl="host")
        want = build_csr(g.state)
        _assert_same_fields(csr_dev, want, "device")
        _assert_same_fields(csr_host, want, "host")
        us_q, vs_q = sample_query_pairs(rng, 16, KEY_SPACE)
        got = traversal.reachable(csr_dev, us_q, vs_q)
        exp = [oracle.reachable(int(a), int(b)) for a, b in zip(us_q, vs_q)]
        assert np.asarray(got).tolist() == exp


def test_delta_merge_via_graph_flag():
    """WaitFreeGraph(maintenance_impl=...) threads the impl through the
    lazy delta-fold path; the folded snapshot equals a rebuild."""
    for impl in DEVICE_IMPLS:
        rng = np.random.default_rng(11)
        g = WaitFreeGraph(256, 1024, maintenance_impl=impl)
        o = SequentialGraph()
        ops, us, vs = sample_batch(rng, 128, "traversal", key_space=KEY_SPACE)
        _apply_both(g, o, ops, us, vs)
        g.traversal_csr()
        for _ in range(3):
            ops, us, vs = sample_update_batch(rng, 12, key_space=KEY_SPACE)
            _apply_both(g, o, ops, us, vs)
        _assert_same_fields(g.traversal_csr(), build_csr(g.state), impl)
        assert g.snapshot() == (o.vertices, o.edges)


@pytest.mark.parametrize("seed", range(3))
def test_delta_merge_past_int32_composite_keys(seed):
    """v_cap * e_cap = 2**31: the size at which a composite ``src * Ce +
    lane`` int32 merge key overflows (and the old guard handed the fold to
    the host).  The device fold sorts by (source slot, lane) as two keys and
    stays bit-identical to a rebuild on churned graphs of that size."""
    cv, ce = 2**12, 2**19
    assert cv * ce >= 2**31
    rng = np.random.default_rng(seed)
    g = WaitFreeGraph(cv, ce, mode="fpsp", maintenance_impl="device")
    o = SequentialGraph()
    keys = rng.choice(2**30, 1500, replace=False).astype(np.int32)
    n_e = 6000
    ops = np.concatenate([np.full(keys.size, OP_ADD_VERTEX), np.full(n_e, OP_ADD_EDGE)])
    us = np.concatenate([keys, rng.choice(keys, n_e)])
    vs = np.concatenate([np.zeros(keys.size, np.int32), rng.choice(keys, n_e)])
    _apply_both(g, o, ops.astype(np.int32), us.astype(np.int32), vs.astype(np.int32))
    csr = g.traversal_csr()
    assert (g.state.v_capacity, g.state.e_capacity) == (cv, ce)
    for _ in range(3):
        n = 512
        kill = rng.choice(keys, 16, replace=False)
        ops = np.concatenate([
            np.full(16, OP_REMOVE_VERTEX),
            rng.choice([OP_ADD_EDGE, 5, OP_ADD_EDGE], n),  # 5 = OP_REMOVE_EDGE
            np.full(8, OP_ADD_VERTEX),
        ]).astype(np.int32)
        us = np.concatenate([kill, rng.choice(keys, n), kill[:8]]).astype(np.int32)
        vs = np.concatenate([np.zeros(16), rng.choice(keys, n), np.zeros(8)]).astype(np.int32)
        _apply_both(g, o, ops, us, vs)
        folded = traversal.apply_delta(csr, g.state, ops, us, vs, impl="device")
        want = build_csr(g.state)
        _assert_same_fields(folded, want, "device")
        _assert_same_fields(traversal.apply_delta(csr, g.state, ops, us, vs, impl="host"), want, "host")
        csr = folded
    assert g.snapshot() == (o.vertices, o.edges)
