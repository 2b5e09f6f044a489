"""Per-kernel sweeps: shapes × dtypes, interpret-mode vs pure-jnp oracle."""

import numpy as np
import pytest
import jax.numpy as jnp

from repro.kernels.compact import (
    masked_compact,
    masked_compact_xla,
    probe_place,
    probe_place_xla,
)
from repro.kernels.flash_attention import attention, mha_chunked, mha_reference
from repro.kernels.frontier import frontier_expand, frontier_expand_xla
from repro.kernels.hash_probe import hash_probe, hash_probe_reference
from repro.kernels.paged_attention import paged_attention, paged_attention_reference
from repro.kernels.ssd_scan import (
    linear_scan_chunked,
    linear_scan_reference,
    linear_scan_step,
    ssd_scan,
)

ATOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}
RTOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


def _rand(rng, shape, dtype):
    return jnp.asarray(rng.standard_normal(shape), jnp.float32).astype(dtype)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,Hq,Hkv,Sq,Sk,D,causal,window",
    [
        (1, 2, 2, 32, 32, 16, True, None),     # MHA causal
        (2, 4, 2, 64, 64, 32, True, None),     # GQA
        (1, 8, 1, 32, 32, 64, True, None),     # MQA
        (2, 4, 2, 64, 64, 32, True, 16),       # sliding window
        (1, 2, 2, 16, 48, 32, False, None),    # cross (Sq != Sk, no causal)
        (1, 2, 2, 32, 40, 16, True, None),     # non-multiple Sk (padding)
    ],
)
def test_flash_attention_sweep(B, Hq, Hkv, Sq, Sk, D, causal, window, dtype):
    rng = np.random.default_rng(hash((B, Hq, Sq, Sk, D, causal, str(window))) % 2**32)
    q = _rand(rng, (B, Hq, Sq, D), dtype)
    k = _rand(rng, (B, Hkv, Sk, D), dtype)
    v = _rand(rng, (B, Hkv, Sk, D), dtype)
    ref = mha_reference(q, k, v, causal=causal, window=window)
    got = attention(
        q, k, v, causal=causal, window=window,
        impl="kernel_interpret", block_q=16, block_k=16,
    )
    np.testing.assert_allclose(
        got.astype(jnp.float32), ref.astype(jnp.float32),
        atol=ATOL[dtype], rtol=RTOL[dtype],
    )


def test_chunked_matches_reference_large_window():
    rng = np.random.default_rng(0)
    q = _rand(rng, (1, 4, 128, 32), jnp.float32)
    k = _rand(rng, (1, 2, 128, 32), jnp.float32)
    v = _rand(rng, (1, 2, 128, 32), jnp.float32)
    for window in (None, 32, 100):
        ref = mha_reference(q, k, v, causal=True, window=window)
        got = mha_chunked(q, k, v, causal=True, window=window, block_k=32)
        np.testing.assert_allclose(got, ref, atol=2e-5, rtol=2e-5)


def test_chunked_decode_offset():
    """Decode: Sq=1 positioned at the cache tail via q_offset."""
    rng = np.random.default_rng(1)
    k = _rand(rng, (2, 2, 64, 16), jnp.float32)
    v = _rand(rng, (2, 2, 64, 16), jnp.float32)
    qfull = _rand(rng, (2, 2, 64, 16), jnp.float32)
    ref = mha_reference(qfull, k, v, causal=True)
    got = mha_chunked(qfull[:, :, -1:], k, v, causal=True, q_offset=63, block_k=16)
    np.testing.assert_allclose(got[:, :, 0], ref[:, :, -1], atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# paged attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,Hq,Hkv,D,P,page,ppseq",
    [
        (2, 4, 4, 16, 8, 8, 2),     # MHA
        (3, 8, 2, 32, 16, 8, 4),    # GQA
        (1, 12, 1, 64, 8, 16, 3),   # MQA, larger pages
    ],
)
def test_paged_attention_sweep(B, Hq, Hkv, D, P, page, ppseq, dtype):
    rng = np.random.default_rng(hash((B, Hq, Hkv, D, P, page, ppseq)) % 2**32)
    q = _rand(rng, (B, Hq, D), dtype)
    kp = _rand(rng, (P, page, Hkv, D), dtype)
    vp = _rand(rng, (P, page, Hkv, D), dtype)
    bt = jnp.asarray(
        rng.choice(P, size=(B, ppseq), replace=False if B * ppseq <= P else True)
        .astype(np.int32)
    )
    sl = jnp.asarray(rng.integers(1, page * ppseq + 1, size=(B,)).astype(np.int32))
    ref = paged_attention_reference(q, kp, vp, bt, sl)
    got = paged_attention(q, kp, vp, bt, sl, impl="kernel_interpret")
    np.testing.assert_allclose(
        got.astype(jnp.float32), ref.astype(jnp.float32),
        atol=ATOL[dtype], rtol=RTOL[dtype],
    )


# ---------------------------------------------------------------------------
# ssd / gated linear attention scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,H,S,K,V,chunk,scalar",
    [
        (1, 2, 64, 8, 8, 16, False),
        (2, 3, 128, 16, 24, 32, False),
        (2, 2, 128, 32, 32, 64, True),     # Mamba-2 scalar-decay MXU path
        (1, 1, 256, 64, 64, 64, False),    # RWKV-ish head dims
    ],
)
def test_ssd_scan_sweep(B, H, S, K, V, chunk, scalar, dtype):
    rng = np.random.default_rng(hash((B, H, S, K, V, chunk, scalar)) % 2**32)
    q = _rand(rng, (B, H, S, K), dtype) * 0.5
    k = _rand(rng, (B, H, S, K), dtype) * 0.5
    v = _rand(rng, (B, H, S, V), dtype) * 0.5
    if scalar:
        w = jnp.broadcast_to(
            jnp.asarray(rng.uniform(0.05, 1.0, (B, H, S, 1)), jnp.float32), (B, H, S, K)
        ).astype(dtype)
    else:
        w = jnp.asarray(rng.uniform(0.01, 1.0, (B, H, S, K)), jnp.float32).astype(dtype)
    ref, _ = linear_scan_reference(q, k, v, w)
    got = ssd_scan(q, k, v, w, chunk=chunk, scalar_decay=scalar, impl="kernel_interpret")
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(
        got.astype(jnp.float32), ref.astype(jnp.float32), atol=tol, rtol=tol
    )


def test_ssd_chunked_final_state_feeds_decode():
    """Train-to-serve continuity: chunked final state == reference, and the
    O(1) decode step continues it exactly."""
    rng = np.random.default_rng(5)
    B, H, S, K, V = 1, 2, 64, 8, 8
    q = _rand(rng, (B, H, S + 1, K), jnp.float32)
    k = _rand(rng, (B, H, S + 1, K), jnp.float32)
    v = _rand(rng, (B, H, S + 1, V), jnp.float32)
    w = jnp.asarray(rng.uniform(0.1, 1.0, (B, H, S + 1, K)), jnp.float32)

    full, _ = linear_scan_reference(q, k, v, w)
    _, h = linear_scan_chunked(q[:, :, :S], k[:, :, :S], v[:, :, :S], w[:, :, :S], chunk=16)
    y, _ = linear_scan_step(q[:, :, S], k[:, :, S], v[:, :, S], w[:, :, S], h)
    np.testing.assert_allclose(y, full[:, :, S], atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# hash probe
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cap,n", [(64, 16), (256, 64), (1024, 256)])
def test_hash_probe_sweep(cap, n):
    rng = np.random.default_rng(cap * 31 + n)
    # build a table via the engine's own claim path for realism
    from repro.core.locate import claim_vertex_slots
    from repro.core.types import EMPTY_KEY

    table = jnp.full((cap,), EMPTY_KEY, jnp.int32)
    present = jnp.asarray(
        rng.choice(10_000, size=cap // 4, replace=False).astype(np.int32)
    )
    table, _, over, _ = claim_vertex_slots(table, present, jnp.ones((cap // 4,), bool))
    assert not bool(over)

    # queries: half present, half absent
    absent = jnp.asarray((10_000 + rng.integers(0, 1000, n // 2)).astype(np.int32))
    queries = jnp.concatenate([present[: n - n // 2], absent])

    f_ref, e_ref = hash_probe_reference(table, queries)
    f_ker, e_ker = hash_probe(table, queries, impl="kernel_interpret")
    np.testing.assert_array_equal(f_ker, f_ref)
    np.testing.assert_array_equal(e_ker, e_ref)
    # semantic check: every present query found, every absent one got an
    # insert candidate
    f = np.asarray(f_ref)
    assert (f[: n - n // 2] >= 0).all()
    assert (f[n - n // 2:] == -1).all()
    assert (np.asarray(e_ref)[n - n // 2:] >= 0).all()


# ---------------------------------------------------------------------------
# frontier expansion (BFS level step; deep coverage in test_frontier_kernel.py)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("S,C,Ce", [(4, 64, 256), (8, 130, 1024), (16, 512, 4096)])
def test_frontier_expand_sweep(S, C, Ce):
    rng = np.random.default_rng(S * 131 + C * 7 + Ce)
    frontier = jnp.asarray(rng.random((S, C)) < 0.2)
    src = jnp.asarray(rng.integers(0, C, Ce).astype(np.int32))
    dst = jnp.asarray(rng.integers(0, C, Ce).astype(np.int32))
    ref = frontier_expand_xla(frontier, src, dst)
    got = frontier_expand(frontier, src, dst, impl="kernel_interpret")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


# ---------------------------------------------------------------------------
# compaction primitives (state maintenance; deep coverage in
# test_maintenance.py — these sweep the raw kernels vs the XLA paths)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("R,N,density", [(1, 64, 0.5), (3, 1000, 0.2), (6, 4096, 0.8)])
def test_masked_compact_sweep(R, N, density):
    rng = np.random.default_rng(R * 17 + N)
    vals = jnp.asarray(rng.integers(-5, 1000, (R, N)).astype(np.int32))
    mask = jnp.asarray(rng.random(N) < density)
    ref, n_ref = masked_compact_xla(vals, mask, fill=-1)
    got, n_got = masked_compact(vals, mask, fill=-1, impl="kernel_interpret")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    assert int(n_got) == int(n_ref) == int(np.asarray(mask).sum())
    # semantic: survivors in lane order, fill tail
    np.testing.assert_array_equal(
        np.asarray(ref)[:, : int(n_ref)], np.asarray(vals)[:, np.asarray(mask)]
    )
    assert (np.asarray(ref)[:, int(n_ref):] == -1).all()


@pytest.mark.parametrize("cap,n,max_probes", [(64, 16, 32), (256, 100, 32), (1024, 500, 32)])
def test_probe_place_sweep(cap, n, max_probes):
    from repro.core.hashing import hash_vertex

    rng = np.random.default_rng(cap + n)
    keys = jnp.asarray(rng.choice(100_000, n, replace=False).astype(np.int32))
    home = hash_vertex(keys, cap)
    active = jnp.asarray(rng.random(n) < 0.9)
    s_ref, o_ref = probe_place_xla(home, active, capacity=cap, max_probes=max_probes)
    s_got, o_got = probe_place(
        home, active, capacity=cap, max_probes=max_probes, impl="kernel_interpret"
    )
    np.testing.assert_array_equal(np.asarray(s_got), np.asarray(s_ref))
    assert bool(o_got) == bool(o_ref) is False
    s = np.asarray(s_ref)
    a = np.asarray(active)
    assert (s[~a] == -1).all() and (s[a] >= 0).all()
    assert len(set(s[a].tolist())) == int(a.sum())  # distinct slots
    # wait-free locate invariant: no empty slot strictly earlier on a
    # placed key's own probe chain (else the engines' locate would stop
    # at the gap and miss the key)
    occ = np.zeros(cap, bool)
    occ[s[a]] = True
    hm = np.asarray(home)
    for i in np.flatnonzero(a):
        for step in range(max_probes):
            slot = (hm[i] + step * (step + 1) // 2) & (cap - 1)
            if slot == s[i]:
                break
            assert occ[slot], (i, step)


def test_probe_slot_replica_pins_hashing():
    """compact.xla keeps a local probe_slot replica (kernel families are
    import-free of repro.core); it must stay bit-identical to the real one."""
    from repro.core.hashing import probe_slot
    from repro.kernels.compact.xla import _probe_slot

    home = jnp.asarray(np.arange(0, 512, 7, dtype=np.int32) % 256)
    for step in (0, 1, 5, 31):
        np.testing.assert_array_equal(
            np.asarray(_probe_slot(home, jnp.int32(step), 256)),
            np.asarray(probe_slot(home, jnp.int32(step), 256)),
        )


def test_probe_place_overflow_is_flagged():
    """Chains capped below what placement needs: both impls agree on the
    overflow verdict (the signal that makes the caller grow further)."""
    from repro.core.hashing import hash_vertex

    keys = jnp.asarray(np.arange(40, dtype=np.int32))
    home = hash_vertex(keys, 32)
    active = jnp.ones(40, bool)
    _, o_ref = probe_place_xla(home, active, capacity=32, max_probes=2)
    _, o_got = probe_place(home, active, capacity=32, max_probes=2, impl="kernel_interpret")
    assert bool(o_ref) and bool(o_got)
