"""Core layers: norm, RoPE, embeddings, attention, MLP, MoE.

Every layer is a (meta, apply) pair — see ``module.py``.  Activation layout
is (B, S, d_model); attention internals use (B, H, S, Dh).  All reductions
accumulate in f32.  Sharding: weights carry logical ("fsdp", "tp") specs;
activations get ``with_sharding_constraint`` at block boundaries (sequence
parallelism: seq dim over "model" on the residual stream).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import attention as flash_attention
from repro.kernels.flash_attention.ref import mha_chunked

from .config import ArchConfig
from .module import ParamMeta

F32 = jnp.float32


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def norm_meta(cfg: ArchConfig, d: Optional[int] = None):
    d = d or cfg.d_model
    m = {"scale": ParamMeta((d,), F32, (None,), "ones")}
    if cfg.norm == "layernorm" and cfg.norm_bias:
        m["bias"] = ParamMeta((d,), F32, (None,), "zeros")
    return m


def norm_apply(p, cfg: ArchConfig, x):
    xf = x.astype(F32)
    if cfg.norm == "rmsnorm":
        var = jnp.mean(xf * xf, axis=-1, keepdims=True)
        out = xf * jax.lax.rsqrt(var + 1e-6) * p["scale"]
    else:
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        out = (xf - mu) * jax.lax.rsqrt(var + 1e-5) * p["scale"]
        if "bias" in p:
            out = out + p["bias"]
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# rotary embeddings
# ---------------------------------------------------------------------------

def rope_apply(x, positions, theta: float):
    """x: (B, H, S, D); positions: (S,) or (B, S)."""
    B, H, S, D = x.shape
    half = D // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=F32) / half))
    if positions.ndim == 1:
        ang = positions.astype(F32)[:, None] * freqs[None, :]        # (S, half)
        ang = ang[None, None]                                        # (1,1,S,half)
    else:
        ang = positions.astype(F32)[:, None, :, None] * freqs[None, None, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half].astype(F32), x[..., half:].astype(F32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def sinusoid_embed(positions, d: int):
    """positions: (S,) int -> (S, d) sinusoidal embedding (no table)."""
    pos = positions.astype(F32)[:, None]
    dim = jnp.arange(0, d, 2, dtype=F32)[None, :]
    ang = pos / jnp.power(10_000.0, dim / d)
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# ---------------------------------------------------------------------------
# embeddings / lm head
# ---------------------------------------------------------------------------

def round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def padded_vocab(cfg: ArchConfig) -> int:
    return round_up(cfg.vocab, 128)  # TP-16 friendly for every assigned arch


def embed_meta(cfg: ArchConfig):
    vp = padded_vocab(cfg)
    m = {
        "tok": ParamMeta(
            (cfg.n_codebooks, vp, cfg.d_model) if cfg.n_codebooks > 1 else (vp, cfg.d_model),
            cfg.param_dtype,
            ((None, "tp", "fsdp") if cfg.n_codebooks > 1 else ("tp", "fsdp")),
            "embed",
            scale=0.02,
        )
    }
    if not cfg.tie_embeddings:
        m["head"] = ParamMeta(
            (cfg.n_codebooks, cfg.d_model, vp) if cfg.n_codebooks > 1 else (cfg.d_model, vp),
            cfg.param_dtype,
            ((None, "fsdp", "tp") if cfg.n_codebooks > 1 else ("fsdp", "tp")),
            "normal",
        )
    return m


def embed_apply(p, cfg: ArchConfig, tokens):
    """tokens: (B, S) int32, or (B, S, n_codebooks) for audio."""
    if cfg.n_codebooks > 1:
        # sum of per-codebook embeddings (MusicGen)
        out = jnp.zeros(tokens.shape[:2] + (cfg.d_model,), cfg.param_dtype)
        for c in range(cfg.n_codebooks):
            out = out + jnp.take(p["tok"][c], tokens[..., c], axis=0)
        return out
    return jnp.take(p["tok"], tokens, axis=0)


def logits_apply(p, cfg: ArchConfig, x, codebook: Optional[int] = None):
    """x: (B, S, d) -> (B, S, padded_vocab) (per codebook for audio)."""
    if cfg.tie_embeddings:
        w = p["tok"].astype(cfg.param_dtype)
        if cfg.n_codebooks > 1:
            w = w[codebook]
        return jnp.einsum("bsd,vd->bsv", x, w)
    w = p["head"] if cfg.n_codebooks == 1 else p["head"][codebook]
    return jnp.einsum("bsd,dv->bsv", x, w)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

def attn_meta(cfg: ArchConfig, cross: bool = False):
    d, hq, hkv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    dt = cfg.param_dtype
    m = {
        "wq": ParamMeta((d, hq * dh), dt, ("fsdp", "tp"), "normal"),
        "wk": ParamMeta((d, hkv * dh), dt, ("fsdp", "tp"), "normal"),
        "wv": ParamMeta((d, hkv * dh), dt, ("fsdp", "tp"), "normal"),
        "wo": ParamMeta((hq * dh, d), dt, ("tp", "fsdp"), "normal"),
    }
    if cfg.qkv_bias:
        m["bq"] = ParamMeta((hq * dh,), F32, ("tp",), "zeros")
        m["bk"] = ParamMeta((hkv * dh,), F32, ("tp",), "zeros")
        m["bv"] = ParamMeta((hkv * dh,), F32, ("tp",), "zeros")
    if cross:
        m["gate"] = ParamMeta((1,), F32, (None,), "zeros")  # tanh-gated (llama-3.2)
    return m


def _split_heads(x, n_heads, d_head):
    B, S, _ = x.shape
    return x.reshape(B, S, n_heads, d_head).transpose(0, 2, 1, 3)


def _decode_attention(q, k, v, valid, start=None):
    """q: (B,Hq,1,Dh); k,v: (B,Hkv,T,Dh); attend over slots < valid.

    ``start`` (B,) optionally masks slots below a per-sequence admission
    offset — the continuous-batching serving engine reuses cache slots, and
    a re-admitted sequence must not attend to its predecessor's stale KV
    rows (valid while the cache has not wrapped; the engine resets slots
    only in the unwrapped regime)."""
    B, Hq, S, Dh = q.shape
    _, Hkv, T, _ = k.shape
    g = Hq // Hkv
    qf = q.reshape(B, Hkv, g, S, Dh).astype(F32) * (Dh ** -0.5)
    s = jnp.einsum("bhgsd,bhtd->bhgst", qf, k.astype(F32))
    slot = jnp.arange(T)
    mask = slot[None, :] < jnp.broadcast_to(valid, (B,))[:, None]
    if start is not None:
        mask = mask & (slot[None, :] >= start[:, None])
    s = jnp.where(mask[:, None, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgst,bhtd->bhgsd", p, v.astype(F32))
    return out.reshape(B, Hq, S, Dh).astype(q.dtype)


def attn_apply(
    p,
    cfg: ArchConfig,
    x,                      # (B, S, d)
    *,
    positions=None,         # (S,) absolute positions (for rope)
    kv_cache=None,          # optional dict(k=(B,Hkv,T,Dh), v=..., len=())
    memory=None,            # (B, M, d) cross-attention memory
    kv_override=None,       # precomputed (k, v) heads (cross-attn decode)
    attn_impl: str = "chunked",
    block_k: int = 512,
    block_q: int = 512,
    seq_spec=None,          # (dp_axes, model_axis): seq-parallel attn layout
):
    """Returns (out, new_kv_cache or None).

    Decode caches are ring buffers of capacity T (= window for SWA archs):
    the step writes at ``len % T`` and attends over ``min(len+1, T)`` valid
    slots.  RoPE is applied pre-cache, so slot order within the ring is
    irrelevant (attention is permutation-invariant over keys).
    """
    B, S, d = x.shape
    hq, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    cross = memory is not None or kv_override is not None

    q = _split_heads(jnp.einsum("bsd,dh->bsh", x, p["wq"]), hq, dh)
    if kv_override is not None:
        k, v = kv_override
    else:
        kv_src = memory if cross else x
        k = _split_heads(jnp.einsum("bsd,dh->bsh", kv_src, p["wk"]), hkv, dh)
        v = _split_heads(jnp.einsum("bsd,dh->bsh", kv_src, p["wv"]), hkv, dh)
    if cfg.qkv_bias:
        q = q + p["bq"].reshape(hq, 1, dh).astype(q.dtype)
        if kv_override is None:
            k = k + p["bk"].reshape(hkv, 1, dh).astype(k.dtype)
            v = v + p["bv"].reshape(hkv, 1, dh).astype(v.dtype)

    if cfg.rope and not cross:
        if positions is None:
            positions = jnp.arange(S)
        q = rope_apply(q, positions, cfg.rope_theta)
        k = rope_apply(k, positions, cfg.rope_theta)

    new_cache = None
    if kv_cache is not None and kv_cache.get("collect") is not None:
        # prefill collection: full-sequence attention, but also hand the
        # projected k/v back to the caller (page writer)
        out = mha_chunked(
            q, k, v, causal=True,
            window=cfg.window, block_k=block_k,
        )
        new_cache = {"k": k, "v": v}
    elif kv_cache is not None:
        # decode (S == 1): ring-buffer append + attend over valid slots
        T = kv_cache["k"].shape[2]
        idx = kv_cache["len"]
        write = jax.lax.rem(idx, T)
        ck = jax.lax.dynamic_update_slice_in_dim(kv_cache["k"], k, write, axis=2)
        cv = jax.lax.dynamic_update_slice_in_dim(kv_cache["v"], v, write, axis=2)
        new_cache = {"k": ck, "v": cv, "len": idx + S}
        valid = jnp.minimum(idx + S, T)
        # direct masked attention: S==1 keeps memory linear, and when the
        # cache T axis is sharded over "model" the softmax reduction becomes
        # the flash-decoding partial-softmax merge (psum over "model") under
        # SPMD — no gather of the KV stripes.
        out = _decode_attention(q, ck, cv, valid, start=kv_cache.get("start"))
    else:
        causal = not cross
        if attn_impl == "kernel":
            out = flash_attention(q, k, v, causal=causal, window=cfg.window)
        else:
            out = mha_chunked(
                q, k, v, causal=causal,
                window=cfg.window if not cross else None,
                block_k=block_k, block_q=block_q,
                seq_spec=seq_spec if not cross else None,
            )

    out = out.transpose(0, 2, 1, 3).reshape(B, S, hq * dh)
    out = jnp.einsum("bsh,hd->bsd", out, p["wo"])
    if cross:
        out = out * jnp.tanh(p["gate"]).astype(out.dtype)
    return out, new_cache


# ---------------------------------------------------------------------------
# dense MLP
# ---------------------------------------------------------------------------

def mlp_meta(cfg: ArchConfig):
    d, ff, dt = cfg.d_model, cfg.d_ff, cfg.param_dtype
    if cfg.act == "swiglu":
        m = {
            "wi": ParamMeta((d, ff), dt, ("fsdp", "tp"), "normal"),
            "wg": ParamMeta((d, ff), dt, ("fsdp", "tp"), "normal"),
            "wo": ParamMeta((ff, d), dt, ("tp", "fsdp"), "normal"),
        }
    else:
        m = {
            "wi": ParamMeta((d, ff), dt, ("fsdp", "tp"), "normal"),
            "wo": ParamMeta((ff, d), dt, ("tp", "fsdp"), "normal"),
        }
    if cfg.mlp_bias:
        m["bi"] = ParamMeta((ff,), F32, ("tp",), "zeros")
        m["bo"] = ParamMeta((d,), F32, (None,), "zeros")
    return m


def mlp_apply(p, cfg: ArchConfig, x):
    h = jnp.einsum("bsd,df->bsf", x, p["wi"])
    if cfg.mlp_bias:
        h = h + p["bi"].astype(h.dtype)
    if cfg.act == "swiglu":
        g = jnp.einsum("bsd,df->bsf", x, p["wg"])
        h = jax.nn.silu(g.astype(F32)).astype(h.dtype) * h
    else:
        h = jax.nn.gelu(h.astype(F32)).astype(h.dtype)
    out = jnp.einsum("bsf,fd->bsd", h, p["wo"])
    if cfg.mlp_bias:
        out = out + p["bo"].astype(out.dtype)
    return out


# ---------------------------------------------------------------------------
# MoE (capacity-based top-k dispatch; deterministic phase-order drops)
#
# Two engines:
#   * moe_apply          — pure-jnp global dispatch (CPU smoke tests, and the
#                          oracle for the sharded path);
#   * moe_apply_shardmap — production path: token-local dispatch per data
#                          shard under shard_map.  Expert weights are
#                          FSDP-all-gathered explicitly (per layer, inside
#                          the remat'd scan body), the expert FFN contracts
#                          its TP-sharded hidden width locally, and one psum
#                          over "model" completes the block — the same
#                          collective budget as the dense TP FFN, zero
#                          cross-shard scatter traffic.  XLA's scatter
#                          sharding propagation is too weak to get there
#                          from the global formulation (measured: 300 GiB/dev
#                          temp vs 10 GiB here — see EXPERIMENTS.md §Perf).
# ---------------------------------------------------------------------------

def moe_meta(cfg: ArchConfig):
    d, dt = cfg.d_model, cfg.param_dtype
    e, ff = cfg.moe.n_experts, cfg.moe.expert_ff
    return {
        "router": ParamMeta((d, e), F32, ("fsdp", None), "normal"),
        "wi": ParamMeta((e, d, ff), dt, (None, "fsdp", "tp"), "normal"),
        "wg": ParamMeta((e, d, ff), dt, (None, "fsdp", "tp"), "normal"),
        "wo": ParamMeta((e, ff, d), dt, (None, "tp", "fsdp"), "normal"),
    }


def _moe_local(router, wi, wg, wo, cfg: ArchConfig, xt, capacity: int):
    """Dispatch + expert FFN over a token set, no collectives.

    router (d, e); wi/wg (e, d, F); wo (e, F, d); xt (T, d).
    Returns (out (T, d) — partial if F is a TP shard — probs, gate_idx).
    """
    T, d = xt.shape
    e, k = cfg.moe.n_experts, cfg.moe.top_k
    n = T * k

    logits = jnp.einsum("td,de->te", xt.astype(F32), router)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, gate_idx = jax.lax.top_k(probs, k)                    # (T, k)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    # sort (expert, phase): position within expert = sorted rank - seg start.
    # Slots are granted in token (phase) order — the graph engine's
    # deterministic combining discipline, so drops are identical on every
    # host with no coordination.
    eid = gate_idx.reshape(n)
    phase = jnp.arange(n, dtype=jnp.int32)
    order = jnp.lexsort((phase, eid))                                # (n,)
    eid_sorted = eid[order]
    rank = jnp.arange(n, dtype=jnp.int32)
    seg_start = jnp.searchsorted(eid_sorted, jnp.arange(e, dtype=eid_sorted.dtype))
    pos_sorted = rank - seg_start[eid_sorted].astype(jnp.int32)
    pos = jnp.zeros((n,), jnp.int32).at[order].set(pos_sorted)
    keep = pos < capacity

    tgt = jnp.where(keep, eid * capacity + pos, e * capacity)        # oob = drop
    src_tok = jnp.arange(n, dtype=jnp.int32) // k
    tgt = tgt.reshape(T, k)
    keep = keep.reshape(T, k)

    # inverted dispatch: scatter token *indices* (int32 — bytes, not rows),
    # then one row gather builds the expert buffer.  No (T·k, d) tensor ever
    # exists, and the gather's backward is a single scatter-add.
    slot_tok = jnp.full((e * capacity,), T, jnp.int32)               # T -> zero row
    slot_tok = slot_tok.at[tgt.reshape(n)].set(src_tok, mode="drop")
    xtp = jnp.concatenate([xt, jnp.zeros((1, d), xt.dtype)], axis=0)
    buf = xtp[slot_tok].reshape(e, capacity, d)

    h = jnp.einsum("ecd,edf->ecf", buf, wi)
    g = jnp.einsum("ecd,edf->ecf", buf, wg)
    h = jax.nn.silu(g.astype(F32)).astype(h.dtype) * h
    out_buf = jnp.einsum("ecf,efd->ecd", h, wo).reshape(e * capacity, d)

    # combine: every slot belongs to exactly one (token, top-k) pair, so the
    # gate weight lives on the slot and one scatter-add per MoE layer maps
    # slots back to tokens (backward = one gather; no (T·k, d) cotangents).
    slot_w = jnp.zeros((e * capacity,), F32)
    slot_w = slot_w.at[tgt.reshape(n)].set(
        (gate_vals * keep).reshape(n), mode="drop"
    )
    weighted = out_buf * slot_w[:, None].astype(out_buf.dtype)
    # bf16 accumulation is safe here: each token row sums at most top_k slot
    # rows — and it keeps the scatter-add cotangent chain out of f32.
    out = jnp.zeros((T + 1, d), xt.dtype)
    out = out.at[slot_tok].add(weighted)
    return out[:T], probs, gate_idx


def _moe_aux(probs, gate_idx, e):
    """Switch load-balancing loss from (possibly local) routing stats."""
    me = probs.mean(axis=0)
    ce = jax.nn.one_hot(gate_idx[:, 0], e, dtype=F32).mean(axis=0)
    return e * jnp.sum(me * ce)


def moe_apply_shardmap(p, cfg: ArchConfig, x, *, dp_axes=("data",),
                       capacity: Optional[int] = None):
    """Production MoE: token-local dispatch per data shard (see header)."""
    from jax.sharding import PartitionSpec as P

    B, S, d = x.shape
    e, k = cfg.moe.n_experts, cfg.moe.top_k
    fsdp = dp_axes if len(dp_axes) > 1 else dp_axes[0]
    mesh = jax.sharding.get_abstract_mesh()
    n_dp = 1
    for a in dp_axes:
        n_dp *= mesh.shape[a]
    T_local = (B // n_dp) * S
    cap = capacity or max(int(cfg.moe.capacity_factor * k * T_local / e), 1)

    def body(xb, router, wi, wg, wo):
        # gather the FSDP shards of the expert weights (per layer, inside
        # the remat scope — re-gathered on the backward pass)
        router = jax.lax.all_gather(router, dp_axes, axis=0, tiled=True)
        wi = jax.lax.all_gather(wi, dp_axes, axis=1, tiled=True)
        wg = jax.lax.all_gather(wg, dp_axes, axis=1, tiled=True)
        wo = jax.lax.all_gather(wo, dp_axes, axis=2, tiled=True)

        Bl, Sl, dl = xb.shape
        out, probs, gate_idx = _moe_local(
            router, wi, wg, wo, cfg, xb.reshape(Bl * Sl, dl), cap
        )
        # complete the TP contraction and average the aux stats
        out = jax.lax.psum(out.astype(F32), "model").astype(xb.dtype)
        aux = _moe_aux(probs, gate_idx, e)
        aux = jax.lax.pmean(aux, dp_axes)
        return out.reshape(Bl, Sl, dl), aux

    fn = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(dp_axes, None, None),
            P(fsdp, None),
            P(None, fsdp, "model"),
            P(None, fsdp, "model"),
            P(None, "model", fsdp),
        ),
        out_specs=(P(dp_axes, None, None), P()),
    )
    return fn(x, p["router"], p["wi"], p["wg"], p["wo"])


def moe_apply(p, cfg: ArchConfig, x, *, capacity: Optional[int] = None):
    """Global-dispatch MoE (single-device / oracle path).

    Token->expert assignment is a batched add-edge workload resolved exactly
    like the graph engine resolves conflicting ops (DESIGN.md §3): sort the
    (expert, phase) pairs, a segmented position count grants capacity slots
    in phase (= token) order, losers are dropped deterministically.
    Returns (out, aux_loss).
    """
    B, S, d = x.shape
    e, k = cfg.moe.n_experts, cfg.moe.top_k
    T = B * S
    if capacity is None:
        capacity = int(cfg.moe.capacity_factor * k * T / e) or 1
    out, probs, gate_idx = _moe_local(
        p["router"], p["wi"], p["wg"], p["wo"], cfg, x.reshape(T, d), capacity
    )
    return out.reshape(B, S, d), _moe_aux(probs, gate_idx, e)
