"""Three-term roofline per (arch × shape × mesh) from the compiled dry-run.

Terms (per-device program, so per-chip peak rates from :data:`PEAKS`,
looked up by the device kind the cells were compiled for):

  compute_s    = exec_flops / flops              (bf16 MXU peak per chip)
  memory_s     = exec_bytes / hbm_bw             (HBM bandwidth per chip)
  collective_s = Σ_site ring_bytes(site) / link_bw (ICI per link)

``exec_*`` are execution-weighted totals from ``repro.launch.hloparse``
(while bodies × known trip count — raw ``cost_analysis`` counts each body
once; see tests/test_hloparse.py).  Collective seconds model a
bidirectional-ring schedule per site:

  all-gather      (g-1)/g × result_bytes        (result = gathered array)
  reduce-scatter  (g-1)   × result_bytes        (result = one shard)
  all-reduce      2(g-1)/g × result_bytes       (RS + AG)
  all-to-all      (g-1)/g × result_bytes
  collective-permute      1 × result_bytes

MODEL_FLOPS (the "useful" flops): 6·N_active·D for training, 2·N_active·D
for prefill, 2·N_active·B per decode step — N_active excludes embedding
tables and counts each MoE expert at top_k/n_experts utilisation; an
attention term (12·L_attn·H·hd·S_eff train / 4·…·fwd-only) is added since
6ND ignores it and it is material at 32k.  The ratio
MODEL_FLOPS / (chips × exec_flops) exposes remat/redundancy waste.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
from typing import Dict, List, Optional

# Published per-chip peaks, keyed by ``jax.Device.device_kind``.  Source:
# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB of HBM at
# 819 GB/s, 1,600 Gbit/s of inter-chip interconnect (four 50 GB/s links).
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9, "link_bw": 50e9},
}

# the dry-run compiles its cells for a v5e pod (launch/dryrun.py)
DRYRUN_DEVICE_KIND = "TPU v5 lite"


def peaks(device_kind: str) -> Dict[str, float]:
    """Peak rates of one chip of ``device_kind``; an unknown kind is an
    error, never a default."""
    if device_kind not in PEAKS:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r} "
            f"(known: {sorted(PEAKS)})"
        )
    return PEAKS[device_kind]

RING_FACTOR = {
    "all-gather": lambda g: (g - 1) / g,
    "reduce-scatter": lambda g: float(g - 1),   # result = one shard
    "all-reduce": lambda g: 2 * (g - 1) / g,
    "all-to-all": lambda g: (g - 1) / g,
    "collective-permute": lambda g: 1.0,
}


# ---------------------------------------------------------------------------
# analytic model flops
# ---------------------------------------------------------------------------

def _param_split(cfg):
    """(N_total, N_embed, N_expert_total) from the LM meta tree (no alloc)."""
    import jax
    from repro.models import LM
    from repro.models.module import is_meta

    model = LM(cfg)
    meta = model.meta()
    n_total = n_embed = n_expert = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(
        meta, is_leaf=is_meta
    )[0]:
        keys = [str(getattr(p, "key", getattr(p, "idx", p))) for p in path]
        n = 1
        for d in leaf.shape:
            n *= d
        n_total += n
        if keys and keys[0] == "embed":
            n_embed += n
        if (
            cfg.moe is not None
            and "ffn" in keys
            and leaf.shape
            and leaf.shape[-1 if "router" in keys else 0] == cfg.moe.n_experts
        ):
            if "router" not in keys:
                n_expert += n
    return n_total, n_embed, n_expert


def model_flops(cfg, shape: Dict, kind: str) -> float:
    """Global useful flops for one step of this cell."""
    B, S = shape["global_batch"], shape["seq_len"]
    n_total, n_embed, n_expert = _param_split(cfg)
    n_active = n_total - n_embed - n_expert
    if cfg.moe is not None and n_expert:
        n_active += n_expert * cfg.moe.top_k / cfg.moe.n_experts

    d_logits = 2 * cfg.d_model * cfg.vocab * cfg.n_codebooks

    # attention context term
    if cfg.n_heads:
        l_attn = cfg.n_layers
        if cfg.shared_attn_every:
            l_attn = cfg.n_layers // cfg.shared_attn_every
        hq = cfg.n_heads * cfg.head_dim
        s_eff = S / 2 if cfg.window is None else min(S / 2, cfg.window)
        attn_tok = 4 * l_attn * hq * s_eff   # fwd qk^T + att·v per token
        if cfg.xattn_every:
            attn_tok += 4 * (cfg.n_layers // cfg.xattn_every) * hq * cfg.n_img_tokens
    else:
        attn_tok = 0.0

    if kind == "train":
        tok = B * S
        return tok * (6 * n_active + 3 * d_logits + 3 * attn_tok)
    if kind == "prefill":
        tok = B * S
        return tok * (2 * n_active + 2 * attn_tok) + B * d_logits
    # decode: one token per sequence; attends to the whole cache (or window)
    s_ctx = S if cfg.window is None else min(S, cfg.window)
    if cfg.n_heads:
        l_attn = cfg.n_layers
        if cfg.shared_attn_every:
            l_attn = cfg.n_layers // cfg.shared_attn_every
        attn_dec = 4 * l_attn * cfg.n_heads * cfg.head_dim * s_ctx
        if cfg.xattn_every:
            attn_dec += (4 * (cfg.n_layers // cfg.xattn_every)
                         * cfg.n_heads * cfg.head_dim * cfg.n_img_tokens)
    else:
        attn_dec = 0.0
    return B * (2 * n_active + d_logits + attn_dec)


def analytic_memory_bytes(cfg, shape: Dict, kind: str, n_dev: int,
                          *, accum: int = 1) -> Dict[str, float]:
    """Per-device HBM traffic for one step on the TPU *target*.

    Why not HLO bytes alone: the CPU-backend HLO materializes chunked
    attention scores and unfused elementwise chains that the TPU build keeps
    in VMEM (flash_attention / ssd_scan Pallas kernels, fused adds) — its
    byte count is a fusion-pessimistic bound, reported separately.  This
    model counts what a tuned TPU program must actually move:

      params     3×P/tp train (fwd+bwd+remat re-read) | 1×P/tp inference
      grads      2×P/tp (write + reduce-scatter read)
      optimizer  30×N/n_dev f32 m/v/master read+write + bf16 param write
      acts       k_act × tokens_dev × d × a per layer
                 (k_act: fwd 12, +bwd 24, +remat 12 re-materialised reads)
      attention  flash: QKVO once + KV re-read per 128-row q block
      decode     whole resident KV (or SSM state) read per emitted token
      logits     chunked xent: hidden + vocab-shard weights + chunk logits
    """
    B, S = shape["global_batch"], shape["seq_len"]
    a = 2  # bf16
    tp = 16
    dp = n_dev // tp
    n_total, n_embed, n_expert = _param_split(cfg)
    p_bytes = n_total * a
    tokens_dev = B * S / dp
    d = cfg.d_model
    out = {}

    if kind in ("train", "prefill"):
        train = kind == "train"
        out["params"] = (3 if train else 1) * p_bytes / tp
        if train:
            out["grads"] = 2 * p_bytes / tp
            out["optimizer"] = 30 * n_total / n_dev
        k_act = 48 if train else 12
        out["acts"] = k_act * tokens_dev * d * a * cfg.n_layers
        if cfg.n_heads:
            l_attn = cfg.n_layers
            if cfg.shared_attn_every:
                l_attn = cfg.n_layers // cfg.shared_attn_every
            s_eff = S / 2 if cfg.window is None else min(S / 2, cfg.window)
            hq, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
            qkvo = (2 * hq + 2 * hkv) * tokens_dev * hd * a / tp * l_attn
            kv_reread = (
                2 * (tokens_dev / 128) * s_eff * (hkv / min(hkv, tp)) * hd * a
                * l_attn
            )
            out["attention"] = (3 if train else 1) * (qkvo + kv_reread)
        vp = cfg.vocab * cfg.n_codebooks
        if train:
            out["logits"] = (
                4 * tokens_dev * vp * a / tp / 8   # chunk-resident logits
                + 2 * d * vp * a / tp              # vocab-shard weights
            )
        else:
            out["logits"] = 2 * d * vp * a / tp    # last-token only
    else:  # decode
        out["params"] = p_bytes / tp  # every weight read once per token
        if cfg.n_heads:
            l_attn = cfg.n_layers
            if cfg.shared_attn_every:
                l_attn = cfg.n_layers // cfg.shared_attn_every
            s_ctx = S if cfg.window is None else min(S, cfg.window)
            cache = (
                l_attn * 2 * cfg.n_kv_heads * cfg.head_dim * s_ctx * B * a
            ) / n_dev
            out["kv_cache"] = cache
        if cfg.ssm is not None:
            heads = d // cfg.ssm.head_dim
            state = cfg.n_layers * B * heads * cfg.ssm.head_dim * cfg.ssm.state * 4
            out["ssm_state"] = 2 * state / n_dev
        out["acts"] = 24 * (B / dp) * d * a * cfg.n_layers
        out["logits"] = d * cfg.vocab * cfg.n_codebooks * a / tp
    out["total"] = sum(out.values())
    return out


def model_flops_6nd(cfg, shape: Dict, kind: str) -> float:
    """The spec's bare convention: 6·N·D (train) / 2·N·D (inference)."""
    B, S = shape["global_batch"], shape["seq_len"]
    n_total, n_embed, n_expert = _param_split(cfg)
    n = n_total - n_embed - n_expert
    if cfg.moe is not None and n_expert:
        n += n_expert * cfg.moe.top_k / cfg.moe.n_experts
    tok = B * S if kind in ("train", "prefill") else B
    return (6 if kind == "train" else 2) * n * tok


# ---------------------------------------------------------------------------
# per-cell roofline
# ---------------------------------------------------------------------------

def collective_seconds(exec_sum: Dict, link_bw: float) -> float:
    """Ring-model seconds over the per-link bandwidth."""
    sites = exec_sum.get("collective_sites") or []
    if sites:
        total = 0.0
        for s in sites:
            f = RING_FACTOR.get(s["kind"], lambda g: 1.0)(max(int(s["group"]), 1))
            total += s["bytes"] * s["mult"] * f
        return total / link_bw
    # fallback: raw sum (no group info)
    return sum(exec_sum.get("collective_bytes", {}).values()) / link_bw


def cell_roofline(rec: Dict) -> Optional[Dict]:
    if rec.get("status") != "ok":
        return None
    peak = peaks(DRYRUN_DEVICE_KIND)
    from repro.configs import SHAPES, get_config

    cfg = get_config(rec["arch"])
    shape = SHAPES[rec["shape"]]
    ex = rec["exec"]
    n_dev = rec["n_devices"]

    compute_s = ex["flops"] / peak["flops"]
    memory_hlo_s = ex["bytes"] / peak["hbm_bw"]
    mem = analytic_memory_bytes(cfg, shape, shape["kind"], n_dev)
    memory_s = mem["total"] / peak["hbm_bw"]
    coll_s = collective_seconds(ex, peak["link_bw"])
    coll_raw_s = sum(ex.get("collective_bytes", {}).values()) / peak["link_bw"]

    mf = model_flops(cfg, shape, shape["kind"])
    mf6 = model_flops_6nd(cfg, shape, shape["kind"])
    useful = mf / (n_dev * ex["flops"]) if ex["flops"] else 0.0

    # bound/step estimate uses the analytic (TPU-fusion-aware) memory term;
    # the raw-HLO bytes term is reported alongside as the fusion-pessimistic
    # bound (CPU HLO materializes what the Pallas kernels keep in VMEM).
    terms = {"compute": compute_s, "memory": memory_s, "collective": coll_s}
    bound = max(terms, key=terms.get)
    step_s = max(terms.values())
    mfu = (mf / (n_dev * peak["flops"])) / step_s if step_s else 0.0

    return {
        "arch": rec["arch"],
        "shape": rec["shape"],
        "mesh": "2x16x16" if rec["multi_pod"] else "16x16",
        "n_devices": n_dev,
        "compute_s": compute_s,
        "memory_s": memory_s,
        "memory_hlo_s": memory_hlo_s,
        "memory_parts": mem,
        "collective_s": coll_s,
        "collective_raw_s": coll_raw_s,
        "bound": bound,
        "model_flops": mf,
        "model_flops_6nd": mf6,
        "useful_ratio": useful,
        "roofline_frac": mfu,
        "hbm_gib_per_dev": (
            rec["memory"]["argument_bytes"] + rec["memory"]["temp_bytes"]
        ) / 2**30,
    }


_NOTES = {
    "compute": "compute-bound: raise useful-ratio (less remat recompute, fuse "
               "elementwise chains into the matmuls)",
    "memory": "HBM-bound: cut activation traffic (better remat policy, bf16 "
              "intermediates, larger fusion windows)",
    "collective": "ICI-bound: reshard to shrink per-layer gathers "
                  "(FSDP axis size, sequence-sharded activations, overlap "
                  "reduce-scatter with backward)",
}


def note_for(row: Dict) -> str:
    return _NOTES[row["bound"]]


def load_all(dirpath: str) -> List[Dict]:
    rows = []
    for p in sorted(glob.glob(os.path.join(dirpath, "*.json"))):
        with open(p) as f:
            rec = json.load(f)
        row = cell_roofline(rec)
        if row is not None:
            rows.append(row)
    return rows


def fmt_table(rows: List[Dict], mesh: str = "16x16") -> str:
    out = [
        "| arch | shape | compute_s | memory_s | collective_s | bound | "
        "useful | roofline_frac |",
        "|---|---|---|---|---|---|---|---|",
    ]
    for r in rows:
        if r["mesh"] != mesh:
            continue
        out.append(
            f"| {r['arch']} | {r['shape']} | {r['compute_s']:.4f} | "
            f"{r['memory_s']:.4f} | {r['collective_s']:.4f} | {r['bound']} | "
            f"{r['useful_ratio']:.2f} | {r['roofline_frac']:.3f} |"
        )
    return "\n".join(out)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dir", default="results/dryrun")
    ap.add_argument("--mesh", default="16x16")
    ap.add_argument("--markdown", action="store_true")
    args = ap.parse_args()
    rows = load_all(args.dir)
    if args.markdown:
        print(fmt_table(rows, args.mesh))
        return rows
    print("bench,arch,shape,mesh,compute_s,memory_s,collective_s,bound,"
          "useful_ratio,roofline_frac")
    for r in rows:
        print(
            f"roofline,{r['arch']},{r['shape']},{r['mesh']},"
            f"{r['compute_s']:.5f},{r['memory_s']:.5f},{r['collective_s']:.5f},"
            f"{r['bound']},{r['useful_ratio']:.3f},{r['roofline_frac']:.4f}"
        )
    return rows


if __name__ == "__main__":
    main()
