"""Per-arch smoke-scale step timings on CPU.

Not a TPU performance claim (CPU backend; the roofline tables are the perf
deliverable) — this is the harness that proves every assigned architecture's
train and decode step *runs*, and tracks relative regressions across code
changes.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_NAMES, get_smoke_config
from repro.launch.mesh import make_mesh
from repro.launch.steps import build_decode_step, build_train_step
from repro.models import LM
from repro.optim import adamw_init


def bench_arch(name: str, steps: int = 3):
    with jax.set_mesh(make_mesh((1, 1), ("data", "model"))):
        return _bench_arch(name, steps)


def _bench_arch(name: str, steps: int = 3):
    cfg = get_smoke_config(name)
    model = LM(cfg)
    params = model.init(jax.random.key(0))
    B, S = 4, 32

    # train
    train_step, _, _ = build_train_step(cfg, multi_pod=False, accum=1)
    opt = adamw_init(params)
    rng = np.random.default_rng(0)
    tok_shape = (B, S) if cfg.n_codebooks == 1 else (B, S, cfg.n_codebooks)
    batch = {
        "tokens": jnp.asarray(rng.integers(0, cfg.vocab, tok_shape), jnp.int32),
        "targets": jnp.asarray(rng.integers(0, cfg.vocab, tok_shape), jnp.int32),
        "mask": jnp.ones((B, S), jnp.float32),
    }
    if cfg.xattn_every:
        batch["memory"] = jnp.zeros((B, cfg.n_img_tokens, cfg.d_model), cfg.param_dtype)
    jitted = jax.jit(train_step)
    out = jitted(params, opt, batch)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(steps):
        out = jitted(params, opt, batch)
    jax.block_until_ready(out)
    train_us = 1e6 * (time.perf_counter() - t0) / steps

    # decode
    decode_step, _, _ = build_decode_step(cfg, multi_pod=False)
    cache = model.decode_init(B, S, params=params)
    tok1 = (B, 1) if cfg.n_codebooks == 1 else (B, 1, cfg.n_codebooks)
    tok = jnp.asarray(rng.integers(0, cfg.vocab, tok1), jnp.int32)
    kwargs = {}
    if cfg.xattn_every:
        kwargs["memory"] = batch["memory"]
    jd = jax.jit(decode_step)
    out = jd(params, tok, cache, **kwargs)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(steps):
        out = jd(params, tok, cache, **kwargs)
    jax.block_until_ready(out)
    decode_us = 1e6 * (time.perf_counter() - t0) / steps
    return train_us, decode_us


def main(quick: bool = False):
    archs = ARCH_NAMES[:3] if quick else ARCH_NAMES
    print("bench,arch,train_us,decode_us")
    rows = []
    for name in archs:
        tr, de = bench_arch(name)
        print(f"lm_step,{name},{tr:.0f},{de:.0f}")
        rows.append((name, tr, de))
    return rows


if __name__ == "__main__":
    main()
