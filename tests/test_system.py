"""System-level integration: train loop learns, serve consumes trained
params, step builders lower for every shape kind, run-dict knobs hold."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.data import DataConfig, SyntheticTokenStream
from repro.launch.mesh import make_mesh
from repro.launch.steps import build_prefill_step, build_train_step
from repro.launch.train import TrainRunner
from repro.models import LM
from repro.models.config import ArchConfig
from repro.serving import Request, ServingEngine

TINY = ArchConfig(
    name="sys-tiny", family="dense", n_layers=2, d_model=64, n_heads=4,
    n_kv_heads=2, d_ff=128, vocab=256, dtype="float32",
)


def test_train_loss_decreases():
    mesh = make_mesh((1, 1), ("data", "model"))
    runner = TrainRunner(TINY, mesh, ckpt_dir=None, batch=8, seq=32)
    runner.init_or_restore()
    losses = runner.train(30, log_every=5, save_every=0, log=lambda *a: None)
    first, last = losses[0][1], losses[-1][1]
    assert np.isfinite(first) and np.isfinite(last)
    assert last < first, (first, last)


def test_train_then_serve():
    """The whole lifecycle: train params, hand them to the serving engine."""
    mesh = make_mesh((1, 1), ("data", "model"))
    runner = TrainRunner(TINY, mesh, ckpt_dir=None, batch=4, seq=32)
    runner.init_or_restore()
    runner.train(3, log_every=10, save_every=0, log=lambda *a: None)

    eng = ServingEngine(TINY, runner.params, max_batch=2, max_len=48,
                        page_size=8)
    eng.submit(Request(id=0, prompt=np.arange(1, 6, dtype=np.int32),
                       max_new_tokens=4))
    done = eng.run()
    assert len(done[0].generated) == 4
    assert all(0 <= t < TINY.vocab for t in done[0].generated)


def test_prefill_matches_train_forward_logits():
    """prefill_step's last-token logits == hidden_states+logits directly."""
    cfg = get_smoke_config("qwen2-7b")
    model = LM(cfg)
    params = model.init(jax.random.key(0))
    prefill, _, run = build_prefill_step(cfg, multi_pod=False)
    toks = jnp.asarray(
        np.random.default_rng(0).integers(0, cfg.vocab, (2, 16)), jnp.int32
    )
    with jax.set_mesh(make_mesh((1, 1), ("data", "model"))):
        out = prefill(params, {"tokens": toks})
        hid, _, _ = model.hidden_states(params, toks, run=run)
        ref = model._logits(params, hid[:, -1:])
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4
    )


@pytest.mark.parametrize("knobs", [
    {"attn_seq_shard": False, "attn_block_q": 512},
    {"attn_seq_shard": True, "attn_block_q": 4096},
])
def test_run_knobs_numerically_equivalent(knobs):
    """The §Perf layout knobs change sharding, never math (1-device check)."""
    cfg = get_smoke_config("qwen2-7b")
    model = LM(cfg)
    params = model.init(jax.random.key(1))
    toks = jnp.asarray(
        np.random.default_rng(1).integers(0, cfg.vocab, (2, 32)), jnp.int32
    )
    base_run = {"sp": True, "remat": False, "dp_axes": ("data",),
                "attn_impl": "chunked", "loss_chunk": 512}
    with jax.set_mesh(make_mesh((1, 1), ("data", "model"))):
        ref, _, _ = model.hidden_states(params, toks, run=base_run)
        got, _, _ = model.hidden_states(params, toks, run={**base_run, **knobs})
    np.testing.assert_allclose(np.asarray(ref), np.asarray(got),
                               rtol=2e-4, atol=2e-4)


def test_data_pipeline_batch_shapes_and_determinism():
    cfg = DataConfig(vocab=128, seq_len=16, global_batch=4, seed=3)
    a = SyntheticTokenStream(cfg).next_batch()
    b = SyntheticTokenStream(cfg).next_batch()
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    assert a["tokens"].shape == (4, 16)
    assert a["targets"].shape == (4, 16)
    # targets are tokens shifted by one within the same row stream
    assert (a["tokens"][:, 1:] == a["targets"][:, :-1]).all()


def test_accum_equals_no_accum():
    """Gradient accumulation (the HBM-fitting device for big train cells)
    must not change the update."""
    cfg = TINY
    model = LM(cfg)
    params = model.init(jax.random.key(2))
    from repro.optim import adamw_init

    rng = np.random.default_rng(2)
    batch = {
        "tokens": jnp.asarray(rng.integers(0, cfg.vocab, (8, 16)), jnp.int32),
        "targets": jnp.asarray(rng.integers(0, cfg.vocab, (8, 16)), jnp.int32),
        "mask": jnp.ones((8, 16), jnp.float32),
    }
    outs = []
    with jax.set_mesh(make_mesh((1, 1), ("data", "model"))):
        for accum in (1, 4):
            step, _, _ = build_train_step(cfg, multi_pod=False, accum=accum)
            opt = adamw_init(params)
            p2, _, metrics = jax.jit(step)(params, opt, batch)
            outs.append((p2, float(metrics["loss"])))
    assert abs(outs[0][1] - outs[1][1]) < 1e-4
    for a, b in zip(jax.tree.leaves(outs[0][0]), jax.tree.leaves(outs[1][0])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=5e-3, atol=5e-5)
