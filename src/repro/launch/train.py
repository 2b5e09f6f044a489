"""End-to-end training driver.

  PYTHONPATH=src python -m repro.launch.train --arch qwen2-7b --smoke \
      --steps 50 --ckpt-dir /tmp/ckpt --mesh 1x1

Production behaviours demonstrated at CPU scale (all tested):
  * sharded init / jitted train step with NamedShardings from the same
    policy tables the 512-chip dry-run uses;
  * deterministic host-sharded data pipeline (restores mid-stream);
  * async, atomic, self-validating checkpoints; ``--crash-at N`` aborts
    mid-run (after the async save of step N kicks off) and a re-invocation
    resumes from the latest valid checkpoint — the kill/resume path;
  * elastic resume: ``--mesh`` on restore may differ from the saving run
    (checkpoints are mesh-agnostic);
  * straggler/failover property: any host can recompute any other host's
    data shard for any step (pipeline is (seed, step, row)-keyed).
"""

from __future__ import annotations

import argparse
import time
from typing import Optional

import jax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.checkpoint import CheckpointStore
from repro.configs import ARCH_NAMES, get_config, get_smoke_config
from repro.data import DataConfig, SyntheticTokenStream
from repro.launch.mesh import make_mesh as _make_mesh
from repro.launch.steps import build_train_step
from repro.models import LM
from repro.optim import AdamWConfig, adamw_init
from repro.optim.adamw import opt_pspecs


def make_mesh(spec: str):
    parts = tuple(int(x) for x in spec.split("x"))
    assert len(parts) == 2, "--mesh DxM"
    n = parts[0] * parts[1]
    assert n <= len(jax.devices()), f"mesh {spec} needs {n} devices"
    return _make_mesh(parts, ("data", "model"))


class TrainRunner:
    """Owns params/opt/data/ckpt; restartable at any step."""

    def __init__(self, cfg, mesh, *, ckpt_dir: Optional[str], batch: int,
                 seq: int, accum: int = 1, seed: int = 0,
                 opt_cfg: Optional[AdamWConfig] = None, keep: int = 3):
        self.cfg, self.mesh = cfg, mesh
        self.model = LM(cfg)
        self.store = CheckpointStore(ckpt_dir, keep=keep) if ckpt_dir else None
        self.data = SyntheticTokenStream(
            DataConfig(vocab=cfg.vocab, seq_len=seq, global_batch=batch,
                       seed=seed, n_codebooks=cfg.n_codebooks)
        )
        self.step_fn, _, self.run = build_train_step(
            cfg, multi_pod=False, accum=accum, opt_cfg=opt_cfg
        )
        self.pspecs = self.model.pspecs(multi_pod=False)
        self.step = 0
        self.params = None
        self.opt_state = None
        self._jit = None

    # -- state ------------------------------------------------------------
    def init_or_restore(self):
        if self.store is not None and self.store.latest_step() is not None:
            self.restore(self.store.latest_step())
            return "restored"
        with jax.set_mesh(self.mesh):
            self.params = jax.jit(
                self.model.init,
                out_shardings=jax.tree.map(
                    lambda s: NamedSharding(self.mesh, s), self.pspecs
                ),
            )(jax.random.key(0))
            self.opt_state = adamw_init(self.params)
        return "initialized"

    def restore(self, step: int):
        """Mesh-agnostic: ``self.mesh`` may differ from the saving run."""
        like_p = jax.eval_shape(self.model.init, jax.random.key(0))
        like = {"params": like_p, "opt": jax.eval_shape(adamw_init, like_p)}
        sh = {
            "params": jax.tree.map(
                lambda s: NamedSharding(self.mesh, s), self.pspecs
            ),
            "opt": jax.tree.map(
                lambda s: NamedSharding(self.mesh, s), opt_pspecs(self.pspecs)
            ),
        }
        tree = self.store.restore(step, like, shardings=sh)
        self.params, self.opt_state = tree["params"], tree["opt"]
        extra = self.store.extra(step)
        self.data.load_state_dict(extra["data"])
        self.step = step

    def save(self, *, sync: bool = False):
        if self.store is None:
            return
        payload = {"params": self.params, "opt": self.opt_state}
        extra = {"data": self.data.state_dict(), "step": self.step}
        if sync:
            self.store.save(self.step, payload, extra=extra)
        else:
            self.store.save_async(self.step, payload, extra=extra)

    # -- loop ------------------------------------------------------------
    def train(self, steps: int, *, log_every: int = 10, save_every: int = 50,
              crash_at: Optional[int] = None, log=print):
        if self.params is None:
            self.init_or_restore()
        mesh = self.mesh
        if self._jit is None:
            self._jit = jax.jit(self.step_fn, donate_argnums=(0, 1))
        losses = []
        with jax.set_mesh(mesh):
            t0 = time.time()
            while self.step < steps:
                host_batch = self.data.next_batch()
                batch = {
                    k: jax.device_put(
                        v,
                        NamedSharding(
                            mesh, P("data", *([None] * (v.ndim - 1)))
                        ),
                    )
                    for k, v in host_batch.items()
                }
                self.params, self.opt_state, metrics = self._jit(
                    self.params, self.opt_state, batch
                )
                self.step += 1
                if self.step % log_every == 0 or self.step == steps:
                    loss = float(metrics["loss"])
                    losses.append((self.step, loss))
                    dt = time.time() - t0
                    log(f"step {self.step:5d} loss {loss:.4f} "
                        f"({dt / log_every:.2f}s/step)")
                    t0 = time.time()
                if save_every and self.step % save_every == 0:
                    self.save()
                if crash_at is not None and self.step >= crash_at:
                    # simulated node failure: the async save may be mid-write;
                    # the atomic-rename contract means restore never sees it
                    # half-written.
                    raise SystemExit(42)
        if self.store is not None:
            self.save(sync=True)
            self.store.wait()
        return losses


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_NAMES, default="qwen2-7b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--mesh", default="1x1")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--save-every", type=int, default=50)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--crash-at", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    mesh = make_mesh(args.mesh)
    runner = TrainRunner(cfg, mesh, ckpt_dir=args.ckpt_dir, batch=args.batch,
                         seq=args.seq, accum=args.accum, seed=args.seed)
    print(f"[train] {cfg.name} ({'smoke' if args.smoke else 'FULL'}) "
          f"mesh={args.mesh} -> {runner.init_or_restore()} @ step {runner.step}")
    runner.train(args.steps, log_every=args.log_every,
                 save_every=args.save_every, crash_at=args.crash_at)
    print(f"[train] done @ step {runner.step}")


if __name__ == "__main__":
    main()
