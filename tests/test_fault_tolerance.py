"""Fault tolerance: kill/resume, checkpoint validity, elastic re-shard,
straggler shard reconstruction.

The elastic (multi-device) cases run in a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` so the main pytest
process keeps seeing exactly one device.
"""

import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.checkpoint import CheckpointStore
from repro.data import DataConfig, SyntheticTokenStream

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _runner(tmp, **kw):
    import jax

    from repro.launch.mesh import make_mesh
    from repro.launch.train import TrainRunner
    from repro.models.config import ArchConfig

    cfg = ArchConfig(
        name="ft-tiny", family="dense", n_layers=2, d_model=64, n_heads=4,
        n_kv_heads=2, d_ff=128, vocab=256, dtype="float32",
    )
    mesh = make_mesh((1, 1), ("data", "model"))
    return TrainRunner(cfg, mesh, ckpt_dir=tmp, batch=4, seq=16, **kw)


# ---------------------------------------------------------------------------
# kill / resume
# ---------------------------------------------------------------------------

def test_kill_resume_bitexact(tmp_path):
    """Crash at step 7, resume from the step-5 checkpoint, continue to 10:
    final params must equal an uninterrupted 10-step run (the whole loop —
    data order, optimizer state, schedule — is restart-invariant)."""
    d1, d2 = str(tmp_path / "a"), str(tmp_path / "b")

    r_ref = _runner(d1)
    r_ref.init_or_restore()
    r_ref.train(10, log_every=100, save_every=5, log=lambda *a: None)
    ref = r_ref.params

    r1 = _runner(d2)
    r1.init_or_restore()
    with pytest.raises(SystemExit):
        r1.train(10, log_every=100, save_every=5, crash_at=7,
                 log=lambda *a: None)
    # deterministic variant of the race: let the async step-5 write land
    # before the replacement node looks (if the crash beats the writer,
    # restore correctly falls back — that path is covered by
    # test_corrupt_checkpoint_is_skipped / partial-dir tests).
    r1.store.wait()

    r2 = _runner(d2)
    assert r2.init_or_restore() == "restored"
    assert r2.step == 5
    r2.train(10, log_every=100, save_every=5, log=lambda *a: None)

    import jax
    for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(r2.params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)


def test_corrupt_checkpoint_is_skipped(tmp_path):
    store = CheckpointStore(str(tmp_path))
    tree = {"w": np.arange(8, dtype=np.float32)}
    store.save(1, tree)
    store.save(2, tree)
    # simulated failure mid-write: payload truncated after manifest landed
    with open(tmp_path / "step_0000000002" / "arrays.npz", "wb") as f:
        f.write(b"garbage")
    assert store.latest_step() == 1  # checksum rejects step 2
    restored = store.restore(1, {"w": np.zeros(8, np.float32)})
    np.testing.assert_array_equal(restored["w"], tree["w"])


def test_partial_checkpoint_dir_is_invisible(tmp_path):
    store = CheckpointStore(str(tmp_path))
    os.makedirs(tmp_path / "step_0000000009")  # no manifest: mid-crash dir
    assert store.latest_step() is None


# ---------------------------------------------------------------------------
# elastic re-shard (subprocess: needs >1 device)
# ---------------------------------------------------------------------------

_ELASTIC_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    import jax, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.checkpoint import CheckpointStore
    from repro.launch.mesh import make_mesh

    tmp = sys.argv[1]
    store = CheckpointStore(tmp)
    w = np.arange(64, dtype=np.float32).reshape(8, 8)
    mesh_a = make_mesh((2, 2), ("data", "model"))
    wa = jax.device_put(w, NamedSharding(mesh_a, P("data", "model")))
    store.save(3, {"w": wa})

    for shape, axes in [((4, 1), ("data", "model")), ((1, 4), ("data", "model")),
                        ((8,), ("data",))]:
        mesh_b = make_mesh(shape, axes)
        sh = {"w": NamedSharding(mesh_b, P("data"))}
        out = store.restore(3, {"w": jax.ShapeDtypeStruct((8, 8), np.float32)},
                            shardings=sh)
        np.testing.assert_array_equal(np.asarray(out["w"]), w)
        assert out["w"].sharding == sh["w"]  # actually resharded onto mesh_b
    print("ELASTIC_OK")
""")


def test_elastic_mesh_restore(tmp_path):
    r = subprocess.run(
        [sys.executable, "-c", _ELASTIC_SCRIPT, str(tmp_path)],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "PYTHONPATH": os.path.join(REPO, "src")},
    )
    assert "ELASTIC_OK" in r.stdout, r.stderr[-2000:]


# ---------------------------------------------------------------------------
# straggler mitigation: any host reconstructs any shard deterministically
# ---------------------------------------------------------------------------

def test_straggler_shard_reconstruction():
    cfg = DataConfig(vocab=512, seq_len=32, global_batch=16, seed=9)
    hosts = [SyntheticTokenStream(cfg, host_id=h, n_hosts=4) for h in range(4)]
    # advance to step 5
    batches = None
    for _ in range(5):
        batches = [h.next_batch() for h in hosts]
    # host 2 is a straggler/dead: host 0 recomputes host 2's shard for step 4
    rescue = SyntheticTokenStream(cfg, host_id=2, n_hosts=4)
    rescue.load_state_dict({"step": 4, "seed": 9})
    again = rescue.next_batch()
    np.testing.assert_array_equal(again["tokens"], batches[2]["tokens"])
    np.testing.assert_array_equal(again["targets"], batches[2]["targets"])


def test_global_batch_invariant_to_host_count():
    cfg = DataConfig(vocab=512, seq_len=32, global_batch=16, seed=9)
    one = SyntheticTokenStream(cfg, host_id=0, n_hosts=1).next_batch()
    parts = [
        SyntheticTokenStream(cfg, host_id=h, n_hosts=4).next_batch()
        for h in range(4)
    ]
    np.testing.assert_array_equal(
        one["tokens"], np.concatenate([p["tokens"] for p in parts])
    )
