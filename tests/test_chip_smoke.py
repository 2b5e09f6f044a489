"""chip_smoke.py rehearsed on the CPU at a tiny scale.

The phases run end to end with every check they make on the chip (oracle
equality, snapshot-refresh counters, shard placement on four virtual
devices), and the script keeps its contract: without a TPU, or with an
implementation override set, it exits non-zero and prints no result.
"""

import importlib.util
import os
import shutil
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY = dict(
    persons=300, knows=1500, load_batch=1024, v_capacity=128, e_capacity=1024,
    churn_batches=3, churn_batch=256, query_sources=4,
)


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py")
    )
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod  # dataclasses resolve the module by name
    spec.loader.exec_module(mod)
    yield mod
    sys.modules.pop("chip_smoke", None)


def test_graph_phase_matches_oracle_and_grows(smoke, capsys):
    smoke.graph_phase(0, smoke.GraphScale(**TINY))
    out = capsys.readouterr().out
    assert "match SequentialGraph" in out
    assert "frontier=xla compact=xla" in out


def test_serve_phase_at_smoke_widths(smoke, capsys):
    from repro.configs import get_smoke_config

    smoke.serve_phase(0, get_smoke_config("h2o-danube-3-4b"))
    assert "failover rebuilt identical page tables" in capsys.readouterr().out


def test_four_chip_phase_on_four_virtual_devices(tmp_path):
    script = textwrap.dedent(f"""
        import importlib.util, sys
        spec = importlib.util.spec_from_file_location("chip_smoke", {os.path.join(ROOT, "chip_smoke.py")!r})
        smoke = importlib.util.module_from_spec(spec)
        sys.modules["chip_smoke"] = smoke
        spec.loader.exec_module(smoke)
        smoke.four_chip_phase(0, smoke.GraphScale(**{TINY!r}))
    """)
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
    }
    r = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True,
        timeout=600, env=env, cwd=tmp_path,
    )
    assert r.returncode == 0, r.stderr[-3000:]
    assert "after the churn: shard s lives on device s" in r.stdout
    assert "identical to the 1-shard graph" in r.stdout


def test_no_tpu_exits_nonzero_without_result(smoke, capsys):
    assert smoke.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out


@pytest.mark.parametrize("var", ["REPRO_FRONTIER_IMPL", "REPRO_COMPACT_IMPL"])
def test_impl_override_is_refused(smoke, monkeypatch, capsys, var):
    monkeypatch.setenv(var, "kernel_interpret")
    assert smoke.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out


def test_script_alone_fails(tmp_path):
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    r = subprocess.run(
        [sys.executable, "chip_smoke.py"], capture_output=True, text=True,
        timeout=300, env={**env, "JAX_PLATFORMS": "cpu"}, cwd=tmp_path,
    )
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
