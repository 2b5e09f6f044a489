"""Wait-free telemetry (repro.obs): the three contracts of
docs/OBSERVABILITY.md, pinned over a churned-graph corpus.

1. **Bit-identity** — obs-on and obs-off runs of the identical op stream
   produce byte-identical table state and query answers, for every mode and
   seed in the corpus.  Every metric is derived from arrays the jitted
   programs compute regardless, so enabling telemetry must never perturb
   the computation.
2. **Shard-invariance** — the abstract-level counters (op counts, inserts,
   the FPSP edge-lane dup split) and the canonical directory probe
   histogram are identical across ``n_shards ∈ {1, 2, 4}``: duplicate
   ``(u, v)`` edge keys co-locate on one shard by construction, and the
   directory's placement depends only on the live key set.  (The *physical*
   per-shard probe histograms are deliberately not shard-invariant.)
3. **Impl-invariance** — ``maintenance_impl="host"`` and
   ``"device_interpret"`` runs agree on tables, physical probe histograms,
   and the engine claim-round histogram (all rehash impls build
   bit-identical tables; claim rounds happen in the engines, not in
   maintenance).
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

import numpy as np
import pytest

from repro.core import WaitFreeGraph, maintenance
from repro.core.types import OP_ADD_VERTEX, OP_REMOVE_VERTEX
from repro.core.workloads import sample_batch, sample_query_pairs
from repro.obs import metrics as obsm
from repro.obs import probes

KEY_SPACE = 24  # small key space: dense conflicts, real churn

# the abstract-level counters that must not depend on how the tables are
# partitioned (physical counters — probe hists, per-shard balance — may)
SHARD_INVARIANT_COUNTERS = (
    "apply.batches",
    "apply.ops",
    "engine.vops",
    "engine.eops",
    "engine.inserted",
    "fastpath.eops",
    "fastpath.edge_dup",
)


def _churn_stream(seed: int):
    """One deterministic churned-graph op stream + query batch: bulk
    traversal traffic, a deletion wave, incarnation revivals, fresh edges
    (the tests/test_traversal.py corpus shape)."""
    rng = np.random.default_rng(seed)
    batches = []
    for _ in range(2):
        batches.append(sample_batch(rng, 192, "traversal", key_space=KEY_SPACE))
    kill = rng.choice(KEY_SPACE, size=8, replace=False).astype(np.int32)
    batches.append(
        (np.full(8, OP_REMOVE_VERTEX, np.int32), kill, np.zeros(8, np.int32))
    )
    revive = kill[:4].copy()
    batches.append(
        (np.full(4, OP_ADD_VERTEX, np.int32), revive, np.zeros(4, np.int32))
    )
    batches.append(sample_batch(rng, 96, "traversal", key_space=KEY_SPACE))
    queries = sample_query_pairs(rng, 32, KEY_SPACE)
    return batches, queries


def _run(seed: int, mode: str, *, obs, n_shards: int = 1,
         maintenance_impl=None):
    batches, (qu, qv) = _churn_stream(seed)
    g = WaitFreeGraph(
        256, 1024, mode=mode, n_shards=n_shards,
        maintenance_impl=maintenance_impl, obs=obs,
    )
    for ops, us, vs in batches:
        g.apply(ops, us, vs)
    return g, np.asarray(g.reachable(qu, qv))


def _states(g: WaitFreeGraph):
    return list(g.shards) if g.n_shards > 1 else [g.state]


def _state_bytes(g: WaitFreeGraph):
    return [
        tuple(np.asarray(a).tobytes() for a in st) for st in _states(g)
    ]


# ---------------------------------------------------------------------------
# 1. obs on/off bit-identity: 2 modes x 25 seeds = 50 churned graphs
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["waitfree", "fpsp"])
@pytest.mark.parametrize("seed", range(25))
def test_obs_on_off_bit_identical(mode, seed):
    g_off, ans_off = _run(seed, mode, obs=False)
    g_on, ans_on = _run(seed, mode, obs=True)
    assert _state_bytes(g_on) == _state_bytes(g_off)
    assert ans_on.tolist() == ans_off.tolist()
    # the enabled run actually observed the traffic it claims to observe
    c = g_on.obs.counters()
    assert c["apply.batches"] == 5
    assert c["apply.ops"] == 192 + 192 + 8 + 4 + 96
    assert c["engine.vops"] + c["engine.eops"] == c["apply.ops"]
    assert g_on.obs.hist_counts("engine.claim_rounds")
    if mode == "fpsp":
        assert c["fastpath.ops"] == c["apply.ops"]
        assert obsm.fastpath_frac(g_on.obs) is not None
    assert not g_off.obs.enabled and g_off.obs.counters() == {}


def test_obs_per_phase_spans_and_probe_health():
    """Sharded apply emits the six-phase span trace; probe_health files the
    physical histograms and they cover exactly the occupied slots."""
    g, _ = _run(0, "fpsp", obs=True, n_shards=2)
    spans = g.obs.dump()["spans"]
    for name in ("graph.apply_sharded", "phase.route", "phase.settle_vertices",
                 "phase.answer_stabs", "phase.gather", "phase.settle_edges"):
        assert name in spans, f"missing span {name}"
    h = g.probe_health()
    from repro.core.types import EMPTY_KEY

    occupied_v = sum(
        int(np.sum(np.asarray(st.v_key) != EMPTY_KEY)) for st in _states(g)
    )
    assert g.obs.hist_counts("probe.vertex") == h["vertex"]
    assert g.obs.hist_counts("probe.edge") == h["edge"]
    assert occupied_v == sum(h["vertex"].values())


def test_obs_frontier_lanes_streamed(monkeypatch):
    """bfs_batch counts the edge lanes one expansion streams: only the blocks
    that hold a valid lane, fewer than the snapshot's lane capacity."""
    from repro.kernels.frontier import xla as frontier_xla

    monkeypatch.setattr(frontier_xla, "_BLOCK_ELEMS", 2**10)  # 64-lane blocks at 16 sources
    g, _ = _run(2, "waitfree", obs=True)
    o, _ = _run(2, "waitfree", obs=False)
    keys = list(range(KEY_SPACE))
    assert g.bfs_batch(keys[:3]) == o.bfs_batch(keys[:3])
    g.bfs_batch(keys[3:6])
    csr = g.traversal_csr()
    c = g.obs.counters()
    streamed = -(-int(csr.n_edges) // 64) * 64
    assert 0 < streamed < csr.e_capacity
    assert c["frontier.lanes_streamed"] == 2 * streamed
    assert c["frontier.lane_capacity"] == 2 * csr.e_capacity == 2 * 1024
    assert "frontier.lanes_streamed" not in o.obs.counters()


# ---------------------------------------------------------------------------
# 2. shard-invariance of abstract counters + canonical directory histogram
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 7])
def test_obs_shard_invariant_counters(seed):
    runs = {}
    for n_shards in (1, 2, 4):
        g, ans = _run(seed, "fpsp", obs=True, n_shards=n_shards)
        runs[n_shards] = (g, ans)
    g1, ans1 = runs[1]
    c1 = g1.obs.counters()
    dir1 = probes.directory_probe_histogram(g1)
    for n_shards in (2, 4):
        g, ans = runs[n_shards]
        assert ans.tolist() == ans1.tolist()
        c = g.obs.counters()
        for name in SHARD_INVARIANT_COUNTERS:
            assert c.get(name) == c1.get(name), (
                f"{name} differs at n_shards={n_shards}: "
                f"{c.get(name)} != {c1.get(name)}"
            )
        # canonical directory placement depends only on the live key set
        assert probes.directory_probe_histogram(g) == dir1
        # edge-lane fast-path fraction is the shard-invariant aggregation
        eops, dup = c["fastpath.eops"], c["fastpath.edge_dup"]
        assert 1.0 - dup / eops == 1.0 - c1["fastpath.edge_dup"] / c1[
            "fastpath.eops"]


# ---------------------------------------------------------------------------
# 3. maintenance-impl invariance: host vs device_interpret
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [3])
def test_obs_maintenance_impl_invariant(seed):
    g_h, ans_h = _run(seed, "fpsp", obs=True, maintenance_impl="host")
    g_d, ans_d = _run(seed, "fpsp", obs=True,
                      maintenance_impl="device_interpret")
    assert _state_bytes(g_h) == _state_bytes(g_d)
    assert ans_h.tolist() == ans_d.tolist()
    assert probes.table_probe_histogram(g_h) == probes.table_probe_histogram(g_d)
    assert (g_h.obs.hist_counts("engine.claim_rounds")
            == g_d.obs.hist_counts("engine.claim_rounds"))


def test_obs_rehash_span_and_claim_rounds():
    """maintenance.rehash records its span + the host placement rounds into
    the ambient registry, and the histograms match across impls' shared
    host-oracle fallback."""
    g, _ = _run(1, "waitfree", obs=True)
    reg = obsm.Registry()
    with obsm.use(reg):
        st, _, ok = maintenance.rehash(
            g.state, 2 * g.state.v_capacity, 2 * g.state.e_capacity,
            impl="host",
        )
    assert ok
    assert reg.counters()["maintenance.rehash"] == 1
    assert "maintenance.rehash.host" in reg.dump()["spans"]
    assert sum(reg.hist_counts("maintenance.claim_rounds").values()) > 0
    # the grown tables are probe-healthy: every key within MAX_PROBES
    h = probes.table_probe_histogram(st)
    assert h["vertex"] and max(h["vertex"]) <= 32


# ---------------------------------------------------------------------------
# switches, schema, renderers
# ---------------------------------------------------------------------------

def test_repro_obs_env_switch(monkeypatch):
    monkeypatch.delenv("REPRO_OBS", raising=False)
    assert not WaitFreeGraph(64, 256).obs.enabled
    monkeypatch.setenv("REPRO_OBS", "1")
    g = WaitFreeGraph(64, 256)
    assert g.obs.enabled
    monkeypatch.setenv("REPRO_OBS", "off")
    assert not WaitFreeGraph(64, 256).obs.enabled
    # explicit flag beats the env
    monkeypatch.setenv("REPRO_OBS", "1")
    assert not WaitFreeGraph(64, 256, obs=False).obs.enabled


def test_registry_dump_schema_roundtrips():
    g, _ = _run(2, "fpsp", obs=True, n_shards=2)
    g.probe_health()
    dump = json.loads(json.dumps(g.obs.dump()))  # JSON-serializable
    assert dump["schema"] == "repro-obs/1"
    assert dump["counters"]["apply.batches"] == 5
    hist = dump["histograms"]["engine.claim_rounds"]
    assert hist["count"] == sum(hist["counts"].values())
    assert set(dump["spans"]) >= {"graph.apply_sharded", "phase.route"}


def _load_tool(name: str):
    path = Path(__file__).resolve().parent.parent / "tools" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_obs_report_renders_dump_and_bundle(tmp_path, capsys):
    obs_report = _load_tool("obs_report")
    g, _ = _run(4, "fpsp", obs=True)
    g.probe_health()
    single = tmp_path / "dump.json"
    single.write_text(json.dumps(g.obs.dump()))
    assert obs_report.main([str(single)]) == 0
    out = capsys.readouterr().out
    assert "fastpath_frac" in out and "engine.claim_rounds" in out
    bundle = tmp_path / "BENCH_obs.json"
    bundle.write_text(json.dumps(
        {"bench": "x", "backend": "cpu", "quick": True,
         "graphs": {"fpsp/ks24": g.obs.dump()}}
    ))
    assert obs_report.main([str(bundle)]) == 0
    assert "fpsp/ks24" in capsys.readouterr().out


def test_bench_regression_fastpath_gate(tmp_path):
    bench_regression = _load_tool("bench_regression")
    row = dict(impl="delta_host", build="fpsp", graph_size=512, batch=8,
               n_shards=1, snap_ms=1.0, us_per_query=4.0, fastpath_frac=0.95)
    base = tmp_path / "base.json"
    base.write_text(json.dumps({"rows": [row]}))
    ok = tmp_path / "ok.json"
    ok.write_text(json.dumps({"rows": [dict(row, fastpath_frac=0.90)]}))
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"rows": [dict(row, fastpath_frac=0.70)]}))
    assert bench_regression.main([str(base), str(ok)]) == 0
    assert bench_regression.main([str(base), str(bad)]) == 1
    # pre-obs baselines (no fastpath_frac column) skip the gate gracefully
    old = tmp_path / "old.json"
    old.write_text(json.dumps(
        {"rows": [{k: v for k, v in row.items() if k != "fastpath_frac"}]}
    ))
    assert bench_regression.main([str(old), str(bad)]) == 0
