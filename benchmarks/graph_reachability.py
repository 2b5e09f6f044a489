"""Batched reachability benchmark: query-batch size × graph size × engine,
plus frontier-kernel impls and rebuild-vs-delta snapshot maintenance.

The workload family of the related papers (arXiv 1809.00896 reachability
queries, arXiv 2310.02380 wait-free snapshots) on top of this repo's graph:
build a graph with the ``traversal`` mix, compact it once into a consistent
CSR snapshot, then answer batches of ``reachable(u, v)`` pairs.

Engine/impl columns:

  oracle / python        — pure-Python sequential BFS per query (ground truth)
  batched / xla          — jitted CSR frontier engine, XLA expansion (the
                           implementation every backend runs)
  batched / kernel_interpret — same engine through the Pallas frontier
                           kernel in the interpreter (``--kernels``; the TPU
                           compiler refuses the kernel, docs/KERNELS.md)

Maintenance rows (engine ``maintenance``) time the two table-maintenance
hot paths:

* snapshot refresh after each small update batch of an update-light
  query-heavy mix: ``rebuild`` pays a full ``build_csr`` per batch,
  ``delta_host`` folds the batch with the numpy splice (O(valid edges)
  lexsort + host round-trip), ``delta_device`` with the fused device
  splice (``repro.core.maintenance.delta_merge``).  The
  ``batch`` column sweeps the update-batch size: the device fold's cost
  should track the batch, not the live-edge count.
* growth rehash (``rehash_host`` vs ``rehash_device``, ``batch`` = 0):
  one capacity-doubling compaction of the current state, host claim
  rounds vs the ``kernels/compact`` placement pipeline.

``snap_ms`` is the mean refresh cost; ``us_per_query`` amortizes it over a
256-query window.  Delta below rebuild (and device at or below host) is
the acceptance signal.  The maintenance rows are also dumped to
``BENCH_maintenance.json`` so the perf trajectory is recorded per run.

Two costs are reported separately: ``snap_ms`` (snapshot compaction /
refresh per graph version — amortized over every query until the next
update batch) and ``us_per_query`` (marginal per-query cost at the given
batch size).

CPU caveat (same as graph_throughput.py): XLA lowers the frontier scatter
near-serially on CPU, so absolute ``us_per_query`` compresses the batched
engine's numbers; the machine-independent content is the *scaling* in batch
size (the whole query batch rides one dispatch), the one-dispatch snapshot
cost, and the rebuild-vs-delta ratio.

The ``n_shards`` column reports the hash-prefix shard count of the graph
the row was measured on (``repro.core.sharding``).  Query rows sweep it —
the batched engine answers against the *fused* cross-shard snapshot
(``fuse_partitioned``: canonical vertex directory + per-shard edge
validation), and all shard counts must agree bit-for-bit (asserted).
Maintenance rows come in both flavors: ``n_shards=1`` rows time the
per-shard primitives in isolation (rebuild / delta folds / one-table
rehash), and ``n_shards>1`` rows time the sharded pipeline end to end —
``rebuild_fused`` is the fused cross-shard refresh, ``rehash_host`` at
``n_shards>1`` doubles every shard against the shared gathered-endpoint
index (``rehash(..., endpoints=...)``).  ``peak_bytes`` is the largest
single shard's table footprint (bytes of its live arrays): the partitioned
design's O(N/S) memory claim as a measured column — it should fall ~1/S as
``n_shards`` rises on the same abstract graph.  See the README
"Benchmarks" section for how to read the CSV and ``BENCH_maintenance.json``.

Three obs-derived columns ride along (``docs/OBSERVABILITY.md``), computed
from each graph's *build* telemetry — the timed loops run with no registry
active: ``fastpath_frac`` (fraction of build ops that stayed on the FPSP
fast path; blank for non-FPSP builds with no conflict accounting),
``mean_probe_len`` (mean physical probe-chain length over both tables,
``repro.obs.probes``), ``claim_rounds_p99`` (p99 of claim rounds per
settle — the helping-bound witness).  The per-graph registries are dumped
to ``BENCH_obs.json`` (rendered by ``tools/obs_report.py``; CI uploads it
next to the CSV artifact), and ``tools/bench_regression.py`` gates on
``fastpath_frac`` drift.

Usage:  python benchmarks/graph_reachability.py [--quick] [--kernels]
Output: CSV rows on stdout
        (bench,engine,impl,build,graph_size,batch,n_shards,snap_ms,
        us_per_query,peak_bytes,fastpath_frac,mean_probe_len,
        claim_rounds_p99).
"""

from __future__ import annotations

import json
import sys
import time
from typing import Dict, List

import jax
import numpy as np

from repro.core import WaitFreeGraph, maintenance, sharding, traversal
from repro.obs import metrics as obsm
from repro.obs import probes as obsprobes
from repro.core.workloads import (
    initial_vertices,
    sample_batch,
    sample_query_pairs,
    sample_update_batch,
)

GRAPH_SIZES = (256, 1024, 4096)
QUERY_BATCHES = (1, 16, 128, 1024)
ORACLE_MAX_BATCH = 128  # python BFS per query; cap its sweep and say so
MAINT_QUERY_WINDOW = 256  # queries amortizing each maintenance refresh


def _build_graph(
    key_space: int, mode: str, seed: int = 0, n_shards: int = 1,
    obs: bool = True,
) -> WaitFreeGraph:
    """Pre-seeded vertices (the paper's initial graph) + traversal-mix
    traffic, so AddE lands on live endpoints and real path structure forms.

    Each graph gets its own obs :class:`~repro.obs.metrics.Registry` so the
    build traffic's telemetry (fast-path fraction, claim rounds) is
    per-graph.  Only the *build* is instrumented — the timed query and
    maintenance loops below run with no registry active, so the numbers in
    the timing columns are obs-free (the overhead contract in
    ``docs/OBSERVABILITY.md``)."""
    rng = np.random.default_rng(seed)
    g = WaitFreeGraph(
        v_capacity=4 * key_space, e_capacity=16 * key_space, mode=mode,
        n_shards=n_shards, obs=obsm.Registry() if obs else False,
    )
    g.apply(*initial_vertices(key_space))
    for _ in range(4):
        ops, us, vs = sample_batch(rng, key_space // 2, "traversal", key_space=key_space)
        g.apply(ops, us, vs)
    return g


def _obs_columns(g: WaitFreeGraph) -> Dict:
    """The three obs-derived CSV columns for one built graph: build-traffic
    fast-path fraction, mean physical probe-chain length, and the p99 of
    claim rounds per settle.  ``None`` (blank CSV cell) where the registry
    saw no relevant traffic."""
    reg = g.obs
    if not reg.enabled:
        return dict(fastpath_frac=None, mean_probe_len=None,
                    claim_rounds_p99=None)
    g.probe_health()  # file probe.vertex / probe.edge hists into the registry
    return dict(
        fastpath_frac=obsm.fastpath_frac(reg),
        mean_probe_len=obsprobes.mean_probe_len(g),
        claim_rounds_p99=reg.percentile("engine.claim_rounds", 99),
    )


def _snap_csr(g: WaitFreeGraph):
    """The full snapshot-compaction pass: build_csr for a 1-shard graph,
    directory placement + partitioned fusion for a sharded one."""
    if g.n_shards == 1:
        return traversal.build_csr(g.state)
    return sharding.fuse_partitioned(g.shards)


def _graph_state_bytes(st) -> int:
    return int(sum(np.asarray(a).nbytes for a in st))


def _peak_shard_bytes(g: WaitFreeGraph) -> int:
    """Peak per-shard table footprint: bytes of the largest shard's live
    arrays.  The partitioned design's O(N/S) claim in one number — at a
    fixed abstract graph this column should fall ~1/S as n_shards rises
    (modulo the power-of-two capacity floor)."""
    states = g.shards if g.n_shards > 1 else [g.state]
    return max(_graph_state_bytes(st) for st in states)


def _bench_snap(g: WaitFreeGraph):
    """One-time CSR compaction cost — impl-independent, measured once per
    graph build and shared across the impl rows."""
    jax.block_until_ready(_snap_csr(g))  # warmup / compile
    t0 = time.perf_counter()
    csr = _snap_csr(g)
    jax.block_until_ready(csr)
    return time.perf_counter() - t0, csr


def _bench_batched(csr, pairs, timed: int, impl=None):
    us, vs = pairs
    r = traversal.reachable(csr, us, vs, impl=impl)  # warmup / compile
    jax.block_until_ready(r)
    t0 = time.perf_counter()
    for _ in range(timed):
        r = traversal.reachable(csr, us, vs, impl=impl)
    jax.block_until_ready(r)
    return (time.perf_counter() - t0) / timed, np.asarray(r)


def _bench_oracle(g: WaitFreeGraph, pairs, timed: int):
    from repro.core.oracle import SequentialGraph

    t0 = time.perf_counter()
    V, E = g.snapshot()
    o = SequentialGraph()
    o.vertices, o.edges = V, E
    dt_snap = time.perf_counter() - t0
    us, vs = pairs
    t0 = time.perf_counter()
    for _ in range(timed):
        out = [o.reachable(int(a), int(b)) for a, b in zip(us, vs)]
    dt = (time.perf_counter() - t0) / timed
    return dt, dt_snap, np.asarray(out)


def _bench_maintenance(
    key_space: int, mode: str, update_batch: int, n_batches: int, seed: int,
    kernels: bool = False,
) -> Dict[str, float]:
    """Mean snapshot-refresh ms per update batch: rebuild vs host delta vs
    device delta.

    One graph, one update stream; after every applied batch all three
    refresh primitives are timed on the same post state — ``build_csr``
    (what the ``rebuild`` policy pays) and ``apply_delta`` from the previous
    snapshot with the host splice and the device searchsorted merge (each
    chains its own snapshot into the next round; tests assert both are
    bit-identical to the rebuild)."""
    g = _build_graph(key_space, mode, seed)
    g.csr_maintenance = "rebuild"  # keep WaitFreeGraph out of the timings
    rng = np.random.default_rng(seed + 2)
    csr = traversal.build_csr(g.state)
    jax.block_until_ready(csr)
    # pass 1 — chain the folds once to (a) record each batch's (pre-CSR,
    # post-state) pair and (b) warm every per-bucket compile the stream
    # needs (touched-key buckets vary batch to batch; timing compiles would
    # charge the device merge for one-time costs the steady state never
    # pays again)
    steps = []
    for _ in range(n_batches):
        ops, us, vs = sample_update_batch(rng, update_batch, key_space)
        g.apply(ops, us, vs)
        steps.append((csr, g.state, ops, us, vs))
        csr = traversal.apply_delta(csr, g.state, ops, us, vs, impl="host")
    jax.block_until_ready(csr.src)
    impls = [("delta_host", "host"), ("delta_device", "device")]
    for pre, state, ops, us, vs in steps:
        jax.block_until_ready(traversal.build_csr(state))
        for _, impl in impls[1:]:
            jax.block_until_ready(
                traversal.apply_delta(pre, state, ops, us, vs, impl=impl).src
            )
    # pass 2 — steady-state timing over the identical work
    timers = {"rebuild": 0.0, **{name: 0.0 for name, _ in impls}}
    for pre, state, ops, us, vs in steps:
        t0 = time.perf_counter()
        jax.block_until_ready(traversal.build_csr(state))
        timers["rebuild"] += time.perf_counter() - t0
        for name, impl in impls:
            t0 = time.perf_counter()
            out = traversal.apply_delta(pre, state, ops, us, vs, impl=impl)
            jax.block_until_ready(out.src)
            timers[name] += time.perf_counter() - t0
    return {k: 1e3 * t / n_batches for k, t in timers.items()}


def _bench_rehash(g: WaitFreeGraph, timed: int, kernels: bool = False) -> Dict[str, float]:
    """Mean growth-rehash ms (one capacity doubling of the current state),
    host claim rounds vs the device compaction pipeline (plus the Pallas
    interpreter row with ``--kernels`` off-TPU, for the parity artifact)."""
    state = g.state
    nv, ne = 2 * state.v_capacity, 2 * state.e_capacity
    impls = ["host", "device"]
    if kernels and jax.default_backend() != "tpu":
        impls.append("device_interpret")
    out = {}
    for impl in impls:
        s, _, ok = maintenance.rehash(state, nv, ne, impl=impl)  # warmup/compile
        assert ok
        jax.block_until_ready(s.v_key)
        t0 = time.perf_counter()
        for _ in range(timed):
            s, _, ok = maintenance.rehash(state, nv, ne, impl=impl)
            jax.block_until_ready(s.v_key)
        out[f"rehash_{impl}"] = 1e3 * (time.perf_counter() - t0) / timed
    return out


def _bench_sharded_maintenance(
    key_space: int, mode: str, update_batch: int, n_batches: int, seed: int,
    n_shards: int,
):
    """The sharded counterparts of the maintenance rows: snapshot refresh is
    a fused per-shard rebuild (``fuse_partitioned`` — directory placement +
    per-shard edge validation), growth rehash doubles every shard against
    the shared gathered-endpoint index (``rehash(..., endpoints=...)``).
    Reported ms are totals across all shards, so they compare directly to
    the 1-shard rows on the same abstract graph."""
    g = _build_graph(key_space, mode, seed, n_shards)
    rng = np.random.default_rng(seed + 2)
    jax.block_until_ready(sharding.fuse_partitioned(g.shards).src)  # warmup
    t_refresh = 0.0
    for _ in range(n_batches):
        ops, us, vs = sample_update_batch(rng, update_batch, key_space)
        g.apply(ops, us, vs)
        t0 = time.perf_counter()
        csr = sharding.fuse_partitioned(g.shards)
        jax.block_until_ready(csr.src)
        t_refresh += time.perf_counter() - t0

    endpoints = sharding.gather_live_vertices(g.shards)

    def grow_all():
        for st in g.shards:
            s, _, ok = maintenance.rehash(
                st, 2 * st.v_capacity, 2 * st.e_capacity,
                impl="host", endpoints=endpoints,
            )
            assert ok
            jax.block_until_ready(s.v_key)

    grow_all()  # warmup / compile
    t0 = time.perf_counter()
    grow_all()
    t_rehash = time.perf_counter() - t0
    return (
        {
            "rebuild_fused": 1e3 * t_refresh / n_batches,
            "rehash_host": 1e3 * t_rehash,
        },
        g,
    )


def run(
    graph_sizes=GRAPH_SIZES,
    batches=QUERY_BATCHES,
    build_modes=("waitfree", "fpsp"),
    timed: int = 8,
    seed: int = 0,
    kernels: bool = False,
    maint_batches: int = 8,
    update_batches=(8, 32, 128),
    shard_counts=(1, 4),
    obs_out: Dict = None,
) -> List[Dict]:
    impls = [("xla", "xla")]
    if kernels:
        impls.append(("kernel_interpret", "kernel_interpret"))
    rows = []
    for key_space in graph_sizes:
        for mode in build_modes:
            # query rows sweep the shard count: same seed -> same op stream
            # and same query pairs, so the fused-snapshot answers must agree
            # bit-for-bit with the 1-shard graph's (asserted below)
            shard_ref: Dict[int, List] = {}
            for n_shards in shard_counts:
                g = _build_graph(key_space, mode, seed, n_shards)
                ocols = _obs_columns(g)
                if obs_out is not None:
                    obs_out[f"{mode}/ks{key_space}/shards{n_shards}"] = (
                        g.obs.dump()
                    )
                rng = np.random.default_rng(seed + 1)
                pb = _peak_shard_bytes(g)
                snap_b, csr = _bench_snap(g)
                for n in batches:
                    pairs = sample_query_pairs(rng, n, key_space)
                    ref_out = None
                    for impl_name, impl in impls:
                        dt_b, out_b = _bench_batched(csr, pairs, timed, impl)
                        rows.append(dict(engine="batched", impl=impl_name, build=mode,
                                         graph_size=key_space, batch=n,
                                         n_shards=n_shards,
                                         snap_ms=1e3 * snap_b,
                                         us_per_query=1e6 * dt_b / n,
                                         peak_bytes=pb, **ocols))
                        if ref_out is None:
                            ref_out = out_b
                        else:
                            assert out_b.tolist() == ref_out.tolist(), "impls disagree"
                    cross = shard_ref.setdefault(n, ref_out.tolist())
                    assert ref_out.tolist() == cross, "shard counts disagree"
                    if n_shards != shard_counts[0]:
                        continue  # oracle ground truth once per (mode, batch)
                    if n > ORACLE_MAX_BATCH:
                        # stderr: stdout is the documented CSV contract
                        print(f"# dropped: oracle @ batch {n} (python BFS per "
                              f"query; capped at {ORACLE_MAX_BATCH})",
                              file=sys.stderr)
                        continue
                    dt_o, snap_o, out_o = _bench_oracle(g, pairs, max(1, timed // 4))
                    assert ref_out.tolist() == out_o.tolist(), "engines disagree"
                    rows.append(dict(engine="oracle", impl="python", build=mode,
                                     graph_size=key_space, batch=n,
                                     n_shards=n_shards,
                                     snap_ms=1e3 * snap_o,
                                     us_per_query=1e6 * dt_o / n,
                                     peak_bytes=pb, **ocols))
            # rebuild-vs-delta maintenance on the update-light mix; the
            # update-batch sweep exposes what each refresh scales with
            # (the device merge should track batch size, the host splice
            # and the rebuild the live-edge count / capacity).  n_shards=1:
            # the refresh primitives are per-shard by construction, so the
            # single-shard number is the per-shard cost.
            g = _build_graph(key_space, mode, seed)
            ocols1 = _obs_columns(g)
            if obs_out is not None:
                obs_out[f"{mode}/ks{key_space}/maint"] = g.obs.dump()
            pb1 = _peak_shard_bytes(g)
            for update_batch in update_batches:
                maint = _bench_maintenance(
                    key_space, mode, update_batch, maint_batches, seed,
                    kernels=kernels,
                )
                for policy, snap_ms in maint.items():
                    rows.append(dict(engine="maintenance", impl=policy, build=mode,
                                     graph_size=key_space, batch=update_batch,
                                     n_shards=1,
                                     snap_ms=snap_ms,
                                     us_per_query=1e3 * snap_ms / MAINT_QUERY_WINDOW,
                                     peak_bytes=pb1, **ocols1))
            # growth rehash: host claim rounds vs device compaction pipeline
            for policy, snap_ms in _bench_rehash(
                g, max(2, timed // 4), kernels=kernels
            ).items():
                rows.append(dict(engine="maintenance", impl=policy, build=mode,
                                 graph_size=key_space, batch=0,
                                 n_shards=1,
                                 snap_ms=snap_ms,
                                 us_per_query=1e3 * snap_ms / MAINT_QUERY_WINDOW,
                                 peak_bytes=pb1, **ocols1))
            # the sharded counterparts: fused refresh + endpoint-indexed
            # per-shard rehash, peak_bytes showing the O(N/S) footprint
            s_last = shard_counts[-1]
            if s_last > 1:
                maint_s, gs = _bench_sharded_maintenance(
                    key_space, mode, update_batches[0], maint_batches, seed,
                    s_last,
                )
                pbs = _peak_shard_bytes(gs)
                ocols_s = _obs_columns(gs)
                if obs_out is not None:
                    obs_out[f"{mode}/ks{key_space}/maint_shards{s_last}"] = (
                        gs.obs.dump()
                    )
                for policy, snap_ms in maint_s.items():
                    rows.append(dict(engine="maintenance", impl=policy,
                                     build=mode, graph_size=key_space,
                                     batch=0 if policy.startswith("rehash")
                                     else update_batches[0],
                                     n_shards=s_last,
                                     snap_ms=snap_ms,
                                     us_per_query=1e3 * snap_ms
                                     / MAINT_QUERY_WINDOW,
                                     peak_bytes=pbs, **ocols_s))
    return rows


def main(argv=None):
    argv = sys.argv[1:] if argv is None else argv
    quick = "--quick" in argv
    kernels = "--kernels" in argv
    obs_dumps: Dict[str, Dict] = {}
    rows = run(
        # 512 floor: at 256 the whole edge table is small enough that a full
        # rebuild costs about as much as the delta's fixed overhead, and the
        # maintenance comparison drowns in scheduler noise on shared CI
        graph_sizes=(512, 1024) if quick else GRAPH_SIZES,
        batches=(16, 128) if quick else QUERY_BATCHES,
        # quick keeps one mode; fpsp so the fastpath_frac column (and the
        # BENCH_obs.json artifact CI uploads) carries the FPSP telemetry
        build_modes=("fpsp",) if quick else ("waitfree", "fpsp"),
        timed=2 if quick else 8,
        kernels=kernels,
        maint_batches=4 if quick else 8,
        update_batches=(8, 64) if quick else (8, 32, 128),
        shard_counts=(1, 2) if quick else (1, 4),
        obs_out=obs_dumps,
    )

    def _cell(v, fmt):
        return "" if v is None else format(v, fmt)

    print("bench,engine,impl,build,graph_size,batch,n_shards,snap_ms,"
          "us_per_query,peak_bytes,fastpath_frac,mean_probe_len,"
          "claim_rounds_p99")
    for r in rows:
        print(
            f"graph_reachability,{r['engine']},{r['impl']},{r['build']},"
            f"{r['graph_size']},{r['batch']},{r['n_shards']},{r['snap_ms']:.3f},"
            f"{r['us_per_query']:.2f},{r['peak_bytes']},"
            f"{_cell(r['fastpath_frac'], '.4f')},"
            f"{_cell(r['mean_probe_len'], '.3f')},"
            f"{_cell(r['claim_rounds_p99'], '.1f')}"
        )
    # the maintenance trajectory, machine-readable (CI uploads it next to
    # the CSV artifact)
    maint_rows = [r for r in rows if r["engine"] == "maintenance"]
    with open("BENCH_maintenance.json", "w") as f:
        json.dump(
            {
                "bench": "graph_reachability/maintenance",
                "jax": jax.__version__,
                "backend": jax.default_backend(),
                "quick": quick,
                "rows": maint_rows,
            },
            f,
            indent=2,
        )
    print(f"# maintenance rows -> BENCH_maintenance.json ({len(maint_rows)} rows)",
          file=sys.stderr)
    # per-graph build telemetry (counters, claim-round + probe histograms,
    # phase spans), machine-readable — ``tools/obs_report.py`` renders it
    with open("BENCH_obs.json", "w") as f:
        json.dump(
            {
                "bench": "graph_reachability/obs",
                "jax": jax.__version__,
                "backend": jax.default_backend(),
                "quick": quick,
                "graphs": obs_dumps,
            },
            f,
            indent=2,
        )
    print(f"# build telemetry -> BENCH_obs.json ({len(obs_dumps)} graphs)",
          file=sys.stderr)
    return rows


if __name__ == "__main__":
    main()
