"""Bring-up smoke for one TPU v5e: the wait-free graph at LDBC SNB SF10 scale,
and the paged LM serving path it keeps the page table of.

    python chip_smoke.py               # phases `graph` and `serve`, one chip
    python chip_smoke.py --four-chips  # the sharded graph on four chips vs
                                       # one shard on one chip, nothing else

Phase `graph` loads a person-knows-person graph shaped like LDBC SNB
Interactive SF10 through ``WaitFreeGraph(mode="fpsp").apply`` (growing both
tables on the device), installs the loaded state in a ``mode="waitfree"``
graph, and runs the same churn and query mix through both.  Every success
bit and every query answer must equal ``SequentialGraph`` replaying the same
ops.  Phase `serve` runs ``ServingEngine`` at the published widths of
h2o-danube-3-4b with random bf16 weights built on the device.

The script needs a TPU: it exits non-zero, printing no result, when JAX finds
none.  The last line of its output is one JSON object naming the device.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

from repro.core.types import (  # noqa: E402
    OP_ADD_EDGE,
    OP_ADD_VERTEX,
    OP_CONTAINS_EDGE,
    OP_CONTAINS_VERTEX,
    OP_REMOVE_EDGE,
    OP_REMOVE_VERTEX,
)

SOURCE = (
    "LDBC SNB Interactive SF10 person-knows-person graph (LDBC SNB spec, "
    "scale factor table: 65,645 persons, 1,938,516 knows edges)"
)
CUTS = (
    "persons and knows only (no messages, forums, places or tags); knows "
    "stored in both directions; power-law degrees in place of the "
    "generator's correlated ones; person keys distinct random int32 in "
    "[0, 2**30)"
)


@dataclasses.dataclass(frozen=True)
class GraphScale:
    persons: int = 65_645
    knows: int = 1_938_516          # undirected; loaded as two directed edges
    load_batch: int = 65_536
    v_capacity: int = 2**17         # both tables grow on the device during
    e_capacity: int = 2**22         # the load (to 2**19 and 2**23 at seed 0)
    churn_batches: int = 16
    churn_batch: int = 4_096
    # each query round: reachable and get_path on this many pairs, bfs and
    # khop from this many sources; the sources of one batch are distinct
    query_sources: int = 8
    khop_k: int = 2


def log(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# data: a power-law knows graph and the op streams, all from the seed
# ---------------------------------------------------------------------------


def knows_graph(rng: np.random.Generator, scale: GraphScale):
    """(person keys, directed edge key pairs) — each knows edge both ways."""
    keys = rng.choice(2**30, scale.persons, replace=False).astype(np.int32)
    weight = rng.pareto(2.0, scale.persons) + 1.0  # power-law degree weights
    p = weight / weight.sum()
    codes = np.zeros(0, np.int64)
    while codes.size < scale.knows:
        n = int(1.2 * (scale.knows - codes.size)) + 64
        a = rng.choice(scale.persons, n, p=p)
        b = rng.choice(scale.persons, n, p=p)
        keep = a != b
        lo = np.minimum(a, b)[keep].astype(np.int64)
        hi = np.maximum(a, b)[keep].astype(np.int64)
        codes = np.unique(np.concatenate([codes, lo * scale.persons + hi]))
    codes = rng.permutation(codes)[: scale.knows]
    a, b = keys[codes // scale.persons], keys[codes % scale.persons]
    eu = np.concatenate([a, b])
    ev = np.concatenate([b, a])
    order = rng.permutation(eu.size)
    return keys, eu[order], ev[order]


def load_stream(rng, scale: GraphScale, keys, eu, ev):
    """Vertex adds, then edge adds, then contains ops up to a whole number
    of load batches (one batch shape, one compile)."""
    ops = np.concatenate(
        [np.full(keys.size, OP_ADD_VERTEX), np.full(eu.size, OP_ADD_EDGE)]
    ).astype(np.int32)
    us = np.concatenate([keys, eu]).astype(np.int32)
    vs = np.concatenate([np.zeros(keys.size, np.int32), ev]).astype(np.int32)
    fill = -ops.size % scale.load_batch
    f_ops = rng.choice([OP_CONTAINS_VERTEX, OP_CONTAINS_EDGE], fill).astype(np.int32)
    f_us = rng.choice(keys, fill).astype(np.int32)
    f_vs = np.where(rng.random(fill) < 0.5, rng.choice(keys, fill), rng.choice(ev, fill))
    return (
        np.concatenate([ops, f_ops]),
        np.concatenate([us, f_us]),
        np.concatenate([vs, f_vs.astype(np.int32)]),
    )


def churn_batch(rng, scale: GraphScale, keys, eu, ev, fresh):
    """One batch of the churn mix: edge adds and removes, vertex removes and
    re-adds (the incarnation hazard: a re-added person must not regain its
    old knows edges), contains.  Returns (ops, us, vs)."""
    n = scale.churn_batch
    n_vrm = n // 32
    gone = rng.choice(keys, n_vrm, replace=False)
    # tail: re-add the removed persons, bind fresh edges to them, and ask
    # for their old edges (must be gone)
    old = rng.choice(np.flatnonzero(np.isin(eu, gone)), n_vrm)
    tail_ops = np.concatenate(
        [
            np.full(n_vrm, OP_ADD_VERTEX),
            np.full(n_vrm, OP_ADD_EDGE),
            np.full(n_vrm, OP_CONTAINS_EDGE),
        ]
    )
    tail_us = np.concatenate([gone, gone, eu[old]])
    tail_vs = np.concatenate([np.zeros(n_vrm), rng.choice(keys, n_vrm), ev[old]])
    m = n - n_vrm - tail_ops.size - 2 * (n // 16)
    kinds = rng.choice(
        [OP_ADD_EDGE, OP_REMOVE_EDGE, OP_CONTAINS_EDGE, OP_CONTAINS_VERTEX], m,
        p=[0.35, 0.3, 0.2, 0.15],
    )
    pick = rng.integers(0, eu.size, m)
    body_us = np.where(kinds == OP_ADD_EDGE, rng.choice(keys, m), eu[pick])
    body_vs = np.where(kinds == OP_ADD_EDGE, rng.choice(keys, m), ev[pick])
    body_us = np.where(kinds == OP_CONTAINS_VERTEX, rng.choice(keys, m), body_us)
    # brand-new persons, each with one knows edge to an existing person
    new = fresh[: n // 16]
    new_ops = np.concatenate([np.full(new.size, OP_ADD_VERTEX), np.full(new.size, OP_ADD_EDGE)])
    new_us = np.concatenate([new, new])
    new_vs = np.concatenate([np.zeros(new.size), rng.choice(keys, new.size)])
    head_ops = np.concatenate([np.full(n_vrm, OP_REMOVE_VERTEX), kinds, new_ops])
    head_us = np.concatenate([gone, body_us, new_us])
    head_vs = np.concatenate([np.zeros(n_vrm), body_vs, new_vs])
    order = rng.permutation(head_ops.size)
    return (
        np.concatenate([head_ops[order], tail_ops]).astype(np.int32),
        np.concatenate([head_us[order], tail_us]).astype(np.int32),
        np.concatenate([head_vs[order], tail_vs]).astype(np.int32),
        new,
    )


# ---------------------------------------------------------------------------
# graph phase
# ---------------------------------------------------------------------------


def replay(oracle, ops, us, vs) -> np.ndarray:
    return np.fromiter(
        (oracle.apply(o, u, v) for o, u, v in zip(ops.tolist(), us.tolist(), vs.tolist())),
        bool, ops.size,
    )


class Timer:
    """Wall times per label; the first call of a label is reported apart
    (it compiles)."""

    def __init__(self):
        self.t = {}

    def __call__(self, label, fn, *args):
        t0 = time.perf_counter()
        out = fn(*args)
        self.t.setdefault(label, []).append(time.perf_counter() - t0)
        return out

    def report(self, prefix):
        for label, ts in self.t.items():
            rest = ts[1:]
            log(
                f"{prefix}: time {label}: first {ts[0]:.6f} s, "
                f"then median {np.median(rest) if rest else float('nan'):.6f} s "
                f"over {len(rest)} calls"
            )


def queries(rng, scale, keys, absent):
    """One round of query arguments: ``query_sources`` distinct sources,
    shared by the four query kinds (so the oracle runs one BFS per source),
    and a target per pair, one of them a key that was never added."""
    n = scale.query_sources
    src = rng.choice(keys, n, replace=False)
    reach_v = rng.choice(keys, n)
    reach_v[0] = rng.choice(absent)
    return dict(src=src, reach_v=reach_v, path_v=rng.choice(keys, n))


def ask(g, q, scale, timer, tag):
    """Every query of one round, answered by graph ``g`` (its snapshot
    refresh — the delta fold on one shard — timed apart, to its end)."""
    import jax

    n, k = scale.query_sources, scale.khop_k
    timer(f"{tag}.snapshot_refresh", lambda: jax.block_until_ready(g.traversal_csr()))
    return dict(
        reach=timer(f"{tag}.reachable[{n} pairs, {n} distinct sources]", g.reachable, q["src"], q["reach_v"]),
        bfs=timer(f"{tag}.bfs_batch[{n} sources]", g.bfs_batch, q["src"]),
        path=timer(f"{tag}.get_path_batch[{n} pairs, {n} distinct sources]", g.get_path_batch, q["src"], q["path_v"]),
        khop=timer(f"{tag}.khop_batch[{n} sources, k={k}]", g.khop_batch, q["src"], k),
    )


def oracle_levels(q, oracle):
    """The oracle's BFS level map from every source of one round (shared by
    the graphs that answer the round), and the seconds it took."""
    t0 = time.perf_counter()
    levels = {int(u): oracle.bfs(int(u)) for u in q["src"]}
    return levels, time.perf_counter() - t0


def check_answers(ans, q, oracle, levels, scale, tag):
    V = oracle.vertices
    want = np.array(
        [u in V and v in V and v in levels[u] for u, v in zip(q["src"].tolist(), q["reach_v"].tolist())]
    )
    assert np.array_equal(np.asarray(ans["reach"]), want), f"{tag}: reachable differs from the oracle"
    for u, got in zip(q["src"].tolist(), ans["bfs"]):
        assert got == levels[u], f"{tag}: bfs({u}) differs from the oracle"
    for u, v, p in zip(q["src"].tolist(), q["path_v"].tolist(), ans["path"]):
        d = levels[u].get(v) if v in V else None
        if d is None:
            assert p is None, f"{tag}: get_path({u}, {v}) found a path the oracle does not"
            continue
        assert p is not None and p[0] == u and p[-1] == v and len(p) == d + 1, (
            f"{tag}: get_path({u}, {v}) is not a shortest path"
        )
        assert all(oracle.contains_edge(a, b) for a, b in zip(p, p[1:])), (
            f"{tag}: get_path({u}, {v}) uses an edge the oracle lacks"
        )
    for u, got in zip(q["src"].tolist(), ans["khop"]):
        assert got == {w for w, d in levels[u].items() if d <= scale.khop_k}, (
            f"{tag}: khop({u}) differs from the oracle"
        )


def table_bytes(g) -> int:
    import jax

    leaves = jax.tree.leaves(g.state) + jax.tree.leaves(g.traversal_csr())
    return int(sum(x.nbytes for x in leaves))


def graph_phase(seed: int, scale: GraphScale = GraphScale()) -> None:
    import jax

    from repro.core import SequentialGraph, WaitFreeGraph, maintenance
    from repro.kernels.compact import ops as compact_ops
    from repro.kernels.frontier import ops as frontier_ops

    log(f"graph: source: {SOURCE}")
    log(f"graph: cuts: {CUTS}")
    rng = np.random.default_rng(seed)
    keys, eu, ev = knows_graph(rng, scale)
    ops, us, vs = load_stream(rng, scale, keys, eu, ev)
    log(
        f"graph: data: {keys.size} persons, {eu.size} directed knows edges, "
        f"{ops.size} load ops in batches of {scale.load_batch}"
    )

    g1 = WaitFreeGraph(scale.v_capacity, scale.e_capacity, mode="fpsp", obs=True)
    log(
        "graph: impl: frontier="
        f"{frontier_ops.resolve(g1.traversal_impl)} compact={compact_ops.resolve()} "
        f"delta_fold={maintenance.resolve_impl(g1.maintenance_impl)} "
        f"rehash={maintenance.resolve_impl(g1.maintenance_impl)}"
    )
    oracle = SequentialGraph()
    timer = Timer()
    t_load = 0.0
    for i in range(0, ops.size, scale.load_batch):
        sl = slice(i, i + scale.load_batch)
        t0 = time.perf_counter()
        got = timer("load.apply", g1.apply, ops[sl], us[sl], vs[sl])
        t_load += time.perf_counter() - t0
        want = replay(oracle, ops[sl], us[sl], vs[sl])
        assert np.array_equal(got, want), f"load batch {i // scale.load_batch}: success bits differ"
        if (i // scale.load_batch) % 16 == 0:
            log(f"graph: load batch {i // scale.load_batch}: {timer.t['load.apply'][-1]:.3f} s")
    st = g1.state
    log(
        f"graph: load: {ops.size // scale.load_batch} batches in {t_load:.3f} s "
        f"({ops.size / t_load:.1f} ops/s incl. compiles); tables now "
        f"v_capacity={st.v_capacity} e_capacity={st.e_capacity}; "
        f"growth events {g1.obs.counters().get('growth.events', 0)}"
    )
    assert st.v_capacity > scale.v_capacity and st.e_capacity > scale.e_capacity, (
        "the load did not grow both tables"
    )

    g2 = WaitFreeGraph(mode="waitfree", obs=True)
    g2.state = g1.state
    graphs = {"fpsp": g1, "waitfree": g2}

    absent = rng.integers(2**30, 2**31 - 1, 64).astype(np.int32)  # never added
    fresh = rng.choice(2**30, scale.churn_batches * scale.churn_batch, replace=False)
    fresh = np.setdiff1d(fresh, keys).astype(np.int32)
    rng.shuffle(fresh)
    n_checked = 0
    for b in range(scale.churn_batches):
        c_ops, c_us, c_vs, new = churn_batch(rng, scale, keys, eu, ev, fresh)
        fresh = fresh[new.size:]
        keys = np.concatenate([keys, new])
        want = replay(oracle, c_ops, c_us, c_vs)
        for tag, g in graphs.items():
            got = timer(f"{tag}.churn_apply", g.apply, c_ops, c_us, c_vs)
            assert np.array_equal(got, want), f"churn batch {b} ({tag}): success bits differ"
        q = queries(rng, scale, keys, absent)
        ans, t_ask = {}, {}
        with ThreadPoolExecutor(1) as pool:
            # the oracle's BFS (host Python) runs while the engines answer
            levels = pool.submit(oracle_levels, q, oracle)
            for tag, g in graphs.items():
                t0 = time.perf_counter()
                ans[tag] = ask(g, q, scale, timer, tag)
                t_ask[tag] = time.perf_counter() - t0
            levels, t_oracle = levels.result()
        for tag in graphs:
            t0 = time.perf_counter()
            check_answers(ans[tag], q, oracle, levels, scale, f"batch {b} {tag}")
            log(
                f"graph: round {b} {tag}: queries {t_ask[tag]:.3f} s, "
                f"check {time.perf_counter() - t0:.3f} s (oracle bfs {t_oracle:.3f} s, "
                "on a second thread)"
            )
        n_checked += 4 * scale.query_sources
    log(
        f"graph: churn: {scale.churn_batches} batches of {scale.churn_batch} ops through "
        f"both engines; every success bit and {n_checked} query answers per engine "
        "match SequentialGraph"
    )
    for tag, g in graphs.items():
        c = g.obs.counters()
        log(
            f"graph: {tag}: snapshot refreshes: delta folds {c.get('csr.delta.folded', 0)}, "
            f"rebuilds {c.get('csr.build', 0)}; rehashes {c.get('maintenance.rehash', 0)}"
        )
        # the first round builds the snapshot (or, after a device growth,
        # folds into the one the rehash pre-compacted); every later one folds
        builds, folds = c.get("csr.build", 0), c.get("csr.delta.folded", 0)
        assert builds <= 1 and builds + folds == scale.churn_batches, (
            f"{tag}: a snapshot refresh skipped the delta fold"
        )
    timer.report("graph")
    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    log(
        f"graph: table bytes (state + snapshot): fpsp {table_bytes(g1)}, "
        f"waitfree {table_bytes(g2)}; peak_bytes_in_use {stats.get('peak_bytes_in_use')}"
    )


# ---------------------------------------------------------------------------
# serve phase
# ---------------------------------------------------------------------------


def serve_phase(seed: int, cfg=None) -> None:
    """``cfg`` defaults to h2o-danube-3-4b at its published widths."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.models import LM
    from repro.models.module import param_bytes
    from repro.serving import Request, ServingEngine

    cfg = cfg or get_config("h2o-danube-3-4b")
    model = LM(cfg)
    t0 = time.perf_counter()
    params = jax.jit(model.init)(jax.random.key(seed))  # built on the device
    jax.block_until_ready(params)
    log(
        f"serve: {cfg.name} ({cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"d_ff {cfg.d_ff}, vocab {cfg.vocab}, {cfg.dtype}): {param_bytes(model.meta())} weight bytes "
        f"built on the device in {time.perf_counter() - t0:.3f} s"
    )
    eng = ServingEngine(cfg, params, max_batch=4, max_len=256, page_size=16, seed=seed)
    step = eng._step
    finite = []

    def checked_step(p, tokens, cache):
        logits, cache = step(p, tokens, cache)
        finite.append(jnp.all(jnp.isfinite(logits)))
        return logits, cache

    eng._step = checked_step
    rng = np.random.default_rng(seed)
    n_req, n_new = 8, 16
    for i in range(n_req):
        plen = int(rng.integers(16, 129))  # + n_new <= max_len
        prompt = rng.integers(0, cfg.vocab, plen).astype(np.int32)
        eng.submit(Request(id=i, prompt=prompt, max_new_tokens=n_new, temperature=0.0))
    t0 = time.perf_counter()
    done = eng.run()
    dt = time.perf_counter() - t0
    assert sorted(done) == list(range(n_req)), "not every request finished"
    for rid, req in done.items():
        assert len(req.generated) == n_new, f"request {rid}: {len(req.generated)} tokens"
        assert all(0 <= t < cfg.vocab for t in req.generated), f"request {rid}: token out of vocab"
    assert bool(jnp.all(jnp.stack(finite))), "non-finite logits"
    eng.failover()  # asserts the replayed page tables are identical
    stats = jax.devices()[0].memory_stats() or {}
    log(
        f"serve: {n_req} requests x {n_new} greedy tokens in {eng.ticks} ticks, "
        f"{dt:.3f} s wall incl. compile; logits finite on every tick; failover "
        f"rebuilt identical page tables; peak_bytes_in_use {stats.get('peak_bytes_in_use')}"
    )


# ---------------------------------------------------------------------------
# four-chip phase
# ---------------------------------------------------------------------------


def assert_shard_placement(g, mesh, when: str) -> None:
    import jax

    devs = list(mesh.devices.flat)
    for s, st in enumerate(g.shards):
        on = {d for leaf in jax.tree.leaves(st) for d in leaf.devices()}
        assert on == {devs[s]}, f"{when}: shard {s} lives on {on}, not {devs[s]}"
    log(f"four-chips: {when}: shard s lives on device s for s in 0..{len(g.shards) - 1}")


def four_chip_phase(seed: int, scale: GraphScale = GraphScale()) -> None:
    import jax

    from repro.core import WaitFreeGraph
    from repro.core.sharding import host_local_mesh

    mesh = host_local_mesh()
    assert mesh.devices.size == 4, f"--four-chips needs 4 devices, found {mesh.devices.size}"
    rng = np.random.default_rng(seed)
    keys, eu, ev = knows_graph(rng, scale)
    ops, us, vs = load_stream(rng, scale, keys, eu, ev)
    log(f"four-chips: source: {SOURCE}; cuts: {CUTS}")
    log(
        f"four-chips: data: {keys.size} persons, {eu.size} directed knows edges, "
        f"{ops.size} load ops in batches of {scale.load_batch}"
    )
    # the same total capacities as the 1-shard graph, so every shard grows
    g4 = WaitFreeGraph(
        scale.v_capacity, scale.e_capacity, mode="fpsp", n_shards=4, mesh=mesh, obs=True
    )
    g1 = WaitFreeGraph(scale.v_capacity, scale.e_capacity, mode="fpsp")
    timer = Timer()
    for i in range(0, ops.size, scale.load_batch):
        sl = slice(i, i + scale.load_batch)
        got4 = timer("load.apply.4shards", g4.apply, ops[sl], us[sl], vs[sl])
        got1 = timer("load.apply.1shard", g1.apply, ops[sl], us[sl], vs[sl])
        assert np.array_equal(got4, got1), f"load batch {i // scale.load_batch}: 4 shards differ"
        if (i // scale.load_batch) % 16 == 0:
            log(
                f"four-chips: load batch {i // scale.load_batch}: 4 shards "
                f"{timer.t['load.apply.4shards'][-1]:.3f} s, 1 shard "
                f"{timer.t['load.apply.1shard'][-1]:.3f} s"
            )
    grown = g4.obs.counters().get("growth.events", 0)
    assert grown > 0, "the sharded load did not grow"
    log(f"four-chips: load: success bits identical on every batch; sharded growth events {grown}")
    assert_shard_placement(g4, mesh, "after the load and its growth")
    absent = rng.integers(2**30, 2**31 - 1, 64).astype(np.int32)
    fresh = np.setdiff1d(
        rng.choice(2**30, scale.churn_batches * scale.churn_batch, replace=False), keys
    ).astype(np.int32)
    rng.shuffle(fresh)
    # a query round costs two full ones of the graph phase (plus the fused
    # snapshot's host rebuild), so the answers are compared after the first
    # and the last churn batch; success bits after every one
    asked = (0, scale.churn_batches - 1)
    for b in range(scale.churn_batches):
        c_ops, c_us, c_vs, new = churn_batch(rng, scale, keys, eu, ev, fresh)
        fresh = fresh[new.size:]
        keys = np.concatenate([keys, new])
        got4 = timer("churn.apply.4shards", g4.apply, c_ops, c_us, c_vs)
        got1 = timer("churn.apply.1shard", g1.apply, c_ops, c_us, c_vs)
        assert np.array_equal(got4, got1), f"churn batch {b}: 4 shards differ"
        if b not in asked:
            continue
        q = queries(rng, scale, keys, absent)
        t0 = time.perf_counter()
        a4 = ask(g4, q, scale, timer, "4shards")
        t1 = time.perf_counter()
        a1 = ask(g1, q, scale, timer, "1shard")
        assert np.array_equal(a4["reach"], a1["reach"]), f"batch {b}: reachable differs"
        for k in ("bfs", "path", "khop"):
            assert a4[k] == a1[k], f"batch {b}: {k} differs"
        log(
            f"four-chips: round {b}: queries 4 shards {t1 - t0:.3f} s, "
            f"1 shard {time.perf_counter() - t1:.3f} s; answers identical"
        )
    assert_shard_placement(g4, mesh, "after the churn")
    log(
        f"four-chips: churn: {scale.churn_batches} batches, success bits identical "
        f"on every one; every reachable/bfs/get_path/khop answer after batches "
        f"{asked[0]} and {asked[1]} identical to the 1-shard graph"
    )
    timer.report("four-chips")


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded graph on four chips and its 1-shard comparison")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    for var in ("REPRO_FRONTIER_IMPL", "REPRO_COMPACT_IMPL"):
        if os.environ.get(var):
            print(f"chip_smoke: {var} is set; the smoke runs the dispatch as shipped", file=sys.stderr)
            return 2

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform}); nothing was run", file=sys.stderr)
        return 1

    from repro.launch.compile_cache import enable_compile_cache

    log(f"device: {dev.device_kind} x {len(jax.devices())}; compile cache {enable_compile_cache()}")
    t0 = time.perf_counter()
    if args.four_chips:
        four_chip_phase(args.seed)
    else:
        graph_phase(args.seed)
        serve_phase(args.seed)
    log(f"total: {time.perf_counter() - t0:.3f} s")
    print(json.dumps({
        "ok": True,
        "device": {"platform": dev.platform, "kind": dev.device_kind, "count": len(jax.devices())},
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
