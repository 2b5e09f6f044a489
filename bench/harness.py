"""One run of one cell: set-up, the measured window, the trace, the check.

Everything a cell needs is found by name: the configuration's file (from
``BENCHMARK.json``), its data generator (``bench/datagen/<generator>.py``),
the traffic mix (``bench/traffic/<traffic>.json``), each request kind the mix
lists (``bench/steps/<kind>.py``) and each per-layer metric's reader
(``bench/layer_metrics/<metric>.py``).

The window drives the public ``WaitFreeGraph`` API, built with its default
engine and implementation arguments and only the table capacities of the
configuration.  It is a closed loop with one client: each round issues one
request of every step of the mix in order, the next only when the previous
one has answered, until ``seconds`` have passed; the request under way then
finishes, and the window ends with it.  A request's latency runs from its
issue until its answers are on the host; each op or query of a request has
the request's latency.  The window keeps only the answers the check will
compare: every answer of a step whose ``check_sample`` is None, else a
sample of that many drawn from the seed as the answers come.
"""

from __future__ import annotations

import gc
import importlib
import importlib.util
import json
import os
import sys
import tempfile
import time
from types import SimpleNamespace

import numpy as np

from bench import peaks as peaks_table
from bench import trace_reduce
from bench.reference import ReferenceGraph
from repro.core import WaitFreeGraph

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def cell_files(spec: dict, workload: str):
    """(cell, configuration, traffic mix) of the cell named ``workload``."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    cfg = load_json(os.path.join(ROOT, entry["file"]))
    traffic = load_json(os.path.join(BENCH_DIR, "traffic", cell["traffic"] + ".json"))
    return cell, cfg, traffic


def load_reader(name: str):
    path = os.path.join(BENCH_DIR, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_layer_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(spec: dict, cell: dict, kind: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics this cell reports."""
    e2e = [m for m in spec["end_to_end"] if cell["name"] in m.get("workloads", [cell["name"]])]
    if kind == "end_to_end":
        return e2e
    names = {m["name"] for m in e2e}
    return [
        m
        for m in spec["per_layer"]
        if cell["name"] in m.get("workloads", [cell["name"]] if m["moves"] in names else [])
    ]


def percentile_over(lat: np.ndarray, sizes: np.ndarray, q: float) -> float:
    """The ``q``-th percentile over every op or query, each with the latency
    of the request it came in."""
    return float(np.percentile(np.repeat(lat, sizes), q))


class Reservoir:
    """A uniform sample of ``k`` of the records offered to it, drawn from
    ``rng`` as they come (Algorithm R).  A record is ``[step, request,
    answer]``; the answer of a record that leaves or never joins the sample
    is dropped at once, so the window holds at most ``k`` answers."""

    def __init__(self, k: int, rng: np.random.Generator):
        self.k, self.rng, self.n, self.kept = k, rng, 0, []

    def offer(self, rec: list) -> None:
        self.n += 1
        if len(self.kept) < self.k:
            self.kept.append(rec)
            return
        j = int(self.rng.integers(self.n))
        if j < self.k:
            self.kept[j][2] = None
            self.kept[j] = rec
        else:
            rec[2] = None


class CompileCounter:
    """Counts the programs JAX traces and compiles while it is on."""

    def __init__(self):
        import jax

        self.on = False
        self.traced = 0
        self.compiled = 0

        def listener(name, secs, **kw):
            if self.on:
                if name == "/jax/core/compile/jaxpr_trace_duration":
                    self.traced += 1
                elif name == "/jax/core/compile/backend_compile_duration":
                    self.compiled += 1

        jax.monitoring.register_event_duration_secs_listener(listener)


def run_cell(
    spec: dict, cell: dict, cfg: dict, traffic: dict, seed: int, seconds: float, trace: bool, t_start: float
) -> dict:
    """One run of ``cell`` (its configuration ``cfg`` and mix ``traffic``
    as read by :func:`cell_files`); returns the result object."""
    import jax

    rng = np.random.default_rng(seed)
    datagen = importlib.import_module(f"bench.datagen.{cfg['generator']}")
    data = datagen.build(cfg, traffic["load_batch"], rng)
    steps = [importlib.import_module(f"bench.steps.{p['kind']}").Step(p, data, rng) for p in traffic["steps"]]

    # -- set-up: the load through apply, then this cell's shapes ----------
    g = WaitFreeGraph(cfg["v_capacity"], cfg["e_capacity"])
    l_ops, l_us, l_vs = data["load"]
    b = traffic["load_batch"]
    load_bits = [g.apply(l_ops[i : i + b], l_us[i : i + b], l_vs[i : i + b]) for i in range(0, l_ops.size, b)]
    caps = (g.state.v_capacity, g.state.e_capacity)
    if caps != (cfg["v_capacity"], cfg["e_capacity"]):
        log(f"setup: the load grew the tables to v_capacity={caps[0]} e_capacity={caps[1]}")
    for step in steps:
        step.warm(g, WaitFreeGraph)
    counter = CompileCounter()
    samples = [
        None if step.check_sample is None else Reservoir(step.check_sample, np.random.default_rng([seed, 1, i]))
        for i, step in enumerate(steps)
    ]
    # what set-up made stays alive through the window: keep it out of the
    # collector's full passes there
    gc.collect()
    gc.freeze()
    setup_s = time.perf_counter() - t_start

    # -- the window ----------------------------------------------------------
    records = []  # [step index, request, answer or None where not compared]
    lat, sizes, kinds = [], [], []
    growth = []
    tmp = tempfile.TemporaryDirectory() if trace else None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(tmp.name, profiler_options=opts)
    counter.on = True
    gc_before = [g_["collections"] for g_ in gc.get_stats()]
    w0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
        while time.perf_counter() - w0 < seconds:
            for i, step in enumerate(steps):
                with jax.profiler.TraceAnnotation("bench.generate"):
                    req = step.next()
                with jax.profiler.TraceAnnotation(step.span):
                    t0 = time.perf_counter()
                    ans = step.issue(g, req)
                    t1 = time.perf_counter()
                with jax.profiler.TraceAnnotation("bench.record"):
                    rec = [i, req, ans]
                    records.append(rec)
                    if samples[i] is not None:
                        samples[i].offer(rec)
                    del ans
                    lat.append(t1 - t0)
                    sizes.append(step.size(req))
                    kinds.append(i)
                    now = (g.state.v_capacity, g.state.e_capacity)
                    if now != caps:
                        growth.append((len(records) - 1, now))
                        caps = now
    window_s = time.perf_counter() - w0
    counter.on = False
    gc_runs = [g_["collections"] - b for g_, b in zip(gc.get_stats(), gc_before)]
    gc.unfreeze()
    t_trace = time.perf_counter()
    if trace:
        jax.profiler.stop_trace()
        log(f"trace: stopped and written in {time.perf_counter() - t_trace:.3f} s")
    log(
        f"window: {window_s:.6f} s, {len(records)} requests; programs traced in the window "
        f"{counter.traced}, compiled {counter.compiled}; table growths in the window "
        + (", ".join(f"after request {r} to v={v} e={e}" for r, (v, e) in growth) or "none")
        + f"; collector passes by generation {gc_runs}"
    )
    # where the window's time went, request by request: a rate that moves
    # while the tail holds points at a few long requests
    lat_w, kinds_w = np.asarray(lat), np.asarray(kinds)
    for i, step in enumerate(steps):
        x = np.sort(lat_w[kinds_w == i]) * 1e3
        if x.size:
            q50, q95, q99 = np.percentile(x, [50, 95, 99])
            log(
                f"latency {step.span}: {x.size} requests in {x.sum() * 1e-3:.6f} s; ms p50 {q50:.4f} p95 {q95:.4f} "
                f"p99 {q99:.4f} max {x[-1]:.4f}; above p99 {x[x > q99].sum() * 1e-3:.6f} s; "
                f"above 2 x p50: {np.count_nonzero(x > 2 * q50)} requests, {x[x > 2 * q50].sum() * 1e-3:.6f} s"
            )
    devices = jax.devices()[: cell["chips"]]
    mem_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices)
    reduced = trace_reduce.read(tmp.name) if trace else None
    if tmp is not None:
        tmp.cleanup()
        log(f"trace: stopped, written and reduced in {time.perf_counter() - t_trace:.3f} s")

    # -- the check, once the device state is freed -------------------------
    t_check = time.perf_counter()
    store_v, store_e = g.snapshot()
    del g
    gc.collect()
    ref = ReferenceGraph()
    checks = {}
    want = np.asarray(ref.apply_all(l_ops, l_us, l_vs), bool)
    checks["load_bits_wrong"] = int(np.count_nonzero(want != np.concatenate(load_bits)))
    seen = [0] * len(steps)
    compared = {}
    least_bytes: dict[str, dict[int, int]] = {}
    for i, req, ans in records:
        step = steps[i]
        k = seen[i]
        seen[i] += 1
        got = step.replay(ref, req, ans, ans is not None)
        if got:
            checks[step.check] = checks.get(step.check, 0) + got["wrong"]
            compared[step.check] = compared.get(step.check, 0) + got["compared"]
            if "least_bytes" in got:
                least_bytes.setdefault(step.span, {})[k] = got["least_bytes"]
    checks["state_wrong"] = len(store_v ^ ref.vertices()) + len(store_e ^ ref.edges())
    log(
        f"check: {time.perf_counter() - t_check:.3f} s; compared "
        + ", ".join(f"{k} over {v}" for k, v in compared.items())
        + f"; load ops {l_ops.size}; live vertices {len(ref.out)}"
    )

    # -- the result ---------------------------------------------------------
    lat_a, sizes_a, kinds_a = np.asarray(lat), np.asarray(sizes), np.asarray(kinds)
    values = {"setup_s": setup_s}
    for i, step in enumerate(steps):
        sel = kinds_a == i
        values[step.rate_metric] = int(sizes_a[sel].sum()) / window_s
        values[step.p95_metric] = percentile_over(lat_a[sel], sizes_a[sel], 95) * 1e3
    dev0 = devices[0]
    device = {
        "platform": dev0.platform,
        "kind": dev0.device_kind,
        "count": len(devices),
        "memory_peak_bytes": int(mem_peak),
    }
    metrics = {}
    breakdown = None
    if not trace:
        for m in cell_metrics(spec, cell, "end_to_end"):
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}
    else:
        ctx = SimpleNamespace(
            trace=reduced, least_bytes=least_bytes, peaks=peaks_table.peaks(dev0.device_kind)
        )
        for m in cell_metrics(spec, cell, "per_layer"):
            v = load_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        device.update(busy_s=reduced.busy_ns() * 1e-9, window_s=reduced.window_ns * 1e-9)
        breakdown = {"device_ops": reduced.top_ops(10), "idle_gaps": reduced.idle_by_span(10)}
        log(f"trace: busy {device['busy_s']:.6f} s of {device['window_s']:.6f} s")
    attempted = int(sizes_a.sum())
    limits = {k: 0 for k in checks}  # exact comparisons
    correct = all(v <= limits[k] for k, v in checks.items())
    for k, v in checks.items():
        log(f"check {k}: {v} (limit {limits[k]})")
    result = {
        "correct": correct,
        "attempted": attempted,
        "failed": 0,
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {k: {"value": v, "limit": limits[k]} for k, v in checks.items()}
    return result
