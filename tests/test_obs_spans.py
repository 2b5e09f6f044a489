"""The program's spans on the profiler's clock, and its device scopes.

``repro.obs`` spans are ``jax.profiler`` annotations whether or not a
registry is enabled: a profiler session sees ``graph.apply`` and
``graph.bfs_batch`` split into child spans that follow one another inside
their parent, and the jitted programs name their device ops by engine wave
and by frontier level (``repro.obs.DEVICE_SCOPES``).
"""

from __future__ import annotations

import glob
import os
import re
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core import WaitFreeGraph, engine, fastpath, traversal
from repro.core.types import (
    OP_ADD_EDGE,
    OP_ADD_VERTEX,
    OP_CONTAINS_EDGE,
    make_batch,
    make_state,
)
from repro.obs import DEVICE_SCOPES, NOOP, SPAN_PREFIXES, Registry

APPLY_CHILDREN = ["prepare", "dispatch", "wait", "growth_check", "readback"]
BFS_CHILDREN = ["snapshot", "dispatch", "readback", "to_dicts"]
# the most a gap between one child span and the next may last: a few Python
# statements, with room for a loaded test machine
MAX_GAP_NS = 5e6


def _host_spans(trace_dir: str) -> list:
    """[(name, start_ns, end_ns)] of the program's spans, by start."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIXES):
                    out.append((ev.name, ev.start_ns, ev.end_ns))
    return sorted(out, key=lambda x: x[1])


def _traced(tmp_path, fn) -> list:
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    return _host_spans(str(tmp_path))


def _children(spans, parent) -> list:
    """The direct children of ``parent`` (the spans named ``<parent>.*``
    inside it), checked to follow one another with no overlap."""
    name, a, b = parent
    kids = [s for s in spans if s[0].startswith(name + ".") and a <= s[1] and s[2] <= b]
    edges = [a] + [x for _, s, e in kids for x in (s, e)] + [b]
    gaps = np.diff(edges)[::2]
    assert np.all(np.diff(edges) >= 0), f"children of {name} overlap or leave it"
    assert gaps.max() < MAX_GAP_NS, f"a gap of {gaps.max()} ns between children of {name}"
    return kids


def _batch(rng, keys, n=8):
    ops = np.full(n, OP_ADD_EDGE, np.int32)
    ops[::2] = OP_CONTAINS_EDGE
    return ops, rng.choice(keys, n).astype(np.int32), rng.choice(keys, n).astype(np.int32)


@pytest.mark.parametrize("obs", [False, True])
def test_apply_and_bfs_batch_spans_nest_and_follow_one_another(tmp_path, obs):
    rng = np.random.default_rng(0)
    keys = np.arange(40, dtype=np.int32)
    g = WaitFreeGraph(128, 512, obs=obs)
    g.apply(np.full(keys.size, OP_ADD_VERTEX, np.int32), keys)
    g.apply(*_batch(rng, keys))  # compiles the traced shapes first
    g.bfs_batch(keys[:4])

    def work():
        for _ in range(2):
            g.apply(*_batch(rng, keys))
            g.bfs_batch(keys[:4])

    spans = _traced(tmp_path, work)
    applies = [s for s in spans if s[0] == "graph.apply"]
    calls = [s for s in spans if s[0] == "graph.bfs_batch"]
    assert len(applies) == 2 and len(calls) == 2
    for parent in applies:
        kids = _children(spans, parent)
        assert [k[0] for k in kids] == [f"graph.apply.{c}" for c in APPLY_CHILDREN]
    for parent in calls:
        kids = [k for k in _children(spans, parent) if k[0].count(".") == 2]
        assert [k[0] for k in kids] == [f"graph.bfs_batch.{c}" for c in BFS_CHILDREN]
    # the snapshot folds the batch before it; the fold's span nests in it
    snap = [s for s in spans if s[0] == "graph.bfs_batch.snapshot"][0]
    folds = [s for s in spans if s[0] == "csr.delta_fold"]
    assert folds and snap[1] <= folds[0][1] and folds[0][2] <= snap[2]
    if obs:
        done = g.obs.dump()["spans"]
        for c in APPLY_CHILDREN:
            assert done[f"graph.apply.{c}"]["count"] == 4
        for c in BFS_CHILDREN:
            assert done[f"graph.bfs_batch.{c}"]["count"] == 3
    else:
        assert g.obs is NOOP and g.obs.dump() == {"schema": "repro-obs/1", "enabled": False}


def test_growth_retry_shows_as_a_second_dispatch_and_wait(tmp_path):
    g = WaitFreeGraph(64, 256, obs=False, maintenance_impl="host")
    keys = np.arange(100, dtype=np.int32)
    spans = _traced(tmp_path, lambda: g.apply(np.full(keys.size, OP_ADD_VERTEX, np.int32), keys))
    (parent,) = [s for s in spans if s[0] == "graph.apply"]
    kids = [k[0].rsplit(".", 1)[1] for k in _children(spans, parent) if k[0].count(".") == 2]
    # 100 keys outgrow 64 slots: one growth or more, each followed by a retry
    assert kids[:3] == ["prepare", "dispatch", "wait"]
    assert kids[-4:] == ["dispatch", "wait", "growth_check", "readback"]
    assert kids.count("grow") >= 1 and kids.count("dispatch") == kids.count("grow") + 1
    grows = [s for s in spans if s[0] == "graph.apply.grow"]
    rehash = [s for s in spans if s[0] == "maintenance.rehash.host"]
    assert len(rehash) >= len(grows)
    assert all(any(g_[1] <= r[1] and r[2] <= g_[2] for g_ in grows) for r in rehash)


def test_span_names_use_the_exported_prefixes_and_are_documented():
    """Every span a dense and a sharded graph open starts with one of
    ``SPAN_PREFIXES`` and is in the catalog of docs/OBSERVABILITY.md."""
    doc = (Path(__file__).resolve().parent.parent / "docs" / "OBSERVABILITY.md").read_text()
    documented = set(re.findall(r"`([a-z_]+(?:\.[a-z_<>]+)+)`", doc))
    keys = np.arange(30, dtype=np.int32)
    rng = np.random.default_rng(1)
    names = set()
    for n_shards in (1, 2):
        g = WaitFreeGraph(64, 256, obs=True, n_shards=n_shards)
        g.apply(np.full(keys.size, OP_ADD_VERTEX, np.int32), keys)
        g.apply(*_batch(rng, keys))
        g.bfs_batch(keys[:2])
        names |= set(g.obs.dump()["spans"])
    assert {"graph.apply.readback", "graph.bfs_batch.to_dicts", "phase.route"} <= names
    for name in names:
        assert name.startswith(SPAN_PREFIXES), name
        assert re.sub(r"\.(host|device|device_interpret)$", ".<impl>", name) in documented, name


def test_disabled_registry_spans_are_profiler_annotations_only():
    assert isinstance(NOOP.span("graph.apply"), jax.profiler.TraceAnnotation)
    reg = Registry()
    with reg.span("graph.apply"):
        pass
    assert reg.dump()["spans"]["graph.apply"]["count"] == 1
    assert "samples" not in reg.dump() and not hasattr(reg, "observe")


def _programs():
    state = make_state(64, 256)
    z = np.zeros(64, np.int32)
    batch = make_batch(z, z, z, phase_base=0)
    csr = traversal.build_csr(state)
    src = np.zeros(16, np.int32)
    return {
        "apply_batch": (engine.apply_batch, (state, batch), DEVICE_SCOPES[:3]),
        "apply_batch_fpsp": (fastpath.apply_batch_fpsp, (state, batch), DEVICE_SCOPES[:3]),
        "bfs_levels": (traversal.bfs_levels, (csr, src), DEVICE_SCOPES[3:]),
    }


@pytest.mark.parametrize("program", ["apply_batch", "apply_batch_fpsp", "bfs_levels"])
def test_lowered_programs_carry_the_device_scopes(program):
    fn, args, scopes = _programs()[program]
    text = fn.lower(*args).as_text(debug_info=True)
    for scope in scopes:
        assert f"/{scope}/" in text, f"{program} has no op under {scope}"
