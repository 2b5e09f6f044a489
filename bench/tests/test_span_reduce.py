"""The program's spans and device scopes in a trace (``bench/spans.py``):
idle time split by the innermost open span, checked on synthetic nested
spans; the benchmark's six first readings unchanged on the trace recorded
before the program had spans; and the new readings on a small trace
recorded on one TPU v5e with the program's spans (``record_span_trace.py``)."""

import json
import os
from types import SimpleNamespace

import numpy as np
import pytest

from bench import harness, spans, trace_reduce
from bench import peaks as peaks_table

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
OLD_TRACE = os.path.join(DATA, "small_trace.xplane.pb")
SPAN_TRACE = os.path.join(DATA, "span_trace.xplane.pb")
SPAN_SCOPES = os.path.join(DATA, "span_trace.scopes.json")

APPLY_CHILDREN = ("prepare", "dispatch", "wait", "growth_check", "readback")


def _synthetic() -> spans.SpanTrace:
    """One device busy over [100, 200], [600, 700] and [750, 800] of a
    window [0, 1000]; a request whose program spans nest in its ``bench.``
    span, the first child starting with its parent."""
    ops = {
        "/device:TPU:0": (
            ["%a = x", "%b = x", "%c = x"],
            np.array([100.0, 600.0, 750.0]),
            np.array([200.0, 700.0, 800.0]),
        )
    }
    modules = {"/device:TPU:0": (["jit_f(1)", "jit_g(2)"], np.array([100.0, 600.0]), np.array([200.0, 800.0]))}
    s = {
        "bench.window": [(0.0, 1000.0)],
        "bench.generate": [(0.0, 50.0)],
        "bench.apply": [(50.0, 900.0)],
        "bench.record": [(900.0, 1000.0)],
        "graph.apply": [(60.0, 890.0)],
        "graph.apply.prepare": [(60.0, 150.0)],
        "graph.apply.dispatch": [(150.0, 160.0)],
        "graph.apply.wait": [(160.0, 700.0)],
        "graph.apply.growth_check": [(700.0, 800.0)],
        "graph.apply.readback": [(800.0, 890.0)],
    }
    return spans.SpanTrace(ops, s, modules)


def test_idle_goes_to_the_innermost_open_span():
    t = _synthetic()
    idle = {n: x * 1e9 for n, x in t.idle_by_span(100) if x > 0}
    assert idle == pytest.approx(
        {
            "bench.generate": 50,
            "bench.apply": 20,  # before graph.apply opens, and after it closes
            "graph.apply.prepare": 40,
            "graph.apply.wait": 400,
            "graph.apply.growth_check": 50,
            "graph.apply.readback": 90,
            "bench.record": 100,
        }
    )
    assert "graph.apply.dispatch" not in idle and "graph.apply" not in idle  # no idle there
    assert sum(idle.values()) == pytest.approx(t.window_ns - t.busy_ns())
    # the pieces of the window follow one another and cover it
    seg = t.segments()
    assert seg[0][1] == 0 and seg[-1][2] == 1000
    assert all(a[2] == b[1] for a, b in zip(seg, seg[1:]))


def test_children_idle_and_the_rest_sum_to_the_requests_idle():
    t = _synthetic()
    kids = [f"graph.apply.{c}" for c in APPLY_CHILDREN]
    assert [n for n, _, _ in t.children("graph.apply", 60, 890)] == kids
    per_child = [sum(t.idle_within(n)) for n in kids]
    assert per_child == pytest.approx([40, 0, 400, 50, 90])
    outside = sum(
        x * 1e9 for n, x in t.idle_by_span(100) if n in ("bench.apply", "graph.apply")
    )
    assert sum(per_child) + outside == pytest.approx(sum(t.idle_within("bench.apply")))
    (longest,) = t.longest("graph.apply", 5)
    assert longest["wall_ms"] == pytest.approx(830e-6)
    assert longest["busy_ms"] == pytest.approx(250e-6)
    assert [c[0] for c in longest["children"]] == kids
    assert longest["outside_children_ms"] == pytest.approx(0)


def test_programs_and_scopes_per_request():
    t = _synthetic()
    assert spans.programs_per_span(t, "bench.apply") == 2.0
    assert spans.programs_per_span(t, "bench.query") is None
    mapping = {("jit_f", "%a"): "engine.vertex_wave", ("jit_g", "%b"): "engine.edge_wave", ("jit_g", "%c"): "engine.edge_wave"}
    labels = spans.op_scopes(t, mapping)
    assert spans.scoped_busy(t, labels, "engine.vertex_wave", "bench.apply") == [100.0]
    assert spans.scoped_busy(t, labels, "engine.edge_wave", "bench.apply") == [150.0]
    assert spans.scoped_busy(t, labels, "engine.stab_wave", "bench.apply") == []
    assert spans.scoped_runs(t, labels, "engine.edge_wave", "bench.apply") == 1
    assert spans.op_scopes(t, {}) == {}


def test_hlo_text_maps_instructions_to_their_innermost_scope():
    text = "\n".join(
        [
            "HloModule jit_apply_batch, is_scheduled=true",
            '  %fusion.1 = s32[8]{0} fusion(%p), kind=kLoop, calls=%f, metadata={op_type="sort" '
            'op_name="jit(apply_batch)/engine.vertex_wave/sort" source_file="x.py"}',
            '  ROOT %copy.2 = s32[8]{0} copy(%fusion.1), metadata={op_name="jit(f)/while/body/'
            'traversal.frontier_expand/jit(g)/traversal.level_update/add"}',
            "  %param.3 = s32[8]{0} parameter(0)",
        ]
    )
    scopes = ("engine.vertex_wave", "traversal.frontier_expand", "traversal.level_update")
    assert spans.hlo_scopes(text, scopes) == {
        "%fusion.1": "engine.vertex_wave",
        "%copy.2": "traversal.level_update",
        "%param.3": None,
    }


# -- the trace recorded before the program had spans ------------------------------

# the six readings as the benchmark read them before this module existed
FIRST_READINGS = {
    "apply_device_ms": 2.79552475,
    "apply_host_ms": 7.036255,
    "traversal_device_ms": 12.875757,
    "traversal_roofline": 19.614056806994114,
    "device_idle_share.update": 99.41180127603671,
    "device_idle_share.query": 99.41180127603671,
}


@pytest.fixture(scope="module")
def old_trace():
    from jax.profiler import ProfileData

    return ProfileData.from_file(OLD_TRACE)


def _ctx(trace):
    return SimpleNamespace(
        trace=trace,
        least_bytes={"bench.query": {0: 10**9, 2: 3 * 10**9}},
        peaks=peaks_table.peaks("TPU v5 lite"),
    )


@pytest.mark.parametrize("reduction", ["trace_reduce", "spans"])
@pytest.mark.parametrize("metric", sorted(FIRST_READINGS))
def test_first_readings_do_not_move(old_trace, reduction, metric):
    reduce = trace_reduce.reduce if reduction == "trace_reduce" else spans.reduce
    got = harness.load_reader(metric)(_ctx(reduce(old_trace)))
    assert got == pytest.approx(FIRST_READINGS[metric], rel=1e-12)


def test_without_program_spans_the_idle_split_is_the_old_one(old_trace):
    assert spans.reduce(old_trace).idle_by_span(10) == trace_reduce.reduce(old_trace).idle_by_span(10)


# -- the trace recorded with the program's spans ------------------------------------


@pytest.fixture(scope="module")
def span_trace():
    from jax.profiler import ProfileData

    t = spans.reduce(ProfileData.from_file(SPAN_TRACE))
    with open(SPAN_SCOPES) as f:
        mapping = {(m, op): sc for m, ops in json.load(f).items() for op, sc in ops.items()}
    return t, spans.op_scopes(t, mapping)


def test_recorded_span_trace_is_small():
    assert os.path.getsize(SPAN_TRACE) < 512 * 1024


def test_apply_idle_by_child_span_adds_up_to_apply_host_ms(span_trace):
    t, _ = span_trace
    n = len(t.durations("bench.apply"))
    assert n == 4 and len(t.durations("graph.apply")) == 4
    per_child = {c: sum(t.idle_within(f"graph.apply.{c}")) / n * 1e-6 for c in APPLY_CHILDREN}
    assert all(x >= 0 for x in per_child.values()) and per_child["wait"] > 0
    # the rest: idle time whose innermost span is the request's or the call's own
    outside = sum(
        x for name, a, b in t.segments() if name in ("bench.apply", "graph.apply")
        for x in [sum(trace_reduce.covered(s, e, a, b) for s, e in t._idle()) / n * 1e-6]
    )
    host_ms = harness.load_reader("apply_host_ms")(_ctx(t))
    assert sum(per_child.values()) + outside == pytest.approx(host_ms, rel=1e-9)
    assert outside < 0.1 * host_ms


def test_bfs_batch_spans_cover_the_query(span_trace):
    t, _ = span_trace
    for parent in t.spans["graph.bfs_batch"]:
        kids = [n.rsplit(".", 1)[1] for n, _, _ in t.children("graph.bfs_batch", *parent)]
        assert kids == ["snapshot", "dispatch", "readback", "to_dicts"]
    idle = dict(t.idle_by_span(100))
    assert idle.get("graph.bfs_batch.readback", 0) > 0


def test_engine_waves_lie_within_apply_device_time(span_trace):
    t, labels = span_trace
    ctx = _ctx(t)
    device = harness.load_reader("apply_device_ms")(ctx)
    waves = []
    for scope in ("engine.vertex_wave", "engine.stab_wave", "engine.edge_wave"):
        busy = spans.scoped_busy(t, labels, scope, "bench.apply")
        assert len(busy) == 4 and min(busy) > 0
        waves.append(sum(busy) / 4 * 1e-6)
    assert sum(waves) <= device * (1 + 1e-9)
    # the recorded window also holds queries, and their programs: the count
    # per batch is meant for a cell of batches alone
    (modules,) = t.modules.values()
    assert harness.load_reader("apply_programs")(ctx) == len(modules[0]) / 4


def test_frontier_expansions_are_counted_from_the_trace(span_trace):
    t, labels = span_trace
    expand = spans.scoped_busy(t, labels, "traversal.frontier_expand", "bench.query")
    update = spans.scoped_busy(t, labels, "traversal.level_update", "bench.query")
    runs = spans.scoped_runs(t, labels, "traversal.level_update", "bench.query")
    assert len(expand) == 4 and min(expand) > 0 and min(update) > 0
    # every call expands at least once per level its deepest source reached
    assert runs >= 4
    device = sum(t.busy_within("bench.query"))
    assert sum(expand) + sum(update) <= device * (1 + 1e-9)


def _compiled(text: str) -> SimpleNamespace:
    """A stand-in for a jitted function whose compiled text is ``text``."""
    compiled = SimpleNamespace(as_text=lambda: text)
    return SimpleNamespace(lower=lambda *args: SimpleNamespace(compile=lambda: compiled))


def _module_text(t: spans.SpanTrace, module: str, mapping: dict, drop: int = 0) -> str:
    """Compiled text holding every op the trace shows for ``module`` (less
    the first ``drop``), each with the scope ``mapping`` gives it."""
    ops = sorted({op for ks in spans._op_keys(t).values() for m, op in ks if m == module})[drop:]
    lines = [f"HloModule {module}, is_scheduled=true"]
    for op in ops:
        scope = mapping.get((module, op))
        meta = f', metadata={{op_name="{module}/{scope}/x"}}' if scope else ""
        lines.append(f"  {op} = s32[8]{{0}} fusion(%p){meta}")
    return "\n".join(lines)


def test_program_scopes_maps_the_recorded_programs(span_trace, monkeypatch, capsys):
    t, _ = span_trace
    with open(SPAN_SCOPES) as f:
        mapping = {(m, op): sc for m, ops in json.load(f).items() for op, sc in ops.items()}
    modules = ("jit_apply_batch", "jit_bfs_levels")
    programs = [(m, _compiled(_module_text(t, m, mapping)), ()) for m in modules]
    monkeypatch.setattr(spans, "_candidates", lambda wanted: [p for p in programs if p[0] in wanted])
    assert spans.program_scopes(t) == mapping
    assert capsys.readouterr().err == ""


def test_program_scopes_says_when_a_program_does_not_match(span_trace, monkeypatch, capsys):
    t, _ = span_trace
    with open(SPAN_SCOPES) as f:
        mapping = {(m, op): sc for m, ops in json.load(f).items() for op, sc in ops.items()}
    apply_text = _module_text(t, "jit_apply_batch", mapping)
    programs = [
        ("jit_apply_batch", _compiled(apply_text), ()),
        ("jit_apply_batch", _compiled(apply_text), ()),
        # compiled at other shapes: one op the trace shows is missing
        ("jit_bfs_levels", _compiled(_module_text(t, "jit_bfs_levels", mapping, drop=1)), ()),
    ]
    monkeypatch.setattr(spans, "_candidates", lambda wanted: programs)
    got = spans.program_scopes(t)
    assert got == {k: v for k, v in mapping.items() if k[0] == "jit_apply_batch"}
    err = capsys.readouterr().err
    assert "none of the 1 programs compiled for jit_bfs_levels" in err
    assert "2 programs compiled for jit_apply_batch" in err


def test_clock_offset_bounds_the_device_against_the_host():
    # the device shows the program 1 early against its dispatch span, and
    # ending 3 before the span that reads it closes
    ops = {"/device:TPU:0": (["%a = x"], np.array([100.0]), np.array([200.0]))}
    modules = {"/device:TPU:0": (["jit_apply_batch(1)"], np.array([100.0]), np.array([200.0]))}
    s = {
        "bench.window": [(0.0, 1000.0)],
        "graph.apply.dispatch": [(101.0, 150.0), (601.0, 650.0)],
        "graph.apply.wait": [(150.0, 203.0), (650.0, 700.0)],
    }
    t = spans.SpanTrace(ops, s, modules)
    assert spans.clock_offset(t) == (1.0, 3.0)
    moved = t.shifted(1.0)
    assert moved.ops["/device:TPU:0"][1].tolist() == [101.0]
    assert moved.modules["/device:TPU:0"][2].tolist() == [201.0]
    assert moved.spans is t.spans


def test_clock_offset_on_the_recorded_trace(span_trace, old_trace):
    from bench import span_report

    t, _ = span_trace
    lo, hi = spans.clock_offset(t)
    # the growth check's _live_counts shows 0.97 ms before the span that
    # dispatches it; an apply_batch ends 2.31 ms before its wait closes
    assert (lo * 1e-6, hi * 1e-6) == pytest.approx((0.967291, 2.314068), abs=1e-6)
    splits = [span_report.idle_ms(t.shifted(x))["bench.apply"] for x in (0.0, lo, hi)]
    for split in splits:
        parts = [v for k, v in split.items() if k != "bench.apply"]
        assert sum(parts) == pytest.approx(split["bench.apply"], rel=1e-9)
    # the later the device's clock is put, the less of the wait it idles
    waits = [split["graph.apply.wait"] for split in splits]
    assert waits[0] > waits[1] > waits[2] > 0
    assert spans.clock_offset(spans.reduce(old_trace)) is None
