"""The trace reduction, on interval sets checked point by point and on a
small trace recorded on one TPU v5e (``record_trace.py``)."""

import os

import numpy as np
import pytest

from bench import trace_reduce

TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data", "small_trace.xplane.pb")


def _mask(starts, ends, n):
    m = np.zeros(n, bool)
    for s, e in zip(starts, ends):
        m[int(s) : int(e)] = True
    return m


@pytest.mark.parametrize("seed", range(5))
def test_union_and_covered_match_a_point_mask(seed):
    rng = np.random.default_rng(seed)
    s = rng.integers(0, 1000, 60).astype(float)
    e = s + rng.integers(0, 80, 60)
    us, ue = trace_reduce.union(s, e)
    assert np.all(us[1:] > ue[:-1])  # disjoint, sorted
    np.testing.assert_array_equal(_mask(us, ue, 1200), _mask(s, e, 1200))
    for a, b in rng.integers(0, 1200, (20, 2)):
        a, b = min(a, b), max(a, b)
        assert trace_reduce.covered(us, ue, a, b) == _mask(s, e, 1200)[a:b].sum()


@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData

    return ProfileData.from_file(TRACE)


def test_recorded_trace_is_small():
    assert os.path.getsize(TRACE) < 512 * 1024


def test_recorded_trace_reduces(recorded):
    t = trace_reduce.reduce(recorded)
    w0, w1 = t.window
    # busy, recomputed from the raw device events by a plain sweep
    events = []
    for plane in recorded.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    events += [(max(ev.start_ns, w0), min(ev.end_ns, w1)) for ev in line.events]
    events = sorted((a, b) for a, b in events if b > a)
    assert events, "the recorded trace holds device ops in its window"
    busy, end = 0.0, -np.inf
    for a, b in events:
        if b > end:
            busy += b - max(a, end)
            end = b
    assert t.busy_ns() == pytest.approx(busy, rel=1e-9)
    assert 0 < t.busy_ns() < t.window_ns
    # per-span busy time lies inside the span, and inside the total
    for name in ("bench.apply", "bench.query"):
        inside = t.busy_within(name)
        assert len(inside) == 4
        assert all(0 <= x <= d for x, d in zip(inside, t.durations(name)))
    assert sum(t.busy_within("bench.apply")) + sum(t.busy_within("bench.query")) <= t.busy_ns() * (1 + 1e-9)
    assert sum(t.busy_within("bench.query")) > 0
    # idle time, split by span, adds up to the window less the busy time
    idle = t.idle_by_span(100)
    assert sum(x for _, x in idle) * 1e9 == pytest.approx(t.window_ns - t.busy_ns(), rel=1e-6)
    ops = t.top_ops(10)
    assert 0 < len(ops) <= 10 and all(x > 0 for _, x in ops)
    progs = [x for n, x in ops if "/" not in n]
    leaves = [x for n, x in ops if "/" in n]
    assert progs and leaves and len(progs) <= 5
    assert progs == sorted(progs, reverse=True) and leaves == sorted(leaves, reverse=True)
    # the programs' time covers the busy time; no leaf op outlasts its program
    assert sum(x for n, x in t.top_ops(1000) if "/" not in n) * 1e9 >= t.busy_ns() * (1 - 1e-6)
    assert max(leaves) <= max(progs)
