"""A whole run, on the CPU at a tiny size, with the harness's look for a chip
skipped: sound, it is correct; with the timed path broken underneath, or
with the cell's control in the program's place, it is not.  The cells come
from ``BENCHMARK.json``, and each cell is broken in every way its request
kinds name (``faults`` in ``bench/steps/<kind>.py``)."""

import importlib

import numpy as np
import pytest

from bench import control, harness
from bench.tests import tiny
from repro.core import WaitFreeGraph


class StateUnchanged(WaitFreeGraph):
    """Answers every batch, then returns the tables as they were."""

    def apply(self, ops, us, vs=None):
        pre = self.state
        out = super().apply(ops, us, vs)
        self.state = pre
        return out


class HalfBatch(WaitFreeGraph):
    """Applies the first half of each batch; the rest answer False."""

    def apply(self, ops, us, vs=None):
        n = len(ops)
        out = np.zeros(n, bool)
        out[: n // 2] = super().apply(ops[: n // 2], us[: n // 2], vs[: n // 2])
        return out

    def bfs_batch(self, sources):
        n = len(sources)
        return super().bfs_batch(sources[: n // 2]) + [{} for _ in range(n - n // 2)]


class AlteredAnswer(WaitFreeGraph):
    """Flips one success bit of each batch; moves one vertex of each BFS
    answer one level further."""

    def apply(self, ops, us, vs=None):
        out = super().apply(ops, us, vs).copy()
        out[len(out) // 2] ^= True
        return out

    def bfs_batch(self, sources):
        out = super().bfs_batch(sources)
        for levels in out:
            if levels:
                k = max(levels)
                levels[k] += 1
        return out


FAULT_CLASSES = {
    "state_unchanged": StateUnchanged,
    "half_batch": HalfBatch,
    "altered_answer": AlteredAnswer,
}


def _faults():
    out = []
    for workload in tiny.workloads():
        _, _, _, traffic = tiny.cell(workload)
        names = []
        for step in traffic["steps"]:
            for name in importlib.import_module(f"bench.steps.{step['kind']}").Step.faults:
                if name not in names:
                    names.append(name)
        out += [(workload, name) for name in names]
    return out


@pytest.mark.parametrize("workload", tiny.workloads())
def test_sound_runs_are_correct(workload):
    r = tiny.run(workload)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0


@pytest.mark.parametrize("workload,fault", _faults())
def test_a_broken_timed_path_is_not_correct(monkeypatch, workload, fault):
    monkeypatch.setattr(harness, "WaitFreeGraph", FAULT_CLASSES[fault])
    r = tiny.run(workload)
    assert not r["correct"], r["checks"]
    # the checks are printed beside their limits, last in the result
    assert list(r)[-1] == "checks"
    assert any(c["value"] > c["limit"] for c in r["checks"].values())


@pytest.mark.parametrize("workload", tiny.workloads())
def test_the_control_is_not_correct(workload):
    s, c, cfg, traffic = tiny.cell(workload)
    r = control.run_control(s, c, cfg, traffic, 2**31 + 3, 1.0)
    assert not r["correct"], r["checks"]


def test_the_sample_keeps_k_answers_and_drops_the_rest():
    rs = harness.Reservoir(3, np.random.default_rng(5))
    recs = [[0, k, {"answer": k}] for k in range(40)]
    for k, r in enumerate(recs):
        rs.offer(r)
        assert sum(x[2] is not None for x in recs[: k + 1]) == min(k + 1, 3)
    kept = [r[1] for r in recs if r[2] is not None]
    assert len(kept) == 3 and sorted(kept) == sorted(r[1] for r in rs.kept)


def test_the_sample_is_uniform():
    hits = np.zeros(11, int)
    for s in range(3000):
        rs = harness.Reservoir(2, np.random.default_rng([s, 9]))
        recs = [[0, k, k] for k in range(11)]
        for r in recs:
            rs.offer(r)
        for r in recs:
            if r[2] is not None:
                hits[r[1]] += 1
    # each of 11 answers is kept in 2 of 11 samples: 545 of 3000 expected
    assert hits.min() > 440 and hits.max() < 660
