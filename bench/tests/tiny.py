"""Tiny versions of the benchmark's cells, for runs on the CPU in tests.

Each configuration and traffic file carries its own ``tiny`` overrides: the
configuration's replace its top-level numbers, and the mix's replace
``load_batch`` and, step by step, the steps' parameters.  The mix's shares
and kinds stay the cell's own.
"""

from __future__ import annotations

import copy
import json
import os

from bench import harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def spec() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def workloads() -> list[str]:
    return [w["name"] for w in spec()["workloads"]]


def cell(workload: str):
    """(spec, cell, cfg, traffic) of ``workload``, cut to a size the CPU runs
    in seconds."""
    s = spec()
    c, cfg, traffic = harness.cell_files(s, workload)
    cfg = dict(cfg, **cfg.get("tiny", {}))
    traffic = copy.deepcopy(traffic)
    small = traffic.get("tiny", {})
    traffic["load_batch"] = small.get("load_batch", traffic["load_batch"])
    for step, over in zip(traffic["steps"], small.get("steps", [])):
        step.update(over)
    return s, c, cfg, traffic


def run(workload: str, seed: int = 2**31 + 11, seconds: float = 1.0) -> dict:
    s, c, cfg, traffic = cell(workload)
    return harness.run_cell(s, c, cfg, traffic, seed, seconds, False, 0.0)
