"""The control of each cell: the reference, with one guarantee of the
configuration broken, put in the program's place and run through the whole
benchmark at the cell's own size.  Its ``correct`` must come out false.

    python3 bench/control.py --workload <cell> --seeds <n> [<n> ...] --seconds <s>

A cell's control is the ``Control`` of the first request kind of its mix
that has one (``bench/steps/<kind>.py``), so a new kind brings its own.
The graphs here are host Python; the harness's look for a chip is skipped,
so this runs anywhere.  The benchmark's own runs never run it.
"""

from __future__ import annotations

import importlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness  # noqa: E402


def control_of(traffic: dict):
    """The ``Control`` class of the mix's first request kind that has one."""
    for step in traffic["steps"]:
        control = getattr(importlib.import_module(f"bench.steps.{step['kind']}"), "Control", None)
        if control is not None:
            return control
    raise LookupError("no request kind of this mix has a control")


def run_control(spec, cell, cfg, traffic, seed: int, seconds: float) -> dict:
    """One benchmark run with the cell's control in the program's place."""
    saved = harness.WaitFreeGraph
    harness.WaitFreeGraph = control_of(traffic)
    try:
        return harness.run_cell(spec, cell, cfg, traffic, seed, seconds, False, time.perf_counter())
    finally:
        harness.WaitFreeGraph = saved


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    cell, cfg, traffic = harness.cell_files(spec, args.workload)
    for seed in args.seeds:
        r = run_control(spec, cell, cfg, traffic, seed, args.seconds)
        print(json.dumps({"workload": args.workload, "seed": seed, "correct": r["correct"], "checks": r["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
