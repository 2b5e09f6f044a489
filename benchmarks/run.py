"""Benchmark driver: ``PYTHONPATH=src python -m benchmarks.run [--full]``.

One section per paper table/figure plus the framework benches:

  graph_throughput — paper Fig. 4 (3 mixes × 5 engines × lane sweep)
  serving_paged_kv — wait-free paged KV vs contiguous (beyond-paper)
  lm_step          — per-arch smoke train/decode step timings
  roofline         — 3-term roofline per dry-run cell (reads results/dryrun)

Everything prints CSV rows ``bench,<fields...>`` so the output diffs cleanly
across runs.
"""

from __future__ import annotations

import argparse
import os
import sys


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="full lane sweep + all archs (default: quick)")
    ap.add_argument("--skip", default="", help="comma list of sections")
    args = ap.parse_args()
    quick = not args.full
    skip = set(filter(None, args.skip.split(",")))

    from repro.launch.compile_cache import enable_compile_cache

    print(f"# compile cache: {enable_compile_cache()}")
    from benchmarks import graph_throughput, lm_step_bench, serving_bench

    if "graph" not in skip:
        print("# === graph_throughput (paper Fig. 4) ===")
        # default: 3-point lane sweep (1/32/512) — the full 5-point sweep
        # (--full) adds ~40 min of engine compiles on this 1-core box
        graph_throughput.main(quick=quick)
    if "serving" not in skip:
        print("# === serving_paged_kv ===")
        serving_bench.main(quick=quick)
    if "lm" not in skip:
        print("# === lm_step ===")
        lm_step_bench.main(quick=quick)
    if "roofline" not in skip:
        d = ("results/dryrun_opt" if os.path.isdir("results/dryrun_opt")
             else "results/dryrun")
        print(f"# === roofline (from {d}) ===")
        if os.path.isdir(d):
            from benchmarks import roofline
            sys.argv = ["roofline", "--dir", d]
            roofline.main()
        else:
            print("# results/dryrun missing — run repro.launch.dryrun first")


if __name__ == "__main__":
    main()
