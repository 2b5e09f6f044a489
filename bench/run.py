"""The graph store's benchmark: one cell, one run.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Cells, configurations, traffic mixes and metrics are named in
``BENCHMARK.json`` at the checkout root; ``bench/harness.py`` finds each by
its name.  The run needs the accelerator the cell names: without it, it
exits non-zero and prints no result.  Its last line of output is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer ones), ``device``,
with ``--trace 1`` a ``breakdown``, and last the ``checks``, each number
compared beside its limit (also the last lines of standard error).
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    chips = {w["name"]: w["chips"] for w in spec["workloads"]}.get(args.workload)
    if chips is None:
        print(f"run: no workload {args.workload!r} in BENCHMARK.json", file=sys.stderr)
        return 2

    import jax

    # the persistent compile cache lives in the checkout, at a fixed path
    jax.config.update("jax_compilation_cache_dir", os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(
            f"run: needs {chips} TPU chip(s); JAX found {len(devices)} {devices[0].platform} "
            "device(s); nothing was run",
            file=sys.stderr,
        )
        return 1

    from bench import harness

    cell, cfg, traffic = harness.cell_files(spec, args.workload)
    result = harness.run_cell(
        spec, cell, cfg, traffic, args.seed % 2**64, args.seconds, bool(args.trace), T_START
    )
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
