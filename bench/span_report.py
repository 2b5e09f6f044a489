"""One traced run of one cell, reported by the program's own spans and
device scopes as well as the benchmark's.

    python3 bench/span_report.py --workload <cell> --seed <n> --seconds <s> [--out <file.json>]

The run is ``bench/run.py --trace 1`` (same set-up, window, profiler
options, check and result line), with the trace reduced by
``bench/spans.py``, which keeps the program's spans (``graph.apply.*``,
``graph.bfs_batch.*``, ``csr.*``, ...) beside the benchmark's.  After the
result line it prints one JSON object (also written to ``--out``):

* ``idle_gaps``: the device's idle time split by the innermost open span;
* ``idle_ms``: for every child span of ``graph.apply`` and
  ``graph.bfs_batch``, the device's idle time inside it per request, and
  the idle time inside the request's ``bench.`` span outside them;
* ``clock_offset_ms`` and ``idle_ms_at_offset``: the bounds
  ``spans.clock_offset`` puts on how far the device's timestamps run early
  against the host's, and ``idle_ms`` read with the device moved by each
  bound; a child's idle time lies between its two readings;
* ``device_ms``: device time per request under each device scope, and the
  rest of the request's device time, under no scope;
* ``frontier``: the frontier expansions per call, and the expansion and
  level-update time per call beside ``traversal_device_ms``;
* ``longest``: the 5 longest ``graph.apply`` and ``graph.bfs_batch``
  calls, each split by child span into wall and device busy time.

The harness reduces its trace with ``bench/trace_reduce.py``, which drops
the program's spans, so this tool swaps in ``spans.read`` for that one
step.  Once the harness reduces with ``spans.read`` itself, this file
shrinks to ``report`` over the harness's reduced trace, and the swap goes.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

REQUESTS = {"bench.apply": "graph.apply", "bench.query": "graph.bfs_batch"}


def idle_ms(trace) -> dict:
    """{bench span: {child span: device idle ms inside it per request}},
    with the request's idle time outside the children, and in all."""
    out = {}
    for bench_span, call in REQUESTS.items():
        n = len(trace.durations(bench_span))
        if not n:
            continue
        idle = {}
        for name in sorted(trace.spans):
            if name.startswith(call + ".") and name.count(".") == call.count(".") + 1:
                idle[name] = sum(trace.idle_within(name)) / n * 1e-6
        whole = sum(trace.idle_within(bench_span)) / n * 1e-6
        idle[f"{bench_span} outside {call}.*"] = whole - sum(idle.values())
        idle[bench_span] = whole
        out[bench_span] = idle
    return out


def report(trace) -> dict:
    """The span and scope breakdown of a reduced ``SpanTrace``."""
    from bench import spans

    labels = spans.op_scopes(trace)
    out = {"idle_gaps": trace.idle_by_span(40), "idle_ms": idle_ms(trace)}
    out.update(device_ms={}, longest=[])
    offset = spans.clock_offset(trace)
    if offset:
        out["clock_offset_ms"] = [x * 1e-6 for x in offset]
        out["idle_ms_at_offset"] = [idle_ms(trace.shifted(x)) for x in offset]
    for bench_span, call in REQUESTS.items():
        n = len(trace.durations(bench_span))
        if not n:
            continue
        busy = sum(trace.busy_within(bench_span)) / n * 1e-6
        dev = {bench_span: busy}
        for scope in sorted({x for lab in labels.values() for x in lab.tolist() if x}):
            t = spans.scoped_busy(trace, labels, scope, bench_span)
            if t:
                dev[scope] = sum(t) / n * 1e-6
        dev["under no scope"] = busy - sum(v for k, v in dev.items() if k != bench_span)
        out["device_ms"][bench_span] = dev
        out["longest"] += trace.longest(call, 5)
        if bench_span == "bench.query":
            runs = spans.scoped_runs(trace, labels, "traversal.level_update", bench_span)
            if runs:
                out["frontier"] = {
                    "expansions_per_call": runs / n,
                    "frontier_level_ms": dev.get("traversal.frontier_expand", 0.0) * n / runs,
                    "expand_ms_per_call": dev.get("traversal.frontier_expand", 0.0),
                    "level_update_ms_per_call": dev.get("traversal.level_update", 0.0),
                    "traversal_device_ms": busy,
                }
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    from bench import harness, run, spans, trace_reduce

    # the harness's trace, reduced with the program's spans kept
    kept = {}

    def read(trace_dir):
        kept["trace"] = spans.read(trace_dir)
        return kept["trace"]

    harness.trace_reduce = SimpleNamespace(read=read, WINDOW_SPAN=trace_reduce.WINDOW_SPAN)
    run.T_START = T_START
    rc = run.main(
        ["--workload", args.workload, "--seed", str(args.seed), "--seconds", str(args.seconds), "--trace", "1"]
    )
    if rc or "trace" not in kept:
        return rc or 1
    t0 = time.perf_counter()
    out = report(kept["trace"])
    out["report_s"] = time.perf_counter() - t0
    text = json.dumps(out)
    print(text, flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            f.write(text)
    return 0


if __name__ == "__main__":
    sys.exit(main())
