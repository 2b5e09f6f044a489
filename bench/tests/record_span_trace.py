"""Record the small device trace that ``test_span_reduce.py`` reads, with
the program's own spans and each op's device scope.

    python3 bench/tests/record_span_trace.py <output directory>

On one TPU chip: a small graph takes a few update batches and BFS calls
inside the benchmark's own spans (as ``record_trace.py`` does), traced with
the benchmark's profiler options.  Kept: each device plane's ``XLA Ops``
and ``XLA Modules`` lines, and the host's ``bench.`` spans and the
program's spans (``repro.obs.SPAN_PREFIXES``), with their times as
recorded.  Written to the output directory:

* ``span_trace.xplane.pb``, the pruned trace;
* ``span_trace.scopes.json``, ``{module: {op: scope}}`` for the ops of the
  engine pass and of the BFS level loop that run under a device scope
  (``repro.obs.DEVICE_SCOPES``), read from the programs' compiled text.

It then prints the stats the chip's trace gives an ``XLA Ops`` event, and
the cost of one span with and without a profiler session.  The recorded
files are ``bench/tests/data/span_trace.*``.
"""

import glob
import json
import os
import sys
import tempfile
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench.tests.record_trace import KEEP_LINES, _quote  # noqa: E402


def prune(pd, keep_host) -> bytes:
    """The device ops and modules and the host events ``keep_host(name)``
    admits, as a serialized XSpace."""
    from jax.profiler import ProfileData

    planes = []
    for p_id, plane in enumerate(pd.planes, 1):
        device = plane.name.startswith("/device:")
        names: dict[str, int] = {}
        lines = []
        for l_id, line in enumerate(plane.lines, 1):
            if device and line.name not in KEEP_LINES:
                continue
            evs = [ev for ev in line.events if device or keep_host(ev.name)]
            if not evs:
                continue
            body = " ".join(
                f"events {{ metadata_id: {names.setdefault(ev.name, len(names) + 1)} "
                f"offset_ps: {round(ev.start_ns * 1000)} duration_ps: {round(ev.duration_ns * 1000)} }}"
                for ev in evs
            )
            lines.append(f"lines {{ id: {l_id} name: {_quote(line.name)} timestamp_ns: 0 {body} }}")
        if not lines:
            continue
        meta = " ".join(
            f"event_metadata {{ key: {i} value {{ id: {i} name: {_quote(n)} }} }}" for n, i in names.items()
        )
        planes.append(f"planes {{ id: {p_id} name: {_quote(plane.name)} {' '.join(lines)} {meta} }}")
    return ProfileData.text_proto_to_serialized_xspace("\n".join(planes))


def span_cost(n: int = 200_000) -> dict:
    """Microseconds per empty ``repro.obs`` span (registry off), without
    and with a profiler session at the benchmark's options."""
    import jax

    from repro.obs import NOOP

    def loop():
        t0 = time.perf_counter()
        for _ in range(n):
            with NOOP.span("graph.apply.wait"):
                pass
        return (time.perf_counter() - t0) / n * 1e6

    off = loop()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp, profiler_options=opts)
        on = loop()
        jax.profiler.stop_trace()
    return {"us_per_span_no_session": off, "us_per_span_session": on, "spans": n}


def main() -> int:
    import jax

    from bench import spans
    from repro.core import WaitFreeGraph, engine, traversal
    from repro.core.types import make_batch
    from repro.obs import DEVICE_SCOPES, SPAN_PREFIXES

    if jax.devices()[0].platform != "tpu":
        print("record_span_trace: no TPU; nothing was recorded", file=sys.stderr)
        return 1
    out = sys.argv[1]
    rng = np.random.default_rng(0)
    g = WaitFreeGraph(2**12, 2**15)
    keys = np.arange(1000, dtype=np.int32)
    g.apply(np.full(1000, 1, np.int32), keys, np.zeros(1000, np.int32))

    def batch():
        return (
            rng.choice([4, 4, 5, 6], 1024).astype(np.int32),
            rng.choice(keys, 1024).astype(np.int32),
            rng.choice(keys, 1024).astype(np.int32),
        )

    g.apply(*batch())
    g.bfs_batch(keys[:16])
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    os.makedirs(out, exist_ok=True)
    from jax.profiler import ProfileData

    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp, profiler_options=opts)
        with jax.profiler.TraceAnnotation("bench.window"):
            for i in range(4):
                with jax.profiler.TraceAnnotation("bench.generate"):
                    b = batch()
                with jax.profiler.TraceAnnotation("bench.apply"):
                    g.apply(*b)
                with jax.profiler.TraceAnnotation("bench.generate"):
                    src = rng.choice(keys, 16, replace=False)
                with jax.profiler.TraceAnnotation("bench.query"):
                    g.bfs_batch(src)
        jax.profiler.stop_trace()
        (path,) = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True)
        pd = ProfileData.from_file(path)
        with open(os.path.join(out, "span_trace.xplane.pb"), "wb") as f:
            f.write(prune(pd, lambda n: n.startswith(("bench.",) + SPAN_PREFIXES)))
        stats = set()
        for plane in pd.planes:
            if plane.name.startswith("/device:"):
                for line in plane.lines:
                    if line.name == "XLA Ops":
                        for ev in list(line.events)[:50]:
                            stats |= {str(x[0] if isinstance(x, tuple) else x) for x in ev.stats}
        print(f"record_span_trace: the chip's XLA Ops events carry the stats {sorted(stats)}")

    # the scopes of the two programs the traced window ran, at its shapes
    pad = np.zeros(1024, np.int32)
    programs = [
        (engine.apply_batch, (g.state, make_batch(pad, pad, pad))),
        (traversal.bfs_levels, (g.traversal_csr(), keys[:16])),
    ]
    ran = spans.reduce(ProfileData.from_file(os.path.join(out, "span_trace.xplane.pb")))
    seen = {k for ks in spans._op_keys(ran).values() for k in ks}
    scopes = {}
    for fn, args in programs:
        text = spans.compiled_text(fn, args)
        module = text.split(None, 2)[1].rstrip(",")
        found = spans.hlo_scopes(text, DEVICE_SCOPES)
        missing = {op for m, op in seen if m == module} - set(found)
        if missing:
            print(f"record_span_trace: {module} compiled again lacks {len(missing)} ops of the trace")
        scopes[module] = {op: sc for op, sc in found.items() if sc is not None and (module, op) in seen}
    with open(os.path.join(out, "span_trace.scopes.json"), "w") as f:
        json.dump(scopes, f, indent=0, sort_keys=True)
    print(f"record_span_trace: {os.path.getsize(os.path.join(out, 'span_trace.xplane.pb'))} bytes; scoped ops "
          + ", ".join(f"{m} {len(v)}" for m, v in scopes.items()))
    print(f"record_span_trace: {json.dumps(span_cost())}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
