"""Record the small device trace that ``test_trace_reduce.py`` reads.

    python3 bench/tests/record_trace.py <output directory>

On one TPU chip: a small graph takes a few update batches and BFS calls
inside the benchmark's own spans, traced with the benchmark's profiler
options.  What the reduction reads is kept (each device plane's ``XLA Ops``
and ``XLA Modules`` lines, and the host's ``bench.`` spans, with their
times as recorded) and written to ``small_trace.xplane.pb`` in the output
directory; the rest of the trace (host threads, compiler passes, async
copies) would make the file ten times larger.  The first traced query
folds the updates before it into the snapshot, and that fold compiles
inside the window: the trace holds a long idle stretch in ``bench.query``.  The recorded file is
``bench/tests/data/small_trace.xplane.pb``.
"""

import glob
import os
import sys
import tempfile

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


KEEP_LINES = ("XLA Ops", "XLA Modules")


def _quote(name: str) -> str:
    return '"' + name.encode("ascii", "replace").decode().replace("\\", "\\\\").replace('"', '\\"') + '"'


def prune(pd) -> bytes:
    """The parts of a trace the reduction reads, as a serialized XSpace."""
    from jax.profiler import ProfileData

    planes = []
    for p_id, plane in enumerate(pd.planes, 1):
        device = plane.name.startswith("/device:")
        names: dict[str, int] = {}
        lines = []
        for l_id, line in enumerate(plane.lines, 1):
            if device and line.name not in KEEP_LINES:
                continue
            evs = [
                ev for ev in line.events if device or ev.name.startswith("bench.")
            ]
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


def main() -> int:
    import jax

    from repro.core import WaitFreeGraph

    if jax.devices()[0].platform != "tpu":
        print("record_trace: no TPU; nothing was recorded", file=sys.stderr)
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
        os.makedirs(out, exist_ok=True)
        from jax.profiler import ProfileData

        with open(os.path.join(out, "small_trace.xplane.pb"), "wb") as f:
            f.write(prune(ProfileData.from_file(path)))
    print(f"record_trace: {os.path.getsize(os.path.join(out, 'small_trace.xplane.pb'))} bytes")
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(os.path.join(out, "small_trace.xplane.pb"))
    for plane in pd.planes:
        lines = [(ln.name, sum(1 for _ in ln.events)) for ln in plane.lines]
        print(f"plane {plane.name}: {lines[:12]}")
        for ln in plane.lines:
            evs = list(ln.events)[:3]
            for ev in evs:
                print(f"   {ln.name}: {ev.name[:60]} start {ev.start_ns} dur {ev.duration_ns}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
