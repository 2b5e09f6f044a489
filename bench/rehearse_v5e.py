"""Compile each cell's programs for a TPU v5e that is described, not attached.

    JAX_PLATFORMS=cpu python3 bench/rehearse_v5e.py [<part of a program's label> ...]

For every cell of ``BENCHMARK.json``, lowers and compiles, at the shapes the
cell runs (table capacities from its configuration, batch sizes from its
mix), the programs its set-up and window drive: the engine pass at the load
batch, the growth check, and the programs each request kind of the mix
names (``programs`` in ``bench/steps/<kind>.py``).  It prints each
program's ``memory_analysis``; arguments select programs by a part of
their label.  Nothing runs; a compile that passes here is not a chip run.
Run it before sending a changed cell to the chip: the TPU compiler refuses
here what the chip would refuse.
"""

from __future__ import annotations

import importlib
import json
import os
import sys
import time
from types import SimpleNamespace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main() -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench import harness
    from repro.core import engine, graph, traversal
    from repro.core.types import GraphState, OpBatch

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    i32 = jnp.int32

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)

    def shapes_for(cv: int, ce: int) -> SimpleNamespace:
        def state():
            return GraphState(
                spec((cv,), i32), spec((cv,), bool), spec((cv,), i32),
                spec((ce,), i32), spec((ce,), i32), spec((ce,), bool), spec((ce,), i32), spec((ce,), i32),
            )

        def batch(n):
            return OpBatch(spec((n,), i32), spec((n,), i32), spec((n,), i32), spec((n,), i32))

        def csr():
            s = spec((), i32)
            return traversal.TraversalCSR(
                spec((cv,), i32), spec((cv,), bool), spec((cv,), i32), s,
                spec((ce,), i32), spec((ce,), i32), spec((ce,), i32),
                spec((cv,), i32), spec((cv,), i32), s,
            )

        return SimpleNamespace(state=state, batch=batch, csr=csr, vector=lambda n: spec((n,), i32))

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench_spec = json.load(f)
    programs = {}
    for w in bench_spec["workloads"]:
        _, cfg, traffic = harness.cell_files(bench_spec, w["name"])
        caps = (cfg["v_capacity"], cfg["e_capacity"])
        shapes = shapes_for(*caps)
        found = [
            (f"apply_batch {traffic['load_batch']}", engine.apply_batch, (shapes.state(), shapes.batch(traffic["load_batch"]))),
            ("_live_counts", graph._live_counts, (shapes.state(),)),
        ]
        for step in traffic["steps"]:
            found += importlib.import_module(f"bench.steps.{step['kind']}").Step.programs(step, shapes)
        for label, fn, args in found:
            programs.setdefault(f"{label} at {caps[0]} / {caps[1]}", (fn, args, w["name"]))
    only = sys.argv[1:]
    for label, (fn, args, first) in programs.items():
        if only and not any(o in label for o in only):
            continue
        t0 = time.perf_counter()
        compiled = jax.jit(fn).lower(*args).compile()
        ma = compiled.memory_analysis()
        print(
            f"{label} (first in {first}): compiled in {time.perf_counter() - t0:.1f} s; argument "
            f"{ma.argument_size_in_bytes} B, output {ma.output_size_in_bytes} B, "
            f"temp {ma.temp_size_in_bytes} B",
            flush=True,
        )
    return 0


if __name__ == "__main__":
    sys.exit(main())
