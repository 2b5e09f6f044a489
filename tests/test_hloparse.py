"""hloparse: execution-weighted HLO cost model vs exactly-known programs.

The whole roofline (EXPERIMENTS.md §Roofline) rests on this module, so the
flop accounting is validated against hand-computable programs, including the
while-loop trip-count multiplication that raw ``cost_analysis()`` misses.
"""

import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp

from repro.launch import hloparse

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_scan_body_multiplied_by_trip_count():
    def f_scan(x, w):
        def body(c, wi):
            return c @ wi, None
        c, _ = jax.lax.scan(body, x, w)
        return c

    x = jax.ShapeDtypeStruct((128, 256), jnp.float32)
    w = jax.ShapeDtypeStruct((8, 256, 256), jnp.float32)
    comp = jax.jit(f_scan).lower(x, w).compile()

    raw = comp.cost_analysis()
    if isinstance(raw, (list, tuple)):  # older jax returns [dict], newer dict
        raw = raw[0]
    raw = raw["flops"]
    s = hloparse.summarize(comp.as_text())
    expect = 8 * 2 * 128 * 256 * 256
    assert raw < expect / 4            # the undercount this module fixes
    assert abs(s["flops"] - expect) / expect < 0.01


def test_nested_scans_multiply():
    def f(x, w):
        def outer(c, wi):
            def inner(c2, _):
                return jnp.tanh(c2 @ wi), None
            c2, _ = jax.lax.scan(inner, c, None, length=4)
            return c2, None
        c, _ = jax.lax.scan(outer, x, w)
        return c

    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    w = jax.ShapeDtypeStruct((3, 64, 64), jnp.float32)
    comp = jax.jit(f).lower(x, w).compile()
    s = hloparse.summarize(comp.as_text())
    expect = 3 * 4 * 2 * 64 * 64 * 64  # 12 executions of one matmul
    assert abs(s["flops"] - expect) / expect < 0.05


def test_unrolled_matches_scanned():
    """Same math scanned vs unrolled must give ~equal exec-weighted flops."""
    def f_scan(x, w):
        def body(c, wi):
            return c @ wi, None
        return jax.lax.scan(body, x, w)[0]

    def f_unroll(x, w):
        c = x
        for i in range(8):
            c = c @ w[i]
        return c

    x = jax.ShapeDtypeStruct((64, 128), jnp.float32)
    w = jax.ShapeDtypeStruct((8, 128, 128), jnp.float32)
    a = hloparse.summarize(jax.jit(f_scan).lower(x, w).compile().as_text())
    b = hloparse.summarize(jax.jit(f_unroll).lower(x, w).compile().as_text())
    assert abs(a["flops"] - b["flops"]) / b["flops"] < 0.01


def test_tuple_type_while_parses():
    """Regression: while-op tuple types embed /*index=N*/ comments that broke
    a regex-only parser (mult dropped to 1 silently)."""
    line = (
        "  %while.359 = (s32[], f32[16,4,7,256]{3,2,1,0}, "
        "/*index=5*/s32[256,1]{1,0}) while(%tuple.405), "
        "condition=%c, body=%b, "
        'backend_config={"known_trip_count":{"n":"28"}}'
    )
    parsed = hloparse._parse_op_line(line)
    assert parsed is not None
    name, type_str, opcode = parsed
    assert opcode == "while" and name == "while.359"
    assert hloparse.shape_bytes(type_str) == 4 + 16 * 4 * 7 * 256 * 4 + 256 * 4


_SHARDED_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch import hloparse
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((2, 4), ("data", "model"))
    def f(x, w):
        def body(c, wi):
            return jnp.tanh(c @ wi), None
        return jax.lax.scan(body, x, w)[0].mean()
    xs = jax.ShapeDtypeStruct((128, 256), jnp.float32,
                              sharding=NamedSharding(mesh, P("data", None)))
    ws = jax.ShapeDtypeStruct((8, 256, 256), jnp.float32,
                              sharding=NamedSharding(mesh, P(None, None, "model")))
    with jax.set_mesh(mesh):
        comp = jax.jit(f).lower(xs, ws).compile()
    s = hloparse.summarize(comp.as_text())
    # per-device dot: (64,256)x(256,64) x 8 trips
    expect = 8 * 2 * 64 * 256 * 64
    assert abs(s["flops"] - expect) / expect < 0.02, s["flops"]
    # loop-carried all-gather of the x shard: f32[64,256] x 8 trips
    assert s["collective_bytes"]["all-gather"] == 8 * 64 * 256 * 4
    assert s["collective_counts"]["all-gather"] == 8
    print("SHARDED_OK")
""")


def test_sharded_collectives_exec_weighted():
    r = subprocess.run(
        [sys.executable, "-c", _SHARDED_SCRIPT],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "PYTHONPATH": os.path.join(REPO, "src")},
    )
    assert "SHARDED_OK" in r.stdout, r.stderr[-2000:]
