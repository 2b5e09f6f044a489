"""Compile-only checks for one TPU v5e chip, at the widths chip_smoke.py runs.

Nothing here runs on a chip: each test lowers a jitted function for a v5e
device that is described, not attached, and compiles it with the TPU
compiler — which refuses what the chip would refuse (an unsupported Pallas
lowering, a program that does not fit).  Covered: whatever the TPU dispatch
selects for the ``frontier`` and ``compact`` families and the device delta
fold, and, pinned as refusals with the compiler's own words
(``docs/KERNELS.md``), the Pallas kernels the dispatch therefore never
selects.  If one of those starts to compile, the dispatch rule is worth
revisiting.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every test worker
imports this file.
"""

import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import maintenance
from repro.core.traversal import TraversalCSR
from repro.core.types import GraphState
from repro.kernels.compact import kernel as compact_kernel
from repro.kernels.compact import masked_compact, ops as compact_ops, probe_place
from repro.kernels.frontier import frontier_expand
from repro.kernels.frontier import kernel as frontier_kernel
from repro.kernels.frontier import ops as frontier_ops
from repro.kernels.hash_probe import kernel as hash_probe_kernel

# the grown tables of the LDBC SNB SF10 person-knows-person smoke graph
V_CAP = 2**19
E_CAP = 2**23
# BFS rows per query batch: the smoke's 8 sources, padded to the 16-row floor
N_SOURCES = 16
MAX_PROBES = 32


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else the compiler logs to /tmp
    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    # a compile for a described chip cannot be read back without one
    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", old)


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def test_dispatch_is_xla():
    assert frontier_ops.resolve() == "xla"
    assert compact_ops.resolve() == "xla"


def test_frontier_expand_compiles(one_chip):
    i32 = jnp.int32
    compiled = _compile(
        frontier_expand,
        _spec(one_chip, (N_SOURCES, V_CAP + 1), bool),
        _spec(one_chip, (E_CAP,), i32),
        _spec(one_chip, (E_CAP,), i32),
    )
    # the edge-blocked proposal tile bounds the working set far below HBM
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * 2**30
    # the pull scan's int32 tiles keep the lane axis minor: a minor axis of
    # 16 sources would pad to 128 lanes in every tile
    arrays = re.findall(rf"s32\[{N_SOURCES},([\d,]+)\]\{{(\d+),", compiled.as_text())
    wide = [(dims, minor) for dims, minor in arrays if int(dims.split(",")[-1]) >= 128]
    assert wide
    for dims, minor in wide:
        assert int(minor) == dims.count(",") + 1, f"[{N_SOURCES},{dims}] has minor axis {minor}"


@pytest.mark.parametrize("rows,n", [(3, V_CAP // 2), (6, E_CAP // 2)])
def test_masked_compact_compiles(one_chip, rows, n):
    fn = functools.partial(masked_compact, fill=-1)
    _compile(fn, _spec(one_chip, (rows, n), jnp.int32), _spec(one_chip, (n,), bool))


@pytest.mark.parametrize("m,cap", [(V_CAP // 2, V_CAP), (E_CAP // 2, E_CAP)])
def test_probe_place_compiles(one_chip, m, cap):
    fn = functools.partial(probe_place, capacity=cap, max_probes=MAX_PROBES)
    _compile(fn, _spec(one_chip, (m,), jnp.int32), _spec(one_chip, (m,), bool))


def _state_specs(sharding, cv, ce):
    i32 = jnp.int32
    return GraphState(
        v_key=_spec(sharding, (cv,), i32),
        v_live=_spec(sharding, (cv,), bool),
        v_inc=_spec(sharding, (cv,), i32),
        e_key_u=_spec(sharding, (ce,), i32),
        e_key_v=_spec(sharding, (ce,), i32),
        e_live=_spec(sharding, (ce,), bool),
        e_inc_u=_spec(sharding, (ce,), i32),
        e_inc_v=_spec(sharding, (ce,), i32),
    )


def test_delta_merge_compiles_past_int32_composite_keys(one_chip):
    """V_CAP * E_CAP = 2**42: the size the composite-key guard sent to the
    host splice."""
    i32 = jnp.int32
    cv, ce = V_CAP, E_CAP
    scalar = _spec(one_chip, (), i32)
    csr = TraversalCSR(
        v_key=_spec(one_chip, (cv,), i32),
        v_live=_spec(one_chip, (cv,), bool),
        v_inc=_spec(one_chip, (cv,), i32),
        n_live=scalar,
        src=_spec(one_chip, (ce,), i32),
        dst=_spec(one_chip, (ce,), i32),
        lane=_spec(one_chip, (ce,), i32),
        row_start=_spec(one_chip, (cv,), i32),
        row_end=_spec(one_chip, (cv,), i32),
        n_edges=scalar,
    )
    nv, ne = 2048, 8192
    fn = functools.partial(maintenance._delta_merge_device, nv=nv, ne=ne)
    _compile(fn, csr, _state_specs(one_chip, cv, ce), _spec(one_chip, (nv + 2 * ne,), i32))


def _frontier_kernel(one_chip):
    fn = frontier_kernel.frontier_expand
    return fn, (
        _spec(one_chip, (8, 1024), bool),
        _spec(one_chip, (4096,), jnp.int32),
        _spec(one_chip, (4096,), jnp.int32),
    )


def _masked_compact_kernel(one_chip):
    fn = functools.partial(compact_kernel.masked_compact, fill=-1)
    return fn, (_spec(one_chip, (3, 4096), jnp.int32), _spec(one_chip, (4096,), bool))


def _probe_place_kernel(one_chip):
    fn = functools.partial(compact_kernel.probe_place, capacity=4096, max_probes=MAX_PROBES)
    return fn, (_spec(one_chip, (2048,), jnp.int32), _spec(one_chip, (2048,), bool))


def _hash_probe_kernel(one_chip):
    return hash_probe_kernel.hash_probe, (
        _spec(one_chip, (4096,), jnp.int32),
        _spec(one_chip, (1024,), jnp.int32),
    )


@pytest.mark.parametrize(
    "build,words",
    [
        (_frontier_kernel, "Shape mismatch in input, indices and output"),
        (_masked_compact_kernel, "cumsum"),
        (_probe_place_kernel, "Only 2D gather is supported"),
        (_hash_probe_kernel, "Cannot do int indexing on TPU"),
    ],
    ids=["frontier", "masked_compact", "probe_place", "hash_probe"],
)
def test_pallas_kernel_refused(one_chip, build, words):
    fn, args = build(one_chip)
    with pytest.raises(Exception, match=words):
        _compile(fn, *args)
