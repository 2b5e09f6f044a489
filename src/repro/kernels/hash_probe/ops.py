"""Public entry point for the batched hash probe.

The family follows the repo-wide ``kernel/ops/ref`` contract documented
once in ``docs/KERNELS.md`` (bit-identity between impls, env-var override,
interpret-mode CI parity).  Sharding note: the probe consumes only the
*suffix* bits of the 32-bit key hash (``& (capacity - 1)``); the *prefix*
bits route keys to shards (:mod:`repro.core.sharding`), so this kernel runs
unchanged on a per-shard table.
"""

from __future__ import annotations

import jax.numpy as jnp

from . import kernel as _kernel
from . import ref as _ref


def hash_probe(table_keys: jnp.ndarray, query_keys: jnp.ndarray, *, impl: str | None = None):
    # fixed rule: the TPU compiler refuses the kernel ("Cannot do int
    # indexing on TPU", docs/KERNELS.md), so it runs only interpreted
    impl = impl or "reference"
    if impl == "kernel_interpret":
        return _kernel.hash_probe(table_keys, query_keys, interpret=True)
    if impl == "reference":
        return _ref.hash_probe_reference(table_keys, query_keys)
    raise ValueError(f"unknown impl {impl!r}")
