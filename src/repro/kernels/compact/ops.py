"""Public entry points for the compaction primitives.

Dispatch is a fixed rule, as for ``repro.kernels.frontier``: every backend
runs the XLA implementation (:mod:`.xla`).  The TPU compiler refuses both
Pallas kernels (:mod:`.kernel`) — Mosaic lowers no ``cumsum`` and no 1-D
gather; the compiler's words are in ``docs/KERNELS.md`` — so they run only
through the Pallas interpreter, where the tests hold them bit-identical to
the XLA path.  ``REPRO_COMPACT_IMPL`` overrides the default (CI's
``kernels-interpret`` job sets it to ``kernel_interpret``).  Callers that
need a *host* (numpy) oracle use ``repro.core.maintenance`` instead.
"""

from __future__ import annotations

import os

import jax.numpy as jnp

from . import kernel as _kernel
from . import xla as _xla


def resolve(impl: str | None = None) -> str:
    """The implementation a call with ``impl`` runs."""
    return impl or os.environ.get("REPRO_COMPACT_IMPL") or "xla"


def masked_compact(
    values: jnp.ndarray,
    mask: jnp.ndarray,
    *,
    fill: int,
    impl: str | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    impl = resolve(impl)
    if impl == "xla":
        return _xla.masked_compact_xla(values, mask, fill=fill)
    if impl == "kernel_interpret":
        return _kernel.masked_compact(values, mask, fill=fill, interpret=True)
    raise ValueError(f"unknown impl {impl!r}")


def probe_place(
    home: jnp.ndarray,
    active: jnp.ndarray,
    *,
    capacity: int,
    max_probes: int,
    impl: str | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    impl = resolve(impl)
    if impl == "xla":
        return _xla.probe_place_xla(home, active, capacity=capacity, max_probes=max_probes)
    if impl == "kernel_interpret":
        return _kernel.probe_place(
            home, active, capacity=capacity, max_probes=max_probes, interpret=True
        )
    raise ValueError(f"unknown impl {impl!r}")
