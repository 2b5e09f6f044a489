"""Public entry point for the batched frontier expansion.

Dispatch is a fixed rule: every backend runs the XLA implementation
(:mod:`.xla`).  The Pallas kernel (:mod:`.kernel`) is refused by the TPU
compiler — Mosaic lowers no scatter and no 1-D gather (the compiler's words
are in ``docs/KERNELS.md``) — so it runs only through the Pallas interpreter,
where the tests hold it bit-identical to the XLA path.  ``REPRO_FRONTIER_IMPL``
overrides the default (CI's ``kernels-interpret`` job sets it to
``kernel_interpret``).
"""

from __future__ import annotations

import os

import jax.numpy as jnp

from . import kernel as _kernel
from . import xla as _xla


def resolve(impl: str | None = None) -> str:
    """The implementation a call with ``impl`` runs."""
    return impl or os.environ.get("REPRO_FRONTIER_IMPL") or "xla"


def frontier_expand(
    frontier: jnp.ndarray,
    src: jnp.ndarray,
    dst: jnp.ndarray,
    *,
    impl: str | None = None,
    view: _xla.PullView | None = None,
) -> jnp.ndarray:
    """i32[S, C]: min frontier source slot over in-edges, NBR_INF where none.

    ``view`` is the XLA path's :func:`~.xla.pull_view` of ``(src, dst)``,
    built once for many levels; the kernel takes ``src`` and ``dst`` as
    they are."""
    impl = resolve(impl)
    if impl == "xla" and view is not None:
        return _xla.frontier_expand_pull(frontier, view)
    if impl == "xla":
        return _xla.frontier_expand_xla(frontier, src, dst)
    if impl == "kernel_interpret":
        return _kernel.frontier_expand(frontier, src, dst, interpret=True)
    raise ValueError(f"unknown impl {impl!r}")
