"""Production mesh definition.

Defined as FUNCTIONS (not module-level constants) so importing this module
never touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import, and everything else (tests, benches) must keep seeing 1 device.

Every mesh in the repo is built by :func:`make_mesh`: ``jax.make_mesh``
defaults to Explicit axis types, under which the bare-``PartitionSpec``
activation constraints and the ``jax.sharding.get_abstract_mesh()`` lookups
of the model code are type errors.  The model is written for Auto axes
(sharding propagated by the compiler from the weight specs), so that is what
the helper asks for.  Enter a mesh with ``jax.set_mesh(mesh)``.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``AxisType.Auto``."""
    return jax.make_mesh(
        tuple(shape), tuple(axes), axis_types=(AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)
