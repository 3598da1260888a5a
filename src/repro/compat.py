"""The one JAX default this repo overrides.

``jax.make_mesh`` makes every axis ``Explicit`` by default in the
installed JAX (0.9), which types sharding into every array; the library
and its tests are written for ``Auto`` axes, where the partitioner
places arrays and ``jax.shard_map`` regions name their own specs.
"""

from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str],
              axis_types=None, devices=None):
    """``jax.make_mesh`` with ``Auto`` axes unless told otherwise."""
    if axis_types is None:
        axis_types = (AxisType.Auto,) * len(axis_names)
    return jax.make_mesh(axis_shapes, axis_names, axis_types=axis_types,
                         devices=devices)
