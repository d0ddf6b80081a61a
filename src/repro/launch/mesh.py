"""Mesh construction: the one place that picks the mesh's axis types.

Every mesh of the main path is built by :func:`make_mesh`, with every axis
``AxisType.Auto``.  The train and serve steps are fully-manual
``jax.shard_map`` regions over all axes, and the code around them (state
init through jit-with-out-shardings, telemetry reads, checkpointing) indexes
and reshapes sharded arrays as plain values.  ``jax.make_mesh`` defaults to
``AxisType.Explicit`` instead, which puts the sharding into every array's
type: a plain ``x[0]`` on a node-sharded array then raises
``ShardingTypeError`` and a jit with mismatched device counts asks for
``jax.set_mesh``.  Auto axes keep sharding a property of placement, which
is what the manual step was written against.
"""

from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType

__all__ = ["make_mesh"]


def make_mesh(shape: Sequence[int], axes: Sequence[str], *, devices=None):
    """``jax.make_mesh`` with every axis ``AxisType.Auto`` (see module doc)."""
    return jax.make_mesh(
        tuple(shape), tuple(axes), (AxisType.Auto,) * len(axes), devices=devices
    )
