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

The production meshes are functions (not module-level constants), so
importing this module never touches jax device state.  Single pod:
(16, 16) = (data, model) — 256 chips, 16 decentralized nodes x 16-way
tensor parallel.  Multi-pod: (2, 16, 16) = (pod, data, model) — 512 chips,
32 decentralized nodes; the gossip graph spans the flattened (pod, data)
axes so cross-pod edges ride the (slow) inter-pod links exactly ``degree``
times per step instead of an all-reduce.
"""

from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType

__all__ = ["make_mesh", "make_production_mesh", "node_axes_of", "MODEL_AXIS"]

MODEL_AXIS = "model"


def make_mesh(shape: Sequence[int], axes: Sequence[str], *, devices=None):
    """``jax.make_mesh`` with every axis ``AxisType.Auto`` (see module doc)."""
    return jax.make_mesh(
        tuple(shape), tuple(axes), (AxisType.Auto,) * len(axes), devices=devices
    )


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def node_axes_of(mesh) -> tuple[str, ...]:
    """The decentralized node axes = every axis except the model axis."""
    return tuple(a for a in mesh.axis_names if a != MODEL_AXIS)


def n_nodes_of(mesh) -> int:
    n = 1
    for a in node_axes_of(mesh):
        n *= mesh.shape[a]
    return n
