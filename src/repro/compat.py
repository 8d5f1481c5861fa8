"""Mesh and shard_map import point.

``make_mesh`` requests Auto axis types: ``jax.make_mesh`` defaults to
Explicit axes, and every sharding rule in this repo is written for
compiler-propagated (Auto) shardings.
"""

from __future__ import annotations

import jax

shard_map = jax.shard_map


def make_mesh(axis_shapes, axis_names, **kw):
    """``jax.make_mesh`` with Auto axis types."""
    kw.setdefault("axis_types",
                  (jax.sharding.AxisType.Auto,) * len(axis_names))
    return jax.make_mesh(axis_shapes, axis_names, **kw)
