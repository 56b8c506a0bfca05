"""Mesh for the suffix-array pipeline.

A FUNCTION (not module-level constant) so importing this module never touches
jax device state.  The SA pipeline flattens whatever mesh it is given into one
shard axis (``sa``).
"""
from __future__ import annotations

import numpy as np


def make_sa_mesh(num_shards: int | None = None):
    """Flat 1-D mesh for the suffix-array pipeline."""
    import jax
    from jax.sharding import Mesh

    devs = np.array(jax.devices())
    if num_shards is not None:
        devs = devs[:num_shards]
    return Mesh(devs, ("sa",))
