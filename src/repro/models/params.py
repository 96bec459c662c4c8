"""Parameter specification trees: one source of truth for shapes, dtypes,
logical sharding axes, and initialization.

Each leaf is a ParamSpec(shape, dtype, axes) where `axes` are LOGICAL names
('embed', 'heads', 'vocab', 'experts', 'layers', ...).  launch/mesh.py maps
logical names to mesh axes (FSDP/TP/EP rules) — models never mention the
mesh.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


class ParamSpec(NamedTuple):
    shape: tuple
    dtype: object
    axes: tuple          # logical axis name (or None) per dim
    init_scale: float = 0.02


def is_spec(x):
    return isinstance(x, ParamSpec)


def abstract(tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype), tree, is_leaf=is_spec)


def axes_tree(tree):
    return jax.tree.map(lambda s: s.axes, tree, is_leaf=is_spec)


@functools.partial(jax.jit, static_argnums=0)
def _init_leaf(spec: ParamSpec, k):
    # jitted per leaf so the f32 draw fuses into the cast: a layer-stacked
    # bf16 leaf never exists as an f32 array in device memory
    if spec.init_scale == 0.0:
        return jnp.zeros(spec.shape, spec.dtype)
    if spec.init_scale == -1.0:              # ones (norm scales)
        return jnp.ones(spec.shape, spec.dtype)
    fan_in = spec.shape[-2] if len(spec.shape) >= 2 else spec.shape[-1]
    scale = min(spec.init_scale, 1.0 / np.sqrt(max(fan_in, 1)))
    return (jax.random.truncated_normal(k, -2, 2, spec.shape, jnp.float32)
            * scale).astype(spec.dtype)


def materialize(tree, key):
    leaves, treedef = jax.tree.flatten(tree, is_leaf=is_spec)
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(treedef, [_init_leaf(s, k)
                                        for s, k in zip(leaves, keys)])


def count_params(tree) -> int:
    return sum(int(np.prod(s.shape))
               for s in jax.tree.leaves(tree, is_leaf=is_spec))
