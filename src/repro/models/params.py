"""Parameter-spec system: shapes + logical axes + init, in one tree.

Every module declares a tree of ``ParamSpec`` leaves. From it we derive:
  * materialized params           (init_tree)
  * abstract params               (abstract_tree — ShapeDtypeStructs, dry-run)
  * logical-axis tree             (axes_tree — feeds sharding.tree_shardings)
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]
    init: str = "normal"      # normal | zeros | ones
    scale: float = 1.0        # multiplier on 1/sqrt(fan_in) for "normal"
    dtype: Optional[str] = None   # override the tree-wide dtype (e.g. "int32")

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def is_spec(x) -> bool:
    return isinstance(x, ParamSpec)


def _fan_in(shape: Tuple[int, ...]) -> int:
    return math.prod(shape[:-1]) if len(shape) > 1 else shape[0] or 1


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _normal(key: jax.Array, shape: Tuple[int, ...], std: float, dtype):
    """N(0, std^2) drawn in f32 and cast to a narrower ``dtype``, as one
    program: the f32 draw is fused into the cast and never held whole, so a
    leaf costs its own bytes only (a stacked bf16 expert leaf would
    otherwise need twice that again in f32 beside it)."""
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def init_tree(key: jax.Array, spec_tree, dtype) -> dict:
    leaves, treedef = jax.tree.flatten(spec_tree, is_leaf=is_spec)
    keys = jax.random.split(key, len(leaves))

    def one(k, s: ParamSpec):
        dt = jnp.dtype(s.dtype) if s.dtype else dtype
        if s.init == "zeros":
            return jnp.zeros(s.shape, dt)
        if s.init == "ones":
            return jnp.ones(s.shape, dt)
        std = s.scale / (_fan_in(s.shape) ** 0.5)
        if dt == jnp.float32:
            return jax.random.normal(k, s.shape, jnp.float32) * std
        return _normal(k, tuple(s.shape), std, dt)

    return jax.tree.unflatten(treedef, [one(k, s) for k, s in zip(keys, leaves)])


def abstract_tree(spec_tree, dtype):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.dtype(s.dtype) if s.dtype
                                       else dtype),
        spec_tree, is_leaf=is_spec)


def axes_tree(spec_tree):
    return jax.tree.map(lambda s: s.axes, spec_tree, is_leaf=is_spec)


def stack_specs(spec_tree, n: int):
    """Prepend a scan-stack axis of size n to every spec (logical axis 'stack')."""
    return jax.tree.map(
        lambda s: ParamSpec((n,) + s.shape, ("stack",) + s.axes, s.init,
                            s.scale, s.dtype),
        spec_tree, is_leaf=is_spec)
