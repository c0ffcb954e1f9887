"""Weights and frames, made on the device from the run's seed.

The benchmark makes both itself, so that the program and the reference see
the same numbers and neither takes anything from the other. Each is one
jitted call; every seed gives the same shapes.
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp


def root_key(seed: int) -> jax.Array:
    """A key from any non-negative seed, including ones past 32 bits."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0x7FFFFFFF)


def engine_seed(seed: int) -> int:
    """The integer seed handed to the engine (it makes its own key)."""
    return seed & 0x7FFFFFFF


def _conv_leaf(key, cin: int, cout: int) -> Dict:
    w = jax.random.normal(key, (3, 3, cin, cout), jnp.float32)
    return {"w": w / (9 * cin) ** 0.5,
            "bn_scale": jnp.ones((cout,)), "bn_bias": jnp.zeros((cout,)),
            "bn_mean": jnp.zeros((cout,)), "bn_var": jnp.ones((cout,)),
            "v_th": jnp.ones(())}


@functools.partial(jax.jit, static_argnums=(1,))
def _weights(key, spec):
    plan, cin, p2m_shape, ncls = spec
    keys = jax.random.split(key, len(plan) + 2)
    fan = p2m_shape[0] * p2m_shape[1] * p2m_shape[2]
    p2m = {"w": jax.random.normal(keys[0], p2m_shape) * (2.0 / fan) ** 0.5,
           "v_th": jnp.ones(())}
    layers, i = {}, 0
    for item in plan:
        if item == "M":
            continue
        layers[f"conv{i}"] = _conv_leaf(keys[1 + i], cin, item)
        cin, i = item, i + 1
    head = {"w": jax.random.normal(keys[-1], (cin, ncls)) / cin ** 0.5,
            "b": jnp.zeros((ncls,))}
    return {"p2m": p2m, "layers": layers, "head": head}


def weights(cfg: Dict, seed: int) -> Dict:
    """The served model's parameters in the tree the engine takes: the
    P2M layer (He-normal, v_th 1), 13 convs (normal / sqrt(fan_in),
    BatchNorm at its initial running statistics) and the linear head."""
    p = cfg["p2m"]
    spec = (tuple(cfg["plan"]), p["out_channels"],
            (p["kernel_size"], p["kernel_size"], p["in_channels"],
             p["out_channels"]), cfg["num_classes"])
    return _weights(jax.random.fold_in(root_key(seed), 1), spec)


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _pool(key, n: int, hw: int, cin: int):
    k1, k2 = jax.random.split(key)
    side = max(hw // 8, 2)
    coarse = jax.random.uniform(k1, (n, side, side, cin))
    scene = jax.image.resize(coarse, (n, hw, hw, cin), "linear")
    grain = jax.random.uniform(k2, (n, hw, hw, cin))
    return jnp.clip(0.8 * scene + 0.2 * grain, 0.0, 1.0)


def frame_pool(cfg: Dict, n: int, seed: int) -> jax.Array:
    """``n`` frames (n, H, W, C) in [0, 1]: smooth random scenes (bilinear
    upsampling of an 8x coarser grid) with 20% pixel grain."""
    return _pool(jax.random.fold_in(root_key(seed), 2), n, cfg["in_hw"],
                 cfg["p2m"]["in_channels"])
