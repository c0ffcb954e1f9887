"""Spike map -> image embeddings: the P2M sensor as a language model's
vision input.

The frontend's binary activation map (B, H', W', C) is cut into
``PATCH`` x ``PATCH`` patches, each flattened to ``PATCH^2 * C`` values in
(row, column, channel) order, one image token per patch in row-major patch
order. A Kimi-VL-style MLP projector maps each to the language model's
width: LayerNorm -> linear -> GELU (erf) -> linear, both linears with bias.
At 224 x 224 frames (a 112 x 112 x 32 map) and 14 x 14 patches that is 64
tokens of 6,272 values, the count Kimi-VL gives a 224 x 224 image.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.models.params import ParamSpec

LN_EPS = 1e-5
# side of a patch of the spike map: one image token each
PATCH = 14


def patchify(spikes: jax.Array) -> jax.Array:
    """(B, H, W, C) -> (B, (H / PATCH) * (W / PATCH), PATCH * PATCH * C)."""
    b, h, w, c = spikes.shape
    p = PATCH
    if h % p or w % p:
        raise ValueError(f"a {h}x{w} map does not cut into {p}x{p} patches")
    x = spikes.reshape(b, h // p, p, w // p, p, c).transpose(0, 1, 3, 2, 4, 5)
    return x.reshape(b, (h // p) * (w // p), p * p * c)


def spec(in_dim: int, hidden: int, out_dim: int) -> Dict[str, Any]:
    return {
        "ln_scale": ParamSpec((in_dim,), (None,), init="ones"),
        "ln_bias": ParamSpec((in_dim,), (None,), init="zeros"),
        "w1": ParamSpec((in_dim, hidden), (None, "ffn")),
        "b1": ParamSpec((hidden,), ("ffn",), init="zeros"),
        "w2": ParamSpec((hidden, out_dim), ("ffn", "embed")),
        "b2": ParamSpec((out_dim,), ("embed",), init="zeros"),
    }


def apply(params: Dict, spikes: jax.Array, dtype) -> jax.Array:
    """Image embeddings (B, tokens, out_dim) in ``dtype`` from a spike map;
    the norm is taken in f32."""
    x = patchify(spikes).astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    x = (x - mu) * jax.lax.rsqrt(var + LN_EPS)
    x = (x * params["ln_scale"].astype(jnp.float32)
         + params["ln_bias"].astype(jnp.float32)).astype(dtype)
    h = x @ params["w1"].astype(dtype) + params["b1"].astype(dtype)
    h = jax.nn.gelu(h, approximate=False)
    return h @ params["w2"].astype(dtype) + params["b2"].astype(dtype)
