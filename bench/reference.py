"""The plain reference of the VGG16 P2M pipeline, independent of the program.

Imports nothing of ``repro``: the sensor physics (pixel curve, threshold-
matched subtractor, VC-MTJ switching fit, 8-MTJ majority fold, the counter-
hash draw words) and the sparse-BNN backbone are written out here in plain
``jax.numpy`` float32 at ``highest`` matmul precision, from the numbers in a
configuration file (``configs/<name>.json``). Later edits to the program
cannot move it.

What it computes for one served microbatch of frames, its rng key and the
threshold the draws ran at:

* ``frontend``: the binary activation map (one Bernoulli draw per activation
  against the folded-majority probability, on the same uint16 words the
  program's draw generator makes from the key), the fresh global Hoyer
  threshold of the microbatch and the per-channel activation rates;
* ``backbone``: the logits of the 13 binary conv layers + global average
  pool + linear head, with each layer's spikes thresholded per example at
  its Hoyer extremum. The conv and head weights are rounded to
  ``operand_dtype`` first: the configuration states bf16 operands with f32
  accumulation (what a TPU does with an f32 conv at default precision), and
  a binary-spike network turns any other rounding into different spikes.
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
_DN = ("NHWC", "HWIO", "NHWC")


# --- sensor physics ---------------------------------------------------------

def quantize(w: jax.Array, bits: int) -> jax.Array:
    """Symmetric per-tensor fake quantization to ``bits`` bits."""
    qmax = 2.0 ** (bits - 1) - 1.0
    scale = jnp.maximum(jnp.max(jnp.abs(w)), 1e-8) / qmax
    return jnp.round(w / scale) * scale


def _fmix32(x: jax.Array) -> jax.Array:
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    return x ^ (x >> 16)


def draw_words(key: jax.Array, n: int, c: int) -> jax.Array:
    """(n, c) uint16 words: two murmur3 finalizer rounds over
    ``(index + golden ratio) ^ key word``, as the sensor model specifies."""
    kd = jax.random.key_data(key).astype(jnp.uint32)
    idx = jax.lax.iota(jnp.uint32, n * c) + jnp.uint32(0x9E3779B9)
    h = _fmix32(_fmix32(idx ^ kd[0]) ^ kd[1])
    return (h & jnp.uint32(0xFFFF)).astype(jnp.uint16).reshape(n, c)


def _curve(x: jax.Array, px: Dict) -> jax.Array:
    sat = px["saturation"]
    return sat * jnp.tanh(x / sat)


def _logit(p: float) -> float:
    return math.log(p / (1.0 - p))


def switching_probability(v: jax.Array, mtj: Dict) -> jax.Array:
    """P(AP->P) at the nominal write pulse: piecewise linear in logit
    space through the three measured points (the pulse envelope is 1)."""
    v0, v1, v2 = mtj["measured_voltages"]
    l0, l1, l2 = (_logit(p) for p in mtj["measured_p_sw"])
    lo = l0 + (l1 - l0) / (v1 - v0) * (v - v0)
    hi = l1 + (l2 - l1) / (v2 - v1) * (v - v1)
    return jax.nn.sigmoid(jnp.where(v < v1, lo, hi))


def majority(p: jax.Array, n: int, m: int) -> jax.Array:
    """P(Binomial(n, p) >= m)."""
    out = jnp.zeros_like(p)
    for k in range(m, n + 1):
        out = out + math.comb(n, k) * (p ** k) * ((1 - p) ** (n - k))
    return out


def hoyer_extremum(zc: jax.Array, axis=None, keepdims=False) -> jax.Array:
    num = jnp.sum(zc * zc, axis=axis, keepdims=keepdims)
    den = jnp.sum(jnp.abs(zc), axis=axis, keepdims=keepdims)
    return num / jnp.maximum(den, 1e-9)


def frontend_u(frames: jax.Array, w: jax.Array, cfg: Dict,
               operand_dtype=jnp.float32) -> jax.Array:
    """The subtractor output u (B, H', W', C): both integration phases of
    the 4-bit weights through the pixel curve, then their difference."""
    p = cfg["p2m"]
    wq = quantize(w, p["weight_bits"])
    cast = lambda a: a.astype(operand_dtype).astype(jnp.float32)  # noqa: E731
    s = p["stride"]

    def phase(wp):
        return jax.lax.conv_general_dilated(
            cast(frames), cast(wp), (s, s), "SAME", dimension_numbers=_DN,
            precision=HIGHEST)
    px = cfg["pixel"]
    return (_curve(phase(jnp.maximum(wq, 0.0)), px)
            - _curve(phase(jnp.maximum(-wq, 0.0)), px))


def fresh_theta(u: jax.Array, v_th: jax.Array) -> jax.Array:
    """The microbatch's global Hoyer threshold in conv-output units."""
    z = jnp.clip(u / jnp.maximum(v_th, 1e-6), 0.0, 1.0)
    return hoyer_extremum(z) * v_th


def mtj_switching(u: jax.Array, theta: jax.Array, cfg: Dict) -> jax.Array:
    """u at threshold theta -> V_CONV -> one MTJ's P_sw."""
    px = cfg["pixel"]
    vpu = px["vdd"] / (2.0 * px["norm_range"])
    v_th = 0.5 * px["vdd"] + vpu * theta
    v = 0.5 * px["vdd"] + (px["v_sw"] - v_th) + vpu * u
    return switching_probability(jnp.clip(v, 0.0, 1.2 * px["vdd"]),
                                 cfg["mtj"])


def draw(p_sw: jax.Array, key: jax.Array, cfg: Dict) -> jax.Array:
    """The activation map in {0, 1} under the configuration's draw model.

    ``hash_words``: one draw per activation against the folded majority
    probability, on the counter-hash uint16 words of ``key``.
    ``per_mtj``: each of the n MTJs switches on its own
    ``jax.random.bernoulli`` draw of ``key`` and the majority votes."""
    n = cfg["mtj"]["n_redundant"]
    if cfg["draws"] == "per_mtj":
        votes = jax.random.bernoulli(key, p_sw[..., None], p_sw.shape + (n,))
        return (jnp.sum(votes.astype(jnp.int32), axis=-1)
                >= n // 2).astype(jnp.float32)
    q = majority(p_sw, n, n // 2)
    b, h, w, c = q.shape
    words = draw_words(key, b * h * w, c).reshape(q.shape)
    return ((words.astype(jnp.int32).astype(jnp.float32) * 2.0 ** -16)
            < q).astype(jnp.float32)


def frontend(params: Dict, frames: jax.Array, key: jax.Array,
             theta_used: jax.Array, cfg: Dict,
             operand_dtype=jnp.float32) -> Tuple[jax.Array, Dict]:
    """(activations (B, H', W', C) in {0, 1}, {"theta", "channel_rates"}):
    the draws run at ``theta_used``; ``theta`` is the fresh threshold."""
    u = frontend_u(frames, params["p2m"]["w"], cfg, operand_dtype)
    theta = fresh_theta(u, params["p2m"]["v_th"])
    acts = draw(mtj_switching(u, theta_used, cfg), key, cfg)
    return acts, {"theta": theta,
                  "channel_rates": jnp.mean(acts, axis=(0, 1, 2))}


# --- backbone ---------------------------------------------------------------

def _maxpool(x: jax.Array) -> jax.Array:
    return jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                 (1, 2, 2, 1), (1, 2, 2, 1), "SAME")


def backbone(params: Dict, x: jax.Array, cfg: Dict,
             operand_dtype=jnp.bfloat16) -> jax.Array:
    """Logits of the binary backbone on frontend activations ``x``.

    Eval semantics: BatchNorm with the stored running statistics, and each
    layer spikes where z = y / v_th reaches the example's own Hoyer
    extremum of clip(z, 0, 1)."""
    cast = lambda a: a.astype(operand_dtype).astype(jnp.float32)  # noqa: E731
    bits = cfg["weight_bits"]
    i = 0
    for item in cfg["plan"]:
        if item == "M":
            if x.shape[1] > 1:
                x = _maxpool(x)
            continue
        p = params["layers"][f"conv{i}"]
        y = jax.lax.conv_general_dilated(
            cast(x), cast(quantize(p["w"], bits)), (1, 1), "SAME",
            dimension_numbers=_DN, precision=HIGHEST)
        y = (y - p["bn_mean"]) / jnp.sqrt(p["bn_var"] + 1e-5)
        y = y * p["bn_scale"] + p["bn_bias"]
        z = y / jnp.maximum(p["v_th"], 1e-6)
        thr = hoyer_extremum(jnp.clip(z, 0.0, 1.0), axis=(1, 2, 3),
                             keepdims=True)
        x = (z >= thr).astype(jnp.float32)
        i += 1
    feat = jnp.mean(x, axis=(1, 2))
    return (jnp.dot(cast(feat), cast(params["head"]["w"]), precision=HIGHEST)
            + params["head"]["b"])


