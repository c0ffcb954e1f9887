"""The plain reference of the VGG16 P2M pipeline, and the comparison with it
that decides ``correct`` for the ``p2m_vgg`` kind.

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

After the window has closed, each microbatch of the seeded sample
(``loops.Sample``) is recomputed from the benchmark's own weights and
frames, the engine's rng key for it (``fold_in(PRNGKey(engine seed), stream
index)``, and the microbatch's place folded in where an item has several)
and the threshold its draws ran at (``theta_used``, the engine's carried
value, as an LM check conditions on the served tokens). The answers
compared are the rows of the item's served result, after the engine merged
its microbatches. Four numbers are compared (``readings``), each against
its own limit from the configuration file (``limits``):

rate_flips      frontend: max over sampled microbatches and channels of
                |rate - reference rate| x rows per channel, i.e. the net
                number of activations of one channel that differ from the
                oracle's draws on the same words
theta_gap       frontend: max relative gap of the fresh Hoyer threshold
theta_guard     engine: max relative gap between the threshold a fused step
                drew at and the reference's fresh one; the configuration
                states the guard (``engine.fused_theta_tol``)
frames_off      backbone: share of the sampled requests whose log-
                probabilities differ from the reference's anywhere by more
                than ``LOGP_TOL``

``control`` computes what the reference gives, in the program's place, one
precision step below the configuration's (bf16 frontend operands, fp8
backbone operands): the check has to call it incorrect.
"""
from __future__ import annotations

import math
from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

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


# --- the comparison ---------------------------------------------------------

# a request is "off" when a log-probability moves by more than this; a
# request whose spikes all agree with the reference is ~1e-6 away
LOGP_TOL = 1e-3

_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
           "float8_e4m3fn": jnp.float8_e4m3fn}
# one precision step below each stated operand precision
_BELOW = {"float32": "bfloat16", "bfloat16": "float8_e4m3fn"}


def engine_seed(seed: int) -> int:
    """The integer seed handed to the engine (it makes its own key)."""
    return seed & 0x7FFFFFFF


def _precisions(cfg: Dict, control: bool) -> Tuple:
    p = cfg["precision"]
    fe, bb = p["frontend"], p["backbone_operands"]
    if control:
        fe, bb = _BELOW[fe], _BELOW[bb]
    return _DTYPES[fe], _DTYPES[bb]


def _reference_fn(cfg: Dict, control: bool):
    """The reference for one microbatch as one jitted program (compiled
    once per run and shape, and kept in the persistent cache)."""
    fe_dt, bb_dt = _precisions(cfg, control)

    def step(params, frames, key, theta_used):
        acts, fa = frontend(params, frames, key, theta_used, cfg, fe_dt)
        return (fa["theta"], fa["channel_rates"],
                jax.nn.log_softmax(backbone(params, acts, cfg, bb_dt)))
    return jax.jit(step)


def reference_step(cfg: Dict, params: Dict, frames: jax.Array,
                   key: jax.Array, theta_used: jax.Array,
                   control: bool = False, fn=None) -> Dict:
    """The reference's outputs for one microbatch: fresh theta, channel
    rates and log-probabilities (float64 on the host)."""
    fn = fn or _reference_fn(cfg, control)
    theta, rates, logp = fn(params, frames, key, theta_used)
    out_hw = -(-cfg["in_hw"] // cfg["p2m"]["stride"])
    return {"theta": float(theta),
            "channel_rates": np.asarray(rates, np.float64),
            "logp": np.asarray(logp, np.float64),
            "rows": frames.shape[0] * out_hw * out_hw}


def step_key(seed: int, index: int, part: int = 0, parts: int = 1
             ) -> jax.Array:
    """The engine's key for microbatch ``part`` of stream item ``index``:
    it folds its item counter into ``PRNGKey(seed)``, and an item of more
    than one microbatch folds in each one's place."""
    key = jax.random.fold_in(jax.random.PRNGKey(engine_seed(seed)), index)
    return key if parts == 1 else jax.random.fold_in(key, part)


def program_view(sample) -> Dict:
    """What the program served for one sampled microbatch, on the host."""
    out = sample.out
    rows = slice(sample.row0, sample.row0 + len(sample.frames))
    return {"theta": float(out["theta"]),
            "theta_used": float(out.get("theta_used", out["theta"])),
            "fused": float(out.get("stream_fused", 0.0)) > 0.5,
            "channel_rates": np.asarray(out["channel_rates"], np.float64),
            "logp": np.log(np.asarray(out["probs"], np.float64)[rows])}


def readings(cfg: Dict, params: Dict, pool: jax.Array, seed: int,
             samples: Sequence, control: bool = False) -> Dict[str, float]:
    """The four compared numbers over ``samples``. With ``control`` the
    program's outputs are replaced by the control's."""
    rate_flips = theta_gap = theta_guard = 0.0
    off = total = 0
    ref_fn = _reference_fn(cfg, False)
    ctl_fn = _reference_fn(cfg, True) if control else None
    with jax.default_matmul_precision("highest"):
        for s in samples:
            frames = jnp.take(pool, jnp.asarray(s.frames, jnp.int32), axis=0)
            prog = program_view(s)
            key = step_key(seed, s.index, s.part, s.parts)
            th_used = jnp.asarray(prog["theta_used"], jnp.float32)
            ref = reference_step(cfg, params, frames, key, th_used,
                                 fn=ref_fn)
            if control:
                ctl = reference_step(cfg, params, frames, key, th_used,
                                     fn=ctl_fn)
                prog = {**prog, "theta": ctl["theta"],
                        "channel_rates": ctl["channel_rates"],
                        "logp": ctl["logp"]}
            rate_flips = max(rate_flips, float(np.max(np.abs(
                prog["channel_rates"] - ref["channel_rates"]))) * ref["rows"])
            theta_gap = max(theta_gap, abs(prog["theta"] - ref["theta"])
                            / abs(ref["theta"]))
            if prog["fused"]:
                theta_guard = max(theta_guard,
                                  abs(prog["theta_used"] - ref["theta"])
                                  / abs(prog["theta_used"]))
            gap = np.max(np.abs(prog["logp"][:s.real]
                                - ref["logp"][:s.real]), axis=1)
            off += int(np.sum(~(gap <= LOGP_TOL)))
            total += s.real
    return {"rate_flips": rate_flips, "theta_gap": theta_gap,
            "theta_guard": theta_guard,
            "frames_off": off / max(total, 1)}


def limits(cfg: Dict) -> Dict[str, float]:
    lim = dict(cfg["limits"])
    lim["theta_guard"] = cfg["engine"]["fused_theta_tol"]
    return lim
