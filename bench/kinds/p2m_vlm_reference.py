"""The plain reference of a P2M-fed vision-language decoder (Kimi-VL's
language model behind the sensor), and the comparison with it that decides
``correct`` for the ``p2m_vlm`` kind.

Imports nothing of ``repro``: the sensor is ``p2m_vgg_reference``'s oracle;
the projector and the decoder are written out here from the equations of
the configuration (``configs/<name>.json``: the model's own ``config.json``
keys) in plain ``jax.numpy`` float32 at ``highest`` matmul precision, with
no cache, no kernel and no batching of the program's kind:

* projector: 14 x 14 patches of the spike map, (row, column, channel)
  order, LayerNorm -> linear -> GELU (erf) -> linear;
* decoder: image embeddings at the first positions, token embeddings
  (unscaled) after them; per layer RMSNorm -> MLA (queries without a
  low-rank step, a normalised rank-512 latent expanded to per-head keys
  and values, one shared 64-wide rotary key, rotate-half RoPE at
  ``rope_theta``, causal softmax at 1/sqrt(qk_nope + qk_rope)) -> residual
  -> RMSNorm -> SwiGLU (the leading dense layers) or the MoE: f32 sigmoid
  scores of an f32 router, the top-k chosen on scores + the selection bias,
  weighted by their scores normalised to sum 1 times
  ``routed_scaling_factor``, every expert's SwiGLU over every token times
  its weight (zero where not chosen), plus the shared experts as one
  SwiGLU -> residual; final RMSNorm, untied head.

Served weights are bf16; the reference reads them as they are, upcast per
layer (experts one at a time; a layer the program stacks with others for
its scan is read at its place in the stack), beside the served weights on
the device.

After the window, one request of each sampled microbatch (``loops.Sample``)
is recomputed teacher-forced: its prompt plus the tokens the program
generated, in one full forward pass. Compared (``readings``), each against
its limit in the configuration (``limits``):

logit_err       max over the compared (request, step) of the relative L2
                gap |prog - ref| / |ref| of the logits: prefill's last
                position and the decode steps ``KEEP_STEPS``
logit_err_p50   the median of the same gaps
route_disagree  share of compared (position, MoE layer) whose chosen
                expert set differs from the reference's: every prompt
                position, and the kept decode steps
token_faults    compared (request, step) whose returned token is not the
                greedy pick of the program's own logits there, or, where
                the reference's top logit leads its second by more than
                twice the step's largest logit gap |prog - ref| (no
                rounding within that gap can change the pick), not the
                reference's pick; the second holds the returned tokens to
                the reference itself (it follows from the first while the
                returned logits are the ones the pick read)
rate_flips      frontend, as ``p2m_vgg``: max over sampled microbatches and
                channels of |rate - reference rate| x rows per channel
theta_gap       frontend: max relative gap of the fresh Hoyer threshold

``control`` puts the reference, one precision step below the
configuration's (bf16 operands -> fp8, f32 router -> bf16), in the
program's place, its tokens the greedy picks of its logits: the check has
to call it incorrect.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.kinds import p2m_vgg_reference as sensor

HIGHEST = jax.lax.Precision.HIGHEST
LN_EPS = 1e-5
# decode steps whose logits are compared, beside prefill's last position
# (step 0); steps past the request's answer are left out
KEEP_STEPS = (0, 1, 64, 127)

_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
           "float8_e4m3fn": jnp.float8_e4m3fn}
_BELOW = {"float32": "bfloat16", "bfloat16": "float8_e4m3fn"}


def kept_steps(new_tokens: int) -> Tuple[int, ...]:
    return tuple(s for s in KEEP_STEPS if s < new_tokens)


def _dot(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def _caster(name: str, control: bool):
    """Rounds matmul operands to the configuration's precision ``name``
    (one step below it for the control), computing in f32."""
    dt = _DTYPES[_BELOW[name] if control else name]
    return lambda a: a.astype(dt).astype(jnp.float32)


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, pos, theta):
    """Rotate-half RoPE over the last axis; x (..., S, H, d), pos (S,)."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def swiglu(x, w1, w3, w2, c):
    return _dot(c(jax.nn.silu(_dot(c(x), c(w1))) * _dot(c(x), c(w3))), c(w2))


def mla(p, x, cfg, c):
    """Causal multi-head latent attention over the whole sequence."""
    b, s, _ = x.shape
    h, dn, dr = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                 cfg["qk_rope_head_dim"])
    pos = jnp.arange(s)
    q = jnp.einsum("bsd,dhk->bshk", c(x), c(p["wq"]), precision=HIGHEST)
    q = jnp.concatenate([q[..., :dn], rope(q[..., dn:], pos,
                                           cfg["rope_theta"])], -1)
    ckv = rmsnorm(_dot(c(x), c(p["wdkv"])), p["kv_norm"], cfg["rms_norm_eps"])
    kr = rope(_dot(c(x), c(p["wkr"]))[:, :, None, :], pos, cfg["rope_theta"])
    kn = jnp.einsum("bsr,rhk->bshk", c(ckv), c(p["wuk"]), precision=HIGHEST)
    v = jnp.einsum("bsr,rhk->bshk", c(ckv), c(p["wuv"]), precision=HIGHEST)
    k = jnp.concatenate([kn, jnp.broadcast_to(kr, (b, s, h, dr))], -1)
    sc = jnp.einsum("bqhk,bthk->bhqt", c(q), c(k),
                    precision=HIGHEST) / math.sqrt(dn + dr)
    sc = jnp.where(pos[:, None] >= pos[None, :], sc, -jnp.inf)
    o = jnp.einsum("bhqt,bthk->bqhk", c(jax.nn.softmax(sc, -1)), c(v),
                   precision=HIGHEST)
    return jnp.einsum("bshk,hkd->bsd", c(o), c(p["wo"]), precision=HIGHEST)


def route(x, router, bias, cfg, c_router):
    """(dense combine weights (T, E), chosen experts (T, K))."""
    scores = jax.nn.sigmoid(_dot(c_router(x), c_router(router)))
    _, idx = jax.lax.top_k(scores + bias, cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(scores, idx, -1)
    if cfg["norm_topk_prob"]:
        w = w / jnp.sum(w, -1, keepdims=True)
    w = w * cfg["routed_scaling_factor"]
    dense = jnp.zeros_like(scores).at[
        jnp.arange(scores.shape[0])[:, None], idx].set(w)
    return dense, idx


def _at(a, at):
    """Layer ``at`` of a stacked weight; the weight itself where ``at`` is
    None."""
    return a if at is None else a[at]


@functools.partial(jax.jit, static_argnames=("cfg", "control"))
def _dense_layer(lp, x, at, *, cfg, control):
    cfg = dict(cfg)
    lp = jax.tree.map(lambda a: _at(a, at), lp)
    c = _caster(cfg["_prec"]["weights"], control)
    eps = cfg["rms_norm_eps"]
    x = x + mla(lp["attn"], rmsnorm(x, lp["ln1"], eps), cfg, c)
    m = lp["mlp"]
    return x + swiglu(rmsnorm(x, lp["ln2"], eps), m["w1"], m["w3"], m["w2"], c)


@functools.partial(jax.jit, static_argnames=("cfg", "control"))
def _moe_layer(lp, x, at, *, cfg, control):
    """One MoE layer: (x, chosen experts); the experts one at a time, each
    read alone out of the stacked weights."""
    cfg = dict(cfg)
    ws = {n: lp[n] for n in ("w1", "w2", "w3")}
    lp = {k: jax.tree.map(lambda a: _at(a, at), v) for k, v in lp.items()
          if k not in ws}
    c = _caster(cfg["_prec"]["weights"], control)
    cr = _caster(cfg["_prec"]["router"], control)
    eps = cfg["rms_norm_eps"]
    x = x + mla(lp["attn"], rmsnorm(x, lp["ln1"], eps), cfg, c)
    b, s, d = x.shape
    h = rmsnorm(x, lp["ln2"], eps).reshape(b * s, d)
    dense, idx = route(h, lp["router"], lp["bias"], cfg, cr)
    sh = lp["shared"]
    y = swiglu(h, sh["w1"], sh["w3"], sh["w2"], c)

    def expert(e, acc):
        w1, w3, w2 = (ws[n][e] if at is None else ws[n][at, e]
                      for n in ("w1", "w3", "w2"))
        return acc + dense[:, e, None] * swiglu(h, w1, w3, w2, c)
    y = jax.lax.fori_loop(0, dense.shape[1], expert, y)
    return x + y.reshape(b, s, d), idx.reshape(b, s, -1)


@functools.partial(jax.jit, static_argnames=("cfg", "control"))
def _head(w, x, *, cfg, control):
    cfg = dict(cfg)
    c = _caster(cfg["_prec"]["weights"], control)
    return _dot(c(rmsnorm(x, w["final_norm"], cfg["rms_norm_eps"])),
                c(w["lm_head"]))


@functools.partial(jax.jit, static_argnames=("patch", "prec", "control"))
def projector(p, spikes, *, patch, prec, control):
    """(B, H, W, C) spikes -> (B, tokens, d_model) image embeddings."""
    c = _caster(prec, control)
    b, hh, ww, ch = spikes.shape
    x = spikes.reshape(b, hh // patch, patch, ww // patch, patch, ch)
    x = x.transpose(0, 1, 3, 2, 4, 5).reshape(b, -1, patch * patch * ch)
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    x = (x - mu) / jnp.sqrt(var + LN_EPS) * p["ln_scale"] + p["ln_bias"]
    x = jax.nn.gelu(_dot(c(x), c(p["w1"])) + p["b1"], approximate=False)
    return _dot(c(x), c(p["w2"])) + p["b2"]


def _hashable(cfg: Dict) -> Tuple:
    """The model's numbers and stated precisions as a jit static."""
    keys = ("num_attention_heads", "qk_nope_head_dim", "qk_rope_head_dim",
            "rope_theta", "rms_norm_eps", "num_experts_per_tok",
            "norm_topk_prob", "routed_scaling_factor")
    prec = cfg["precision"]
    return tuple((k, cfg[k]) for k in keys) + (
        ("_prec", _Frozen(weights=prec["weights"], router=prec["router"])),)


class _Frozen(dict):
    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def decoder(w: Dict, cfg: Dict, ids: jax.Array, images: jax.Array,
            read: Sequence[int], control: bool = False
            ) -> Tuple[np.ndarray, np.ndarray]:
    """Teacher-forced forward of ``ids`` (R, S) with ``images`` (R, N, D)
    at the first N positions: (logits at positions ``read`` (R, P, V) f64,
    each MoE layer's chosen experts (layers, R, S, K))."""
    key = _hashable(cfg)
    x = jnp.take(w["embed"], ids, axis=0).astype(jnp.float32)
    x = jnp.concatenate([images.astype(jnp.float32),
                         x[:, images.shape[1]:]], 1)
    routes = []
    with jax.default_matmul_precision("highest"):
        for lp, at in w["layers"]:
            at = None if at is None else jnp.asarray(at, jnp.int32)
            if "router" not in lp:
                x = _dense_layer(lp, x, at, cfg=key, control=control)
                continue
            x, idx = _moe_layer(lp, x, at, cfg=key, control=control)
            routes.append(np.asarray(idx))
        logits = _head(w, x[:, jnp.asarray(read)], cfg=key, control=control)
    return np.asarray(logits, np.float64), np.stack(routes)


# --- the comparison ---------------------------------------------------------

def image_embeddings(w: Dict, cfg: Dict, frames: jax.Array, key: jax.Array,
                     theta_used: float, control: bool = False) -> Dict:
    """One microbatch through the sensor oracle and the projector: image
    embeddings (B, N, D), the fresh threshold and the channel rates."""
    fe = cfg["frontend"]
    below = _BELOW[fe["precision"]["frontend"]]
    fe_dt = _DTYPES[below if control else fe["precision"]["frontend"]]
    with jax.default_matmul_precision("highest"):
        acts, aux = sensor.frontend({"p2m": w["p2m"]}, frames, key,
                                    jnp.asarray(theta_used, jnp.float32), fe,
                                    fe_dt)
        emb = projector(w["projector"], acts, patch=cfg["projector"]["patch"],
                        prec=cfg["precision"]["weights"], control=control)
    out_hw = acts.shape[1]
    return {"images": emb, "theta": float(aux["theta"]),
            "channel_rates": np.asarray(aux["channel_rates"], np.float64),
            "rows": frames.shape[0] * out_hw * out_hw}


def _sets_differ(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    return np.any(np.sort(a, -1) != np.sort(b, -1), -1)


def token_fault(token: int, prog: np.ndarray, ref: np.ndarray) -> bool:
    """Whether ``token``, returned at a step with the logits ``prog``, is
    not the greedy pick of ``prog``, or not the pick of the reference's
    ``ref`` where that pick leads by more than twice the largest gap."""
    if token != int(np.argmax(prog)):
        return True
    second, first = np.partition(ref, -2)[-2:]
    return bool(first - second > 2 * np.max(np.abs(prog - ref))
                and token != int(np.argmax(ref)))


def readings(cfg: Dict, w: Dict, pool: jax.Array, seed: int,
             samples: Sequence, control: bool = False) -> Dict[str, float]:
    """The compared numbers over ``samples``; with ``control`` the
    program's outputs are replaced by the control's."""
    rate_flips = theta_gap = 0.0
    ids, imgs, ctl_imgs, views = [], [], [], []
    for s in samples:
        frames = jnp.take(pool, jnp.asarray(s.frames, jnp.int32), axis=0)
        v = s.out
        key = sensor.step_key(seed, s.index, s.part, s.parts)
        ref = image_embeddings(w, cfg, frames, key, v["theta"])
        prog = (image_embeddings(w, cfg, frames, key, v["theta"], True)
                if control else v)
        rate_flips = max(rate_flips, float(np.max(np.abs(
            np.asarray(prog["channel_rates"]) - ref["channel_rates"])))
            * ref["rows"])
        theta_gap = max(theta_gap, abs(prog["theta"] - ref["theta"])
                        / abs(ref["theta"]))
        n = len(v["tokens"])
        ids.append(np.concatenate([v["prompt"], v["tokens"][:n - 1]]))
        imgs.append(ref["images"][v["row"]])
        if control:
            ctl_imgs.append(prog["images"][v["row"]])
        views.append(v)
    s_len = len(views[0]["prompt"])
    steps = kept_steps(len(views[0]["tokens"]))
    read = [s_len - 1 + k for k in steps]
    ids = jnp.asarray(np.stack(ids), jnp.int32)
    ref_logits, ref_routes = decoder(w, cfg, ids, jnp.stack(imgs), read)
    if control:
        ctl_logits, ctl_routes = decoder(w, cfg, ids, jnp.stack(ctl_imgs),
                                         read, control=True)
    errs: List[float] = []
    differ = total = faults = 0
    for r, v in enumerate(views):
        if control:
            logits = ctl_logits[r]
            routes = {k: ctl_routes[:, r, :s_len] if k == 0
                      else ctl_routes[:, r, s_len - 1 + k] for k in steps}
        else:
            logits = np.stack([v["logits"][k] for k in steps])
            routes = v["routes"]
        for j, k in enumerate(steps):
            ref = ref_logits[r, j]
            errs.append(float(np.linalg.norm(logits[j] - ref)
                              / np.linalg.norm(ref)))
            token = (int(np.argmax(logits[j])) if control
                     else int(v["tokens"][k]))
            faults += token_fault(token, logits[j], ref)
            want = (ref_routes[:, r, :s_len] if k == 0
                    else ref_routes[:, r, s_len - 1 + k])
            d = _sets_differ(np.asarray(routes[k]), want)
            differ += int(np.sum(d))
            total += d.size
    return {"logit_err": max(errs), "logit_err_p50": float(np.median(errs)),
            "route_disagree": differ / max(total, 1),
            "token_faults": float(faults),
            "rate_flips": rate_flips, "theta_gap": theta_gap}


def limits(cfg: Dict) -> Dict[str, float]:
    return dict(cfg["limits"])
