"""Transformer building blocks: norms, RoPE, chunked (flash-style) attention,
GQA / MLA / local-window attention, dense MLP, expert-parallel MoE.

All functions are pure; parameters are dict pytrees built from ParamSpecs
(see params.py). Activation sharding is annotated via sharding.constrain.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro import sharding
from repro.configs.base import ArchConfig
from repro.models.params import ParamSpec

NEG_INF = -1e30


# ----------------------------------------------------------------------------
# norms & rope
# ----------------------------------------------------------------------------

def rmsnorm_spec(d: int) -> Dict[str, ParamSpec]:
    return {"scale": ParamSpec((d,), ("embed",), init="ones")}


def rmsnorm(params: Dict, x: jax.Array, eps: float) -> jax.Array:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps) * params["scale"].astype(jnp.float32)).astype(dt)


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """Rotate-half RoPE. x: (..., S, H, D); positions: (..., S)."""
    d = x.shape[-1]
    half = d // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs          # (..., S, half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    cos = cos[..., None, :]   # broadcast over heads
    sin = sin[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ----------------------------------------------------------------------------
# chunked online-softmax attention (pure-JAX flash; Pallas kernel in kernels/)
# ----------------------------------------------------------------------------

def _chunk(x: jax.Array, axis: int, size: int) -> jax.Array:
    n = x.shape[axis] // size
    new = x.shape[:axis] + (n, size) + x.shape[axis + 1:]
    return x.reshape(new)


def flash_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, *,
    causal: bool, window: int = 0,
    q_chunk: int = 512, kv_chunk: int = 1024,
    q_offset: int = 0, unroll: bool = False,
) -> jax.Array:
    """Online-softmax attention that never materializes S_q x S_k.

    q: (B, Sq, H, D); k, v: (B, Sk, Hkv, D); H % Hkv == 0.
    window > 0: local (sliding-window) causal attention.
    Returns (B, Sq, H, D).
    """
    b, sq, h, d = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    dv = v.shape[-1]          # may differ from d (MLA: qk dim > v dim)
    g = h // hkv

    def _pick(s, target):
        """largest divisor of s that is <= target (keeps chunk counts low
        for awkward lengths like whisper's 1500 frames)."""
        for c in range(min(target, s), 0, -1):
            if s % c == 0:
                return c
        return s

    q_chunk = _pick(sq, q_chunk)
    kv_chunk = _pick(sk, kv_chunk)
    nq, nk = sq // q_chunk, sk // kv_chunk
    scale = d ** -0.5

    qc = _chunk(q.reshape(b, sq, hkv, g, d), 1, q_chunk)       # (B,Nq,Cq,Hkv,G,D)
    kc = jnp.moveaxis(_chunk(k, 1, kv_chunk), 1, 0)            # (Nk,B,Ck,Hkv,D)
    vc = jnp.moveaxis(_chunk(v, 1, kv_chunk), 1, 0)

    q_pos = (q_offset + jnp.arange(sq)).reshape(nq, q_chunk)   # (Nq,Cq)

    def body(carry, inp):
        acc, m, l = carry
        kj, vj, j = inp
        s = jnp.einsum("bnqhgd,bkhd->bnqhgk", qc, kj,
                       preferred_element_type=jnp.float32) * scale
        k_pos = j * kv_chunk + jnp.arange(kv_chunk)            # (Ck,)
        mask = jnp.ones((nq, q_chunk, kv_chunk), bool)
        if causal:
            mask &= q_pos[:, :, None] >= k_pos[None, None, :]
        if window > 0:
            mask &= (q_pos[:, :, None] - k_pos[None, None, :]) < window
        s = jnp.where(mask[None, :, :, None, None, :], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        m_safe = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
        p = jnp.exp(s - m_safe[..., None])
        p = jnp.where(mask[None, :, :, None, None, :], p, 0.0)
        alpha = jnp.exp(jnp.where(m <= NEG_INF / 2, NEG_INF, m) - m_safe)
        alpha = jnp.where(m <= NEG_INF / 2, 0.0, alpha)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bnqhgk,bkhd->bnqhgd", p.astype(vj.dtype), vj,
            preferred_element_type=jnp.float32)
        return (acc_new, m_new, l_new), None

    acc0 = jnp.zeros((b, nq, q_chunk, hkv, g, dv), jnp.float32)
    m0 = jnp.full((b, nq, q_chunk, hkv, g), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, nq, q_chunk, hkv, g), jnp.float32)
    if unroll:   # dry-run cost-extrapolation: no while loops in the HLO
        carry = (acc0, m0, l0)
        for j in range(nk):
            carry, _ = body(carry, (kc[j], vc[j], jnp.asarray(j)))
        acc, m, l = carry
    else:
        (acc, m, l), _ = jax.lax.scan(
            body, (acc0, m0, l0), (kc, vc, jnp.arange(nk)))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    return out.reshape(b, sq, h, dv).astype(q.dtype)


def decode_attention(
    q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
    cur_len: jax.Array, *, window: int = 0,
) -> jax.Array:
    """Single-token attention against a (possibly ring-buffer) KV cache.

    q: (B, 1, H, D); caches: (B, Smax, Hkv, D); cur_len: () current length.
    """
    b, _, h, d = q.shape
    smax, hkv = k_cache.shape[1], k_cache.shape[2]
    dv = v_cache.shape[-1]
    g = h // hkv
    qr = q.reshape(b, hkv, g, d)
    s = jnp.einsum("bhgd,bshd->bhgs", qr, k_cache,
                   preferred_element_type=jnp.float32) * d ** -0.5
    pos = jnp.arange(smax)
    valid = pos < cur_len
    if window > 0:
        valid &= pos >= (cur_len - window)
    s = jnp.where(valid[None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgs,bshd->bhgd", p.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, 1, h, dv).astype(q.dtype)


# ----------------------------------------------------------------------------
# GQA attention block
# ----------------------------------------------------------------------------

def attn_spec(cfg: ArchConfig, window: bool = False) -> Dict[str, Any]:
    d, h, hkv, dh = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    return {
        "wq": ParamSpec((d, h, dh), ("embed", "heads", "head_dim")),
        "wk": ParamSpec((d, hkv, dh), ("embed", "kv_heads", "head_dim")),
        "wv": ParamSpec((d, hkv, dh), ("embed", "kv_heads", "head_dim")),
        "wo": ParamSpec((h, dh, d), ("heads", "head_dim", "embed")),
    }


def attn_apply(
    params: Dict, x: jax.Array, positions: jax.Array, cfg: ArchConfig,
    mesh: Optional[Mesh], rules, *,
    causal: bool = True, window: int = 0,
    mode: str = "train", cache: Optional[Dict] = None,
    kv_override: Optional[Tuple[jax.Array, jax.Array]] = None,
) -> Tuple[jax.Array, Optional[Dict]]:
    """GQA attention. mode: train | prefill | decode.

    kv_override: (k, v) for cross-attention (already projected + cached).
    """
    def cons(t, axes):
        return sharding.constrain(t, axes, mesh, rules) if mesh else t

    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(x.dtype))
    q = cons(q, ("batch", "seq", "heads", "head_dim"))
    if kv_override is None:
        k = jnp.einsum("bsd,dhk->bshk", x, params["wk"].astype(x.dtype))
        v = jnp.einsum("bsd,dhk->bshk", x, params["wv"].astype(x.dtype))
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    else:
        k, v = kv_override

    new_cache = None
    if mode == "decode":
        assert cache is not None
        if kv_override is None:
            if window > 0:   # ring buffer
                slot = cache["pos"] % cache["k"].shape[1]
            else:
                slot = cache["pos"]
            kc = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, slot, 1)
            vc = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, slot, 1)
            cur = cache["pos"] + 1
            new_cache = {"k": kc, "v": vc, "pos": cur}
            # ring buffer (window > 0): every held position is inside the
            # window by construction, so no extra window mask is needed
            out = decode_attention(q, kc, vc, jnp.minimum(cur, kc.shape[1]))
        else:
            out = decode_attention(q, k, v, jnp.asarray(k.shape[1]))
            new_cache = cache
    else:
        out = flash_attention(q, k, v, causal=causal, window=window,
                              q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk,
                              unroll=cfg.force_unroll)
        if mode == "prefill" and kv_override is None:
            new_cache = {"k": k, "v": v,
                         "pos": jnp.asarray(k.shape[1], jnp.int32)}
    out = cons(out, ("batch", "seq", "heads", "head_dim"))
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(x.dtype))
    return cons(y, ("batch", "seq", "embed")), new_cache


def attn_cache_spec(cfg: ArchConfig, batch: int, max_len: int, window: int = 0
                    ) -> Dict[str, Any]:
    s = min(window, max_len) if window > 0 else max_len
    hkv, dh = cfg.num_kv_heads, cfg.resolved_head_dim
    # "cache_seq" (default: replicated) can be rule-mapped to "model" to
    # sequence-shard long KV caches when kv_heads can't use the model axis
    kv = ParamSpec((batch, s, hkv, dh),
                   ("batch", "cache_seq", "kv_heads", "head_dim"),
                   init="zeros")
    return {"k": kv, "v": kv,
            "pos": ParamSpec((), (), init="zeros", dtype="int32")}


# ----------------------------------------------------------------------------
# MLA (DeepSeek-style multi-head latent attention)
# ----------------------------------------------------------------------------

def mla_spec(cfg: ArchConfig) -> Dict[str, Any]:
    d, h = cfg.d_model, cfg.num_heads
    dh = cfg.resolved_head_dim            # nope dim (and value dim)
    r, dr = cfg.kv_lora_rank, cfg.rope_head_dim
    spec = {
        "wdkv": ParamSpec((d, r), ("embed", "kv_lora")),
        "wkr": ParamSpec((d, dr), ("embed", "head_dim")),
        "kv_norm": rmsnorm_spec(r),
        "wuk": ParamSpec((r, h, dh), ("kv_lora", "heads", "head_dim")),
        "wuv": ParamSpec((r, h, dh), ("kv_lora", "heads", "head_dim")),
        "wo": ParamSpec((h, dh, d), ("heads", "head_dim", "embed")),
    }
    if cfg.q_lora_rank > 0:
        spec["wdq"] = ParamSpec((d, cfg.q_lora_rank), ("embed", "kv_lora"))
        spec["q_norm"] = rmsnorm_spec(cfg.q_lora_rank)
        spec["wuq"] = ParamSpec((cfg.q_lora_rank, h, dh + dr),
                                ("kv_lora", "heads", "head_dim"))
    else:
        spec["wq"] = ParamSpec((d, h, dh + dr), ("embed", "heads", "head_dim"))
    return spec


def mla_apply(
    params: Dict, x: jax.Array, positions: jax.Array, cfg: ArchConfig,
    mesh, rules, *, mode: str = "train", cache: Optional[Dict] = None,
) -> Tuple[jax.Array, Optional[Dict]]:
    def cons(t, axes):
        return sharding.constrain(t, axes, mesh, rules) if mesh else t

    dh, dr, r = cfg.resolved_head_dim, cfg.rope_head_dim, cfg.kv_lora_rank
    # --- queries
    if cfg.q_lora_rank > 0:
        cq = rmsnorm(params["q_norm"], x @ params["wdq"].astype(x.dtype), cfg.norm_eps)
        q = jnp.einsum("bsr,rhk->bshk", cq, params["wuq"].astype(x.dtype))
    else:
        q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(x.dtype))
    q_nope, q_rope = q[..., :dh], q[..., dh:]
    q_rope = rope(q_rope, positions, cfg.rope_theta)
    q = cons(jnp.concatenate([q_nope, q_rope], -1),
             ("batch", "seq", "heads", "head_dim"))

    # --- latent kv
    c_kv = rmsnorm(params["kv_norm"], x @ params["wdkv"].astype(x.dtype), cfg.norm_eps)
    k_rope = rope((x @ params["wkr"].astype(x.dtype))[:, :, None, :],
                  positions, cfg.rope_theta)[:, :, 0, :]      # (B,S,dr) single head

    new_cache = None
    if mode == "decode":
        assert cache is not None
        slot = cache["pos"]
        cc = jax.lax.dynamic_update_slice_in_dim(cache["c_kv"], c_kv, slot, 1)
        kr = jax.lax.dynamic_update_slice_in_dim(cache["k_rope"], k_rope, slot, 1)
        cur = cache["pos"] + 1
        new_cache = {"c_kv": cc, "k_rope": kr, "pos": cur}
        # weight-absorbed decode: score in the latent space (cache stays rank-r)
        q_lat = jnp.einsum("bshk,rhk->bshr", q_nope, params["wuk"].astype(x.dtype))
        s = (jnp.einsum("bshr,btr->bhst", q_lat, cc,
                        preferred_element_type=jnp.float32)
             + jnp.einsum("bshk,btk->bhst", q_rope, kr,
                          preferred_element_type=jnp.float32)) * (dh + dr) ** -0.5
        valid = jnp.arange(cc.shape[1]) < cur
        s = jnp.where(valid[None, None, None, :], s, NEG_INF)
        p = jax.nn.softmax(s, axis=-1)
        o_lat = jnp.einsum("bhst,btr->bshr", p.astype(x.dtype), cc)
        out = jnp.einsum("bshr,rhk->bshk", o_lat, params["wuv"].astype(x.dtype))
    else:
        # train/prefill: expand per-head K/V from the latent (MQA-style rope)
        k_nope = jnp.einsum("bsr,rhk->bshk", c_kv, params["wuk"].astype(x.dtype))
        v = jnp.einsum("bsr,rhk->bshk", c_kv, params["wuv"].astype(x.dtype))
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                      k_nope.shape[:-1] + (dr,))], -1)
        k = cons(k, ("batch", "seq", "heads", "head_dim"))
        out = flash_attention(q, k, v, causal=True,
                              q_chunk=cfg.q_chunk, kv_chunk=cfg.kv_chunk,
                              unroll=cfg.force_unroll)
        if mode == "prefill":
            new_cache = {"c_kv": c_kv, "k_rope": k_rope,
                         "pos": jnp.asarray(x.shape[1], jnp.int32)}
    out = cons(out, ("batch", "seq", "heads", "head_dim"))
    y = jnp.einsum("bshk,hkd->bsd", out, params["wo"].astype(x.dtype))
    return cons(y, ("batch", "seq", "embed")), new_cache


def mla_cache_spec(cfg: ArchConfig, batch: int, max_len: int) -> Dict[str, Any]:
    return {
        "c_kv": ParamSpec((batch, max_len, cfg.kv_lora_rank),
                          ("batch", "cache_seq", "kv_lora"), init="zeros"),
        "k_rope": ParamSpec((batch, max_len, cfg.rope_head_dim),
                            ("batch", "cache_seq", None), init="zeros"),
        "pos": ParamSpec((), (), init="zeros", dtype="int32"),
    }


# ----------------------------------------------------------------------------
# MLPs
# ----------------------------------------------------------------------------

def mlp_spec(cfg: ArchConfig, d_ff: int = 0) -> Dict[str, Any]:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    spec = {
        "w1": ParamSpec((d, f), ("embed", "ffn")),
        "w2": ParamSpec((f, d), ("ffn", "embed")),
    }
    if cfg.mlp_gated:
        spec["w3"] = ParamSpec((d, f), ("embed", "ffn"))
    return spec


def mlp_apply(params: Dict, x: jax.Array, cfg: ArchConfig, mesh, rules) -> jax.Array:
    def cons(t, axes):
        return sharding.constrain(t, axes, mesh, rules) if mesh else t
    h = x @ params["w1"].astype(x.dtype)
    h = cons(h, ("batch", "seq", "ffn"))
    if cfg.mlp_gated:
        h = jax.nn.silu(h) * (x @ params["w3"].astype(x.dtype))
    else:
        h = jax.nn.gelu(h)
    y = h @ params["w2"].astype(x.dtype)
    return cons(y, ("batch", "seq", "embed"))


# ----------------------------------------------------------------------------
# Mixture of Experts — expert-parallel over the "model" mesh axis
# ----------------------------------------------------------------------------

def moe_spec(cfg: ArchConfig) -> Dict[str, Any]:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    spec = {
        "router": ParamSpec((d, e), ("embed", "expert")),
        "w1": ParamSpec((e, d, f), ("expert", "embed", "expert_ffn")),
        "w2": ParamSpec((e, f, d), ("expert", "expert_ffn", "embed")),
        "w3": ParamSpec((e, d, f), ("expert", "embed", "expert_ffn")),
    }
    if cfg.moe_routing == "sigmoid":
        # the scores decide which experts run: router and selection bias
        # are kept, and applied, in f32
        spec["router"] = ParamSpec((d, e), ("embed", "expert"),
                                   dtype="float32")
        spec["bias"] = ParamSpec((e,), ("expert",), init="zeros",
                                 dtype="float32")
    if cfg.num_shared_experts > 0:
        fs = cfg.d_ff * cfg.num_shared_experts
        spec["shared"] = mlp_spec(cfg, d_ff=fs)
    return spec


def _expert_ffn(w1, w2, w3, x):
    """x: (E, C, D); weights (E, D, F)/(E, F, D)."""
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", x, w1)) * jnp.einsum(
        "ecd,edf->ecf", x, w3)
    return jnp.einsum("ecf,efd->ecd", h, w2)


def route_sigmoid(logits: jax.Array, bias: jax.Array, top_k: int,
                  scale: float) -> Tuple[jax.Array, jax.Array]:
    """(weights (T, K) f32, experts (T, K)) of sigmoid routing with a
    selection bias (DeepSeek-V3 ``noaux_tc``, one group): the top-k experts
    by score + bias, weighted by their unbiased scores normalised to sum 1,
    times ``scale``. logits: (T, E) f32."""
    scores = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(scores + bias, top_k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    return w / jnp.sum(w, axis=-1, keepdims=True) * scale, idx


# up to this many tokens every held expert runs on every token: that costs
# no more time than reading the experts' weights, which any dispatch must
# (a TPU v5e does 240 bf16 FLOPs per byte of HBM), with no sort or gather
DENSE_TOKENS = 256


def _moe_dropless(x_flat, weights, idx, w1, w2, w3, *, e_start: int,
                  e_local: int):
    """Every token-expert assignment computed (no capacity), by the experts
    [e_start, e_start + e_local) held here, combined with ``weights`` in
    f32; assignments to experts held elsewhere add nothing. Up to
    ``DENSE_TOKENS`` tokens each held expert runs on every token, weighted
    by zero where not chosen; past that the assignments are sorted by
    expert and each projection is one grouped matmul.

    x_flat: (T, D); weights, idx: (T, K). Returns the partial output (T, D).
    """
    t, d = x_flat.shape
    k = idx.shape[1]
    local = idx.reshape(-1) - e_start
    held = (local >= 0) & (local < e_local)
    if t <= DENSE_TOKENS:
        comb = jnp.zeros((t, e_local), jnp.float32).at[
            jnp.repeat(jnp.arange(t), k), jnp.clip(local, 0, e_local - 1)
        ].add(jnp.where(held, weights.reshape(-1), 0.0))
        h = (jax.nn.silu(jnp.einsum("td,edf->etf", x_flat, w1))
             * jnp.einsum("td,edf->etf", x_flat, w3))
        y = jnp.einsum("etf,efd->etd", h, w2).astype(jnp.float32)
        return jnp.sum(y * comb.T[:, :, None], axis=0).astype(x_flat.dtype)
    local = jnp.where(held, local, e_local)
    order = jnp.argsort(local)
    sizes = jnp.sum(jax.nn.one_hot(local, e_local, dtype=jnp.int32), axis=0)
    xs = jnp.take(x_flat, order // k, axis=0)
    h = (jax.nn.silu(jax.lax.ragged_dot(xs, w1, sizes))
         * jax.lax.ragged_dot(xs, w3, sizes))
    ys = jax.lax.ragged_dot(h, w2, sizes)
    # rows past the held groups are not written by the grouped matmul
    ys = jnp.where(held[order][:, None], ys, 0).astype(jnp.float32)
    y = jnp.take(ys, jnp.argsort(order), axis=0).reshape(t, k, d)
    return jnp.sum(y * weights[..., None], axis=1).astype(x_flat.dtype)


def _moe_local(x_flat, router_logits, w1, w2, w3, *, e_start: int, e_local: int,
               top_k: int, capacity: int):
    """Token dispatch -> local-expert FFN -> weighted combine (one shard).

    x_flat: (T, D); router_logits: (T, E_total). Returns partial output (T, D)
    containing only the contribution of experts [e_start, e_start + e_local).
    """
    t, d = x_flat.shape
    gates, idx = jax.lax.top_k(router_logits, top_k)            # (T, K)
    gates = jax.nn.softmax(gates.astype(jnp.float32), axis=-1).astype(x_flat.dtype)

    counts = jnp.zeros((e_local,), jnp.int32)
    trash = e_local * capacity
    buf = jnp.zeros((e_local * capacity + 1, d), x_flat.dtype)
    slots, keeps, locals_ = [], [], []
    for kk in range(top_k):
        local = idx[:, kk] - e_start
        in_range = (local >= 0) & (local < e_local)
        lc = jnp.clip(local, 0, e_local - 1)
        onehot = jax.nn.one_hot(lc, e_local, dtype=jnp.int32) * in_range[:, None]
        pos = jnp.cumsum(onehot, axis=0) - 1 + counts[None, :]   # (T, E_local)
        counts = counts + jnp.sum(onehot, axis=0)
        slot = jnp.sum(onehot * pos, axis=1)                     # (T,)
        keep = in_range & (slot < capacity)
        flat = jnp.where(keep, lc * capacity + slot, trash)
        buf = buf.at[flat].add(x_flat)
        slots.append(flat)
        keeps.append(keep)
    expert_in = buf[:-1].reshape(e_local, capacity, d)
    expert_out = _expert_ffn(w1, w2, w3, expert_in).reshape(e_local * capacity, d)
    expert_out = jnp.concatenate([expert_out, jnp.zeros((1, d), x_flat.dtype)], 0)
    out = jnp.zeros_like(x_flat)
    for kk in range(top_k):
        contrib = expert_out[slots[kk]] * keeps[kk][:, None].astype(x_flat.dtype)
        out = out + contrib * gates[:, kk:kk + 1]
    return out


def _fsdp_axes(mesh: Mesh, rules, d_ff: int) -> Tuple[str, ...]:
    """Mesh axes over which expert weights are ZeRO-3 sharded at rest."""
    ax = rules.lookup("expert_ffn") if rules else None
    if ax is None:
        return ()
    if isinstance(ax, str):
        ax = (ax,)
    ax = tuple(a for a in ax if a in mesh.shape)
    size = 1
    for a in ax:
        size *= mesh.shape[a]
    while ax and d_ff % size != 0:
        ax = ax[:-1]
        size = 1
        for a in ax:
            size *= mesh.shape[a]
    return ax


def _routed(cfg: ArchConfig, x_flat, router, bias, w1, w2, w3, *,
            e_start, e_local: int):
    """Route ``x_flat`` (T, D) over all experts and compute the part of the
    result that experts [e_start, e_start + e_local) give. Returns
    (partial output (T, D), chosen experts (T, K))."""
    e, k = cfg.num_experts, cfg.top_k
    dt = x_flat.dtype
    with jax.named_scope("router"):
        if cfg.moe_routing == "sigmoid":
            logits = jnp.dot(x_flat.astype(jnp.float32), router,
                             precision=jax.lax.Precision.HIGHEST)
            weights, idx = route_sigmoid(logits, bias, k, cfg.routed_scaling)
        else:
            logits = x_flat @ router.astype(dt)
    with jax.named_scope("experts"):
        if cfg.moe_routing == "sigmoid":
            out = _moe_dropless(x_flat, weights, idx, w1.astype(dt),
                                w2.astype(dt), w3.astype(dt),
                                e_start=e_start, e_local=e_local)
        else:
            cap = int(math.ceil(x_flat.shape[0] * k / e * cfg.capacity_factor))
            out = _moe_local(x_flat, logits, w1.astype(dt), w2.astype(dt),
                             w3.astype(dt), e_start=e_start, e_local=e_local,
                             top_k=k, capacity=cap)
            idx = jax.lax.top_k(logits, k)[1]
    return out, idx


def moe_apply(params: Dict, x: jax.Array, cfg: ArchConfig, mesh: Optional[Mesh],
              rules, return_routes: bool = False):
    """Expert-parallel MoE.

    Tokens are replicated over the "model" axis (they are already sharded over
    batch axes); each model-rank runs its E/TP local experts on the full local
    token set and a single psum combines — one collective per MoE layer, the
    same count as the Megatron dense-MLP pattern.

    Expert weights can additionally be ZeRO-3 sharded over the batch axes at
    rest (rules["expert_ffn"] -> ("pod","data")) and all-gathered per layer —
    required to fit 236B/1T-param MoEs in 16 GB/chip; the gather is the
    transpose-friendly FSDP pattern (its cotangent is the grad reduce-scatter).

    ``cfg.moe_routing`` picks the routing: "softmax" (capacity drops) or
    "sigmoid" (selection bias, dropless). With ``return_routes`` the chosen
    experts (B, S, K) come back beside the output on the single-shard path
    (None on the expert-parallel one).
    """
    b, s, d = x.shape
    e = cfg.num_experts
    bias = params.get("bias")
    idx = None

    if mesh is None or "model" not in mesh.shape or mesh.shape["model"] == 1 \
            or e % max(mesh.shape.get("model", 1), 1) != 0:
        # reference path (single shard)
        out, idx = _routed(cfg, x.reshape(-1, d), params["router"], bias,
                           params["w1"], params["w2"], params["w3"],
                           e_start=0, e_local=e)
        y = out.reshape(b, s, d)
        idx = idx.reshape(b, s, -1)
    else:
        n_model = mesh.shape["model"]
        e_local = e // n_model
        batch_ax = sharding.batch_axes(mesh)
        fsdp = _fsdp_axes(mesh, rules, cfg.d_ff)

        def shard_fn(xs, router, bias_, w1, w2, w3):
            ridx = jax.lax.axis_index("model")
            if fsdp:
                n_fsdp = 1
                for a in fsdp:
                    n_fsdp *= mesh.shape[a]
                tok_vol = xs.size * n_fsdp
                w_vol = (w1.size + w2.size + w3.size) * n_fsdp
                if tok_vol * 4 < w_vol:
                    # token-gather path (decode / small batches): move the
                    # tokens to the F-sharded expert weights instead of
                    # gathering 2 TB of experts to serve 128 tokens.
                    # psum spans model (EP combine) + fsdp (F partial sums).
                    x_all = jax.lax.all_gather(xs, fsdp, axis=0, tiled=True)
                    t_all = x_all.shape[0] * x_all.shape[1]
                    out, _ = _routed(cfg, x_all.reshape(t_all, d), router,
                                     bias_, w1, w2, w3,
                                     e_start=ridx * e_local, e_local=e_local)
                    out = jax.lax.psum(out, ("model",) + fsdp)
                    out = out.reshape(x_all.shape)
                    fidx = jax.lax.axis_index(fsdp)
                    blk = xs.shape[0]
                    return jax.lax.dynamic_slice_in_dim(out, fidx * blk,
                                                        blk, axis=0)
                # weight-gather path (training): ZeRO-3 materialization
                w1 = jax.lax.all_gather(w1, fsdp, axis=2, tiled=True)
                w3 = jax.lax.all_gather(w3, fsdp, axis=2, tiled=True)
                w2 = jax.lax.all_gather(w2, fsdp, axis=1, tiled=True)
            t_loc = xs.shape[0] * xs.shape[1]
            out, _ = _routed(cfg, xs.reshape(t_loc, d), router, bias_, w1, w2,
                             w3, e_start=ridx * e_local, e_local=e_local)
            out = jax.lax.psum(out, "model")
            return out.reshape(xs.shape)

        wspec1 = P("model", None, fsdp if fsdp else None)
        wspec2 = P("model", fsdp if fsdp else None, None)
        y = jax.shard_map(
            shard_fn,
            mesh=mesh,
            in_specs=(P(batch_ax, None, None), P(None, None), P(None),
                      wspec1, wspec2, wspec1),
            out_specs=P(batch_ax, None, None),
            check_vma=False,
        )(x, params["router"], bias if bias is not None else jnp.zeros((e,)),
          params["w1"], params["w2"], params["w3"])

    if "shared" in params:
        with jax.named_scope("shared"):
            y = y + mlp_apply(params["shared"], x, cfg, mesh, rules)
    return (y, idx) if return_routes else y
