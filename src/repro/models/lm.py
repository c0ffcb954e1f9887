"""Unified causal LM / encoder-decoder model.

One implementation covers all 10 assigned architectures: the per-layer mixer
(attn / local_attn / mla / rglru / mlstm / slstm) and MLP kind (dense / moe /
none) come from ``ArchConfig.layer_kinds()``. Homogeneous runs of layers are
``lax.scan``-ned over stacked parameters so the HLO stays compact at any depth
(61-layer / 1T-param Kimi-K2 compiles as one layer body + scan).

Modes: "train" (logits), "prefill" (last position's logits + cache), "decode"
(one token).

Device scopes (``jax.named_scope``, trace-time metadata that adds no
operation): each decoder layer's ops sit under ``lm/<mixer>`` (``lm/mla``,
``lm/attn``, ...), ``lm/moe/{router,experts,shared}`` or ``lm/dense_mlp``,
the token embeddings under ``lm_embed``, the final norm and head under
``lm_head``; in decode mode all of them under
``decode/`` as well, so a trace tells a decode step's device time from a
prefill's.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import sharding
from repro.configs.base import ArchConfig
from repro.models import blocks, recurrent
from repro.models.params import (ParamSpec, abstract_tree, axes_tree,
                                 init_tree, stack_specs)


# ----------------------------------------------------------------------------
# segmentation: group layers into unrolled prefix + scanned periodic body
# ----------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Segment:
    name: str
    kinds: Tuple[Tuple[str, str], ...]   # (mixer, mlp) per layer in the unit
    repeats: int                          # >1 => lax.scan over stacked params
    layer_ids: Tuple[int, ...]            # absolute layer indices covered


def segment_plan(cfg: ArchConfig) -> Tuple[Segment, ...]:
    kinds = cfg.layer_kinds()
    segs: List[Segment] = []
    i = cfg.first_dense_layers
    for j in range(cfg.first_dense_layers):
        segs.append(Segment(f"prefix{j}", (kinds[j],), 1, (j,)))
    period = len(cfg.block_pattern)
    rest = cfg.num_layers - i
    reps = rest // period
    if reps > 0:
        unit = kinds[i:i + period]
        ids = tuple(range(i, i + reps * period))
        segs.append(Segment("body", unit, reps, ids))
        i += reps * period
    for j in range(i, cfg.num_layers):
        segs.append(Segment(f"tail{j}", (kinds[j],), 1, (j,)))
    return tuple(segs)


# ----------------------------------------------------------------------------
# specs
# ----------------------------------------------------------------------------

def _mixer_spec(cfg: ArchConfig, mixer: str) -> Dict[str, Any]:
    if mixer in ("attn", "local_attn", "enc_attn"):
        return blocks.attn_spec(cfg)
    if mixer == "mla":
        return blocks.mla_spec(cfg)
    if mixer == "rglru":
        return recurrent.rglru_spec(cfg)
    if mixer == "mlstm":
        return recurrent.mlstm_spec(cfg)
    if mixer == "slstm":
        return recurrent.slstm_spec(cfg)
    raise ValueError(mixer)


def _layer_spec(cfg: ArchConfig, mixer: str, mlp: str,
                cross: bool = False) -> Dict[str, Any]:
    d = cfg.d_model
    spec: Dict[str, Any] = {
        "ln1": blocks.rmsnorm_spec(d),
        "mixer": _mixer_spec(cfg, mixer),
    }
    if cross:
        spec["ln_x"] = blocks.rmsnorm_spec(d)
        spec["cross"] = blocks.attn_spec(cfg)
    if mlp == "dense":
        spec["ln2"] = blocks.rmsnorm_spec(d)
        ff = cfg.dense_d_ff or cfg.d_ff
        spec["mlp"] = blocks.mlp_spec(cfg, d_ff=ff if mlp == "dense" and
                                      cfg.num_experts > 0 else cfg.d_ff)
    elif mlp == "moe":
        spec["ln2"] = blocks.rmsnorm_spec(d)
        spec["mlp"] = blocks.moe_spec(cfg)
    return spec


def _segment_spec(cfg: ArchConfig, seg: Segment, cross: bool) -> Dict[str, Any]:
    unit = {f"l{j}": _layer_spec(cfg, mx, mlp, cross)
            for j, (mx, mlp) in enumerate(seg.kinds)}
    if seg.repeats > 1:
        unit = stack_specs(unit, seg.repeats)
    return unit


def model_spec(cfg: ArchConfig) -> Dict[str, Any]:
    d, v = cfg.d_model, cfg.vocab_size
    spec: Dict[str, Any] = {
        "embed": {"w": ParamSpec((v, d), ("vocab", "embed"), scale=1.0)},
        "final_norm": blocks.rmsnorm_spec(d),
    }
    if not cfg.tie_embeddings:
        spec["lm_head"] = {"w": ParamSpec((d, v), ("embed", "vocab"))}
    spec["decoder"] = {seg.name: _segment_spec(cfg, seg, cfg.is_encdec)
                       for seg in segment_plan(cfg)}
    if cfg.is_encdec:
        enc_unit = {f"l{j}": _layer_spec(cfg, "enc_attn", "dense")
                    for j in range(1)}
        spec["encoder"] = {
            "body": stack_specs(enc_unit, cfg.encoder_layers),
            "norm": blocks.rmsnorm_spec(d),
        }
    return spec


def init_params(key: jax.Array, cfg: ArchConfig):
    return init_tree(key, model_spec(cfg), cfg.pdtype)


def abstract_params(cfg: ArchConfig):
    return abstract_tree(model_spec(cfg), cfg.pdtype)


def param_axes(cfg: ArchConfig):
    return axes_tree(model_spec(cfg))


# ----------------------------------------------------------------------------
# cache specs (decode)
# ----------------------------------------------------------------------------

def _mixer_cache_spec(cfg: ArchConfig, mixer: str, batch: int, max_len: int):
    if mixer == "attn":
        return blocks.attn_cache_spec(cfg, batch, max_len)
    if mixer == "local_attn":
        return blocks.attn_cache_spec(cfg, batch, max_len, window=cfg.window)
    if mixer == "mla":
        return blocks.mla_cache_spec(cfg, batch, max_len)
    if mixer == "rglru":
        return recurrent.rglru_cache_spec(cfg, batch)
    if mixer == "mlstm":
        return recurrent.mlstm_cache_spec(cfg, batch)
    if mixer == "slstm":
        return recurrent.slstm_cache_spec(cfg, batch)
    raise ValueError(mixer)


def cache_spec(cfg: ArchConfig, batch: int, max_len: int) -> Dict[str, Any]:
    spec: Dict[str, Any] = {"decoder": {}}
    for seg in segment_plan(cfg):
        unit = {}
        for j, (mx, _) in enumerate(seg.kinds):
            c = {"mixer": _mixer_cache_spec(cfg, mx, batch, max_len)}
            if cfg.is_encdec:
                hkv, dh = cfg.num_kv_heads, cfg.resolved_head_dim
                ekv = ParamSpec((batch, cfg.encoder_seq, hkv, dh),
                                ("batch", None, "kv_heads", "head_dim"),
                                init="zeros")
                c["enc_k"], c["enc_v"] = ekv, ekv
            unit[f"l{j}"] = c
        if seg.repeats > 1:
            unit = stack_specs(unit, seg.repeats)
        spec["decoder"][seg.name] = unit
    spec["pos"] = ParamSpec((), (), init="zeros", dtype="int32")
    return spec


def init_cache(cfg: ArchConfig, batch: int, max_len: int):
    return init_tree(jax.random.PRNGKey(0), cache_spec(cfg, batch, max_len),
                     cfg.dtype)


def abstract_cache(cfg: ArchConfig, batch: int, max_len: int):
    return abstract_tree(cache_spec(cfg, batch, max_len), cfg.dtype)


def cache_axes(cfg: ArchConfig, batch: int, max_len: int):
    return axes_tree(cache_spec(cfg, batch, max_len))


# ----------------------------------------------------------------------------
# forward
# ----------------------------------------------------------------------------

# key of a MoE layer's chosen experts in its per-step cache output, taken out
# by ``forward`` before the cache is returned
ROUTE = "route"


def _phase(mode: str):
    """The decode-phase scope around a decode step's layers and head."""
    return (jax.named_scope("decode") if mode == "decode"
            else contextlib.nullcontext())


def _mixer(lp: Dict, h: jax.Array, positions: jax.Array, cfg: ArchConfig,
           mesh, rules, mixer: str, *, mode: str, cache: Optional[Dict]
           ) -> Tuple[jax.Array, Optional[Dict]]:
    if mixer in ("attn", "enc_attn", "local_attn"):
        return blocks.attn_apply(
            lp, h, positions, cfg, mesh, rules,
            causal=(mixer != "enc_attn"),
            window=cfg.window if mixer == "local_attn" else 0,
            mode=mode, cache=cache)
    if mixer == "mla":
        return blocks.mla_apply(lp, h, positions, cfg, mesh, rules,
                                mode=mode, cache=cache)
    if mixer == "rglru":
        return recurrent.rglru_apply(lp, h, cfg, mesh, rules, mode=mode,
                                     cache=cache)
    if mixer == "mlstm":
        return recurrent.mlstm_apply(lp, h, cfg, mesh, rules, mode=mode,
                                     cache=cache)
    if mixer == "slstm":
        return recurrent.slstm_apply(lp, h, cfg, mesh, rules, mode=mode,
                                     cache=cache)
    raise ValueError(mixer)


def _apply_layer(lp: Dict, x: jax.Array, positions: jax.Array,
                 cfg: ArchConfig, mesh, rules, mixer: str, mlp: str, *,
                 mode: str, cache: Optional[Dict], enc_out: Optional[jax.Array]
                 ) -> Tuple[jax.Array, Optional[Dict]]:
    new_cache: Dict[str, Any] = {}
    with jax.named_scope(mixer):
        h = blocks.rmsnorm(lp["ln1"], x, cfg.norm_eps)
        out, nm = _mixer(lp["mixer"], h, positions, cfg, mesh, rules, mixer,
                         mode=mode,
                         cache=cache.get("mixer") if cache else None)
    if nm is not None:
        new_cache["mixer"] = nm
    x = x + out

    if "cross" in lp and (enc_out is not None or
                          (cache and "enc_k" in cache)):
        hx = blocks.rmsnorm(lp["ln_x"], x, cfg.norm_eps)
        if enc_out is not None:   # train / prefill: project enc K/V now
            ek = jnp.einsum("bsd,dhk->bshk", enc_out,
                            lp["cross"]["wk"].astype(x.dtype))
            ev = jnp.einsum("bsd,dhk->bshk", enc_out,
                            lp["cross"]["wv"].astype(x.dtype))
        else:
            ek, ev = cache["enc_k"], cache["enc_v"]
        cout, _ = blocks.attn_apply(
            lp["cross"], hx, positions, cfg, mesh, rules, causal=False,
            mode="decode" if mode == "decode" else "train",
            cache={} if mode == "decode" else None, kv_override=(ek, ev))
        x = x + cout
        if mode in ("prefill", "decode"):
            new_cache["enc_k"], new_cache["enc_v"] = ek if enc_out is not None \
                else cache["enc_k"], ev if enc_out is not None else cache["enc_v"]

    if mlp != "none" and "mlp" in lp:
        if mlp == "moe":
            with jax.named_scope("moe"):
                h2 = blocks.rmsnorm(lp["ln2"], x, cfg.norm_eps)
                y, route = blocks.moe_apply(lp["mlp"], h2, cfg, mesh, rules,
                                            return_routes=True)
            if route is not None and mode in ("prefill", "decode"):
                new_cache[ROUTE] = route
            x = x + y
        else:
            with jax.named_scope("dense_mlp"):
                h2 = blocks.rmsnorm(lp["ln2"], x, cfg.norm_eps)
                x = x + blocks.mlp_apply(lp["mlp"], h2, cfg, mesh, rules)
    return x, (new_cache if new_cache else None)


def _apply_unit(up: Dict, x, positions, cfg, mesh, rules, seg: Segment, *,
                mode, cache, enc_out):
    """Apply one period (len(seg.kinds) layers)."""
    new_cache = {}
    for j, (mx, mlp) in enumerate(seg.kinds):
        lc = cache.get(f"l{j}") if cache else None
        with _phase(mode), jax.named_scope("lm"):
            x, nc = _apply_layer(up[f"l{j}"], x, positions, cfg, mesh, rules,
                                 mx, mlp, mode=mode, cache=lc,
                                 enc_out=enc_out)
        if nc is not None:
            new_cache[f"l{j}"] = nc
    return x, (new_cache if new_cache else None)


def _run_decoder(params, x, positions, cfg: ArchConfig, mesh, rules, *,
                 mode, cache, enc_out):
    new_cache: Dict[str, Any] = {}
    for seg in segment_plan(cfg):
        sp = params["decoder"][seg.name]
        sc = cache["decoder"].get(seg.name) if cache else None
        if seg.repeats == 1:
            x, nc = _apply_unit(sp, x, positions, cfg, mesh, rules, seg,
                                mode=mode, cache=sc, enc_out=enc_out)
        elif cfg.force_unroll:
            def one_unit(up_, x_, uc_):
                return _apply_unit(up_, x_, positions, cfg, mesh, rules, seg,
                                   mode=mode, cache=uc_, enc_out=enc_out)

            if cfg.remat != "none" and mode == "train":
                one_unit = jax.checkpoint(one_unit)
            ncs_list = []
            for j in range(seg.repeats):
                up = jax.tree.map(lambda a: a[j], sp)
                uc = jax.tree.map(lambda a: a[j], sc) if sc is not None \
                    else None
                x, nc_j = one_unit(up, x, uc)
                ncs_list.append(nc_j)
            nc = None
            if mode != "train" and ncs_list[0] is not None:
                nc = jax.tree.map(lambda *xs: jnp.stack(xs), *ncs_list)
        else:
            def body(carry, xs):
                xc = carry
                up, uc = xs
                y, nc_ = _apply_unit(up, xc, positions, cfg, mesh, rules, seg,
                                     mode=mode, cache=uc, enc_out=enc_out)
                if nc_ is None:
                    nc_ = 0  # scan needs a leaf; pruned below
                return y, nc_

            if cfg.remat != "none" and mode == "train":
                body = jax.checkpoint(body)
            x, ncs = jax.lax.scan(body, x, (sp, sc))
            nc = None if (mode == "train") else ncs
        if nc is not None:
            new_cache[seg.name] = nc
    return x, new_cache


def _run_encoder(params, emb: jax.Array, cfg: ArchConfig, mesh, rules):
    positions = jnp.arange(emb.shape[1])[None, :]
    seg = Segment("enc", (("enc_attn", "dense"),), cfg.encoder_layers,
                  tuple(range(cfg.encoder_layers)))

    def body(carry, up):
        y, _ = _apply_unit(up, carry, positions, cfg, mesh, rules, seg,
                           mode="train", cache=None, enc_out=None)
        return y, None

    if cfg.force_unroll:
        x = emb
        for j in range(cfg.encoder_layers):
            up = jax.tree.map(lambda a: a[j], params["encoder"]["body"])
            x, _ = body(x, up)
    else:
        x, _ = jax.lax.scan(body, emb, params["encoder"]["body"])
    return blocks.rmsnorm(params["encoder"]["norm"], x, cfg.norm_eps)


def _pop_routes(cfg: ArchConfig, decoder_cache: Dict) -> Optional[jax.Array]:
    """Take each MoE layer's chosen experts out of a step's decoder cache:
    (MoE layers, B, S, K), by segment and, within a scanned unit, by its
    layer then its repeat (layer order where the unit is one layer); None
    where no layer routed."""
    routes = []
    for seg in segment_plan(cfg):
        unit = decoder_cache.get(seg.name) or {}
        for j in range(len(seg.kinds)):
            layer = unit.get(f"l{j}")
            if isinstance(layer, dict) and ROUTE in layer:
                r = layer.pop(ROUTE)
                routes.extend(list(r) if seg.repeats > 1 else [r])
    return jnp.stack(routes) if routes else None


def forward_with_routes(
    params: Dict, tokens: jax.Array, cfg: ArchConfig,
    mesh=None, rules=None, *,
    mode: str = "train",
    cache: Optional[Dict] = None,
    encoder_embeddings: Optional[jax.Array] = None,
    positions: Optional[jax.Array] = None,
    image_embeddings: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Optional[Dict], Optional[jax.Array]]:
    """``forward``, with each MoE layer's chosen experts (MoE layers, B, S,
    K) in prefill and decode on the single-shard path (else None)."""
    rules = rules or sharding.ShardingRules.make(dict(cfg.rule_overrides))
    emb = params["embed"]["w"]
    with _phase(mode), jax.named_scope("lm_embed"):
        x = jnp.take(emb, tokens, axis=0, mode="clip").astype(cfg.dtype)
        if cfg.scale_embeddings:
            x = x * (cfg.d_model ** 0.5)
        if image_embeddings is not None:
            n = image_embeddings.shape[1]
            x = jnp.concatenate([image_embeddings.astype(x.dtype), x[:, n:]],
                                1)
    if mesh is not None:
        x = sharding.constrain(x, ("batch", "seq", "embed"), mesh, rules)

    if positions is None:
        if mode == "decode":
            assert cache is not None
            positions = jnp.broadcast_to(cache["pos"], (tokens.shape[0], 1))
        else:
            positions = jnp.arange(tokens.shape[1])[None, :]

    enc_out = None
    if cfg.is_encdec and encoder_embeddings is not None:
        enc_out = _run_encoder(params, encoder_embeddings.astype(cfg.dtype),
                               cfg, mesh, rules)

    x, new_cache = _run_decoder(params, x, positions, cfg, mesh, rules,
                                mode=mode, cache=cache, enc_out=enc_out)
    routes = _pop_routes(cfg, new_cache)
    if mode == "prefill":
        x = x[:, -1:]            # the head runs at the last position only
    with _phase(mode), jax.named_scope("lm_head"):
        x = blocks.rmsnorm(params["final_norm"], x, cfg.norm_eps)
        if cfg.tie_embeddings:
            logits = jnp.einsum("bsd,vd->bsv", x, emb.astype(x.dtype))
        else:
            logits = x @ params["lm_head"]["w"].astype(x.dtype)
    if mesh is not None:
        logits = sharding.constrain(logits, ("batch", "seq", "vocab"),
                                    mesh, rules)
    if mode in ("prefill", "decode"):
        prev = cache["pos"] if (cache is not None and "pos" in cache) \
            else jnp.asarray(0, jnp.int32)
        out_cache = {"decoder": new_cache,
                     "pos": prev + tokens.shape[1]}
        return logits, out_cache, routes
    return logits, None, routes


def forward(
    params: Dict, tokens: jax.Array, cfg: ArchConfig,
    mesh=None, rules=None, *,
    mode: str = "train",
    cache: Optional[Dict] = None,
    encoder_embeddings: Optional[jax.Array] = None,
    positions: Optional[jax.Array] = None,
    image_embeddings: Optional[jax.Array] = None,
) -> Tuple[jax.Array, Optional[Dict]]:
    """tokens: (B, S) int32. Returns (logits, new_cache | None).

    ``image_embeddings`` (B, N, d_model) take the place of the first N
    positions' token embeddings. Prefill returns the last position's
    logits only, (B, 1, V)."""
    logits, out_cache, _ = forward_with_routes(
        params, tokens, cfg, mesh, rules, mode=mode, cache=cache,
        encoder_embeddings=encoder_embeddings, positions=positions,
        image_embeddings=image_embeddings)
    return logits, out_cache


def lm_loss(params, batch: Dict, cfg: ArchConfig, mesh=None, rules=None
            ) -> Tuple[jax.Array, Dict]:
    """Next-token cross-entropy. batch: {tokens, labels[, encoder_embeddings]}."""
    logits, _ = forward(params, batch["tokens"], cfg, mesh, rules,
                        mode="train",
                        encoder_embeddings=batch.get("encoder_embeddings"))
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, batch["labels"][..., None], axis=-1)
    loss = jnp.mean(nll)
    return loss, {"loss": loss, "ppl": jnp.exp(loss)}
