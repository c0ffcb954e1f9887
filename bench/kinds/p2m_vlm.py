"""Kind ``p2m_vlm``: a vision-language decoder fed by the P2M sensor, served
by ``ServingEngine`` (see ``bench/kinds/__init__.py`` for what a kind
provides).

A configuration holds the language model's own ``config.json`` keys at its
top level (DeepSeek-V3-style: MLA, sigmoid-routed experts), the sensor
under ``frontend`` (the ``p2m_vgg`` keys), the projector's ``patch`` and
``hidden`` width, the request shape under ``requests`` (batch, text tokens
after the image tokens, tokens generated) and the stated ``precision``.
Weights, frames and text prompts are made on the device from the seed; the
engine serves one batch of ``requests["batch"]`` requests per item (the
frames in microbatches of ``frontend["microbatch"]``) and brings the
generated tokens to the host. The comparison and its plain reference, which
import nothing of the program, are in ``p2m_vlm_reference.py`` beside this
file.
"""
from __future__ import annotations

import functools
import re
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import loops
from bench.kinds import p2m_vgg
from bench.kinds import p2m_vlm_reference as reference
from repro import frontend
from repro.configs.base import ArchConfig
from repro.models import lm, params as params_mod, projector
from repro.obs import Obs, compiles
from repro.serving import engine as serving_engine

# pool of frames and prompts the closed loop cycles through, in batches
POOL_BATCHES = 2
# std of the seeded per-expert selection bias; the router's scores spread
# about 0.2 over the experts at these weights
BIAS_STD = 0.1

# what the traced window is read by: the grouped matmul of the experts
# (XLA's ``ragged-dot`` kernels, whose metadata keeps no scope), the
# model's device scopes (``models/lm.py``: a decode step's under
# ``decode/``), the engine's host spans outermost first
# (``serving/engine.py``) and the marks it leaves for each program load and
# host sync
KERNELS = ("ragged-dot",)
SCOPES = re.compile(
    r"(?:^|/)((?:decode/)?(?:lm/(?:mla|moe/(?:router|experts|shared)|"
    r"dense_mlp)|lm_embed|lm_head)|projector|p2m_frontend)(?=/|$)")
SPANS = ("vlm_prefill", "vlm_decode", "vlm_fetch")
MARKS = {"compiles": compiles.MARK,
         "syncs": serving_engine.HOST_SYNC_MARK}
COUNTERS = (serving_engine.DECODE_STEPS, serving_engine.TOKENS,
            serving_engine.HOST_SYNCS)

limits = reference.limits


def arch_config(cfg: Dict) -> ArchConfig:
    """The program's ``ArchConfig`` from the configuration's
    ``config.json`` keys, at its stated precision."""
    if cfg["qk_nope_head_dim"] != cfg["v_head_dim"]:
        raise ValueError("the program's MLA takes one head width for the "
                         "no-rope keys and the values")
    if not (cfg["scoring_func"] == "sigmoid" and cfg["topk_method"]
            == "noaux_tc" and cfg["n_group"] == cfg["topk_group"] == 1
            and cfg["norm_topk_prob"]):
        raise ValueError("routing other than one-group noaux_tc sigmoid "
                         "with normalised weights")
    prec = cfg["precision"]
    return ArchConfig(
        name=cfg["name"], family="vlm",
        num_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["moe_intermediate_size"], vocab_size=cfg["vocab_size"],
        head_dim=cfg["qk_nope_head_dim"], block_pattern=("mla",),
        kv_lora_rank=cfg["kv_lora_rank"],
        q_lora_rank=cfg["q_lora_rank"] or 0,
        rope_head_dim=cfg["qk_rope_head_dim"],
        num_experts=cfg["n_routed_experts"],
        num_shared_experts=cfg["n_shared_experts"],
        top_k=cfg["num_experts_per_tok"],
        first_dense_layers=cfg["first_k_dense_replace"],
        dense_d_ff=cfg["intermediate_size"], moe_routing="sigmoid",
        routed_scaling=cfg["routed_scaling_factor"],
        rope_theta=float(cfg["rope_theta"]), norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"], scale_embeddings=False,
        param_dtype=prec["weights"], compute_dtype=prec["activations"],
        remat="none", source=cfg["source"])


def frontend_config(cfg: Dict) -> frontend.FrontendConfig:
    if cfg["projector"]["patch"] != projector.PATCH:
        raise ValueError(f"the program cuts {projector.PATCH}x"
                         f"{projector.PATCH} patches")
    fe = cfg["frontend"]
    vcfg = p2m_vgg.vision_config({**fe, "name": cfg["name"], "arch": "",
                                  "num_classes": 0, "weight_bits": 0})
    return frontend.FrontendConfig(p2m=vcfg.p2m, backend=fe["backend"])


def image_tokens(cfg: Dict) -> int:
    fe = cfg["frontend"]
    side = -(-fe["in_hw"] // fe["p2m"]["stride"]) // cfg["projector"]["patch"]
    return side * side


def _patch_dim(cfg: Dict) -> int:
    return cfg["projector"]["patch"] ** 2 * cfg["frontend"]["p2m"][
        "out_channels"]


# --- weights, frames and prompts, made on the device from the seed ----------

@functools.partial(jax.jit, static_argnums=(1, 2))
def _normal(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


def weights(cfg: Dict, arch: ArchConfig, seed: int) -> Dict:
    """The served parameters: the language model's (``lm.init_params``,
    N(0, 1/fan_in) matrices), with N(0, 1) token embeddings (the model does
    not scale them) and each MoE layer's selection bias N(0, BIAS_STD^2);
    the projector's; the P2M layer's (He-normal, v_th 1, as ``p2m_vgg``)."""
    key = jax.random.fold_in(p2m_vgg.root_key(seed), 1)
    k_lm, k_emb, k_bias, k_proj, k_p2m = jax.random.split(key, 5)
    params = lm.init_params(k_lm, arch)
    params["embed"]["w"] = _normal(k_emb, params["embed"]["w"].shape,
                                   arch.pdtype)
    for seg in lm.segment_plan(arch):
        moe = params["decoder"][seg.name]["l0"]["mlp"]
        if "bias" in moe:
            k_bias, k = jax.random.split(k_bias)
            moe["bias"] = BIAS_STD * jax.random.normal(
                k, moe["bias"].shape, jnp.float32)
    params["projector"] = params_mod.init_tree(
        k_proj, projector.spec(_patch_dim(cfg), cfg["projector"]["hidden"],
                               arch.d_model), arch.pdtype)
    p = cfg["frontend"]["p2m"]
    shape = (p["kernel_size"], p["kernel_size"], p["in_channels"],
             p["out_channels"])
    fan = shape[0] * shape[1] * shape[2]
    params["p2m"] = {"w": jax.random.normal(k_p2m, shape) * (2.0 / fan) ** 0.5,
                     "v_th": jnp.ones(())}
    return params


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _prompts(key, n: int, img: int, text: int, vocab: int):
    ids = jax.random.randint(key, (n, text), 0, vocab, jnp.int32)
    return jnp.concatenate([jnp.zeros((n, img), jnp.int32), ids], axis=1)


def prompt_pool(cfg: Dict, n: int, seed: int) -> jax.Array:
    """(n, image tokens + text tokens) prompts: placeholders where the image
    embeddings go, then text ids drawn uniformly from the vocabulary."""
    return _prompts(jax.random.fold_in(p2m_vgg.root_key(seed), 3), n,
                    image_tokens(cfg), cfg["requests"]["text_tokens"],
                    cfg["vocab_size"])


def reference_weights(params: Dict, arch: ArchConfig) -> Dict:
    """The served weights in the reference's layout (the same arrays): a
    layer is (its weights, its place in their stack, or None where the
    program keeps the layer apart)."""
    def layer(lp):
        a = lp["mixer"]
        out = {"attn": {k: a[k] for k in ("wq", "wdkv", "wkr", "wuk", "wuv",
                                           "wo")},
               "ln1": lp["ln1"]["scale"], "ln2": lp["ln2"]["scale"]}
        out["attn"]["kv_norm"] = a["kv_norm"]["scale"]
        m = lp["mlp"]
        if "router" in m:
            out.update({k: m[k] for k in ("router", "bias", "w1", "w2",
                                          "w3")})
            out["shared"] = m["shared"]
        else:
            out["mlp"] = m
        return out
    layers = []
    for seg in lm.segment_plan(arch):
        if len(seg.kinds) > 1:
            raise ValueError("the reference takes one layer per repeat")
        lp = layer(params["decoder"][seg.name]["l0"])
        layers += ([(lp, None)] if seg.repeats == 1
                   else [(lp, i) for i in range(seg.repeats)])
    return {"embed": params["embed"]["w"], "lm_head": params["lm_head"]["w"],
            "final_norm": params["final_norm"]["scale"], "layers": layers,
            "projector": params["projector"], "p2m": params["p2m"]}


# --- the system under test ---------------------------------------------------

class Server:
    """``ServingEngine`` with the sensor in front, on the benchmark's
    weights and a pool of ``pool_batches`` batches of frames and prompts.
    An item is one batch of requests; ``serve`` returns its generated
    tokens on the host. With ``trace`` the engine reports to an ``Obs``."""

    def __init__(self, cfg: Dict, seed: int, trace: bool = False,
                 pool_batches: int = POOL_BATCHES):
        req = cfg["requests"]
        self.cfg, self.seed = cfg, seed
        self.arch = arch_config(cfg)
        self.mb = cfg["frontend"]["microbatch"]
        self.batch, self.new_tokens = req["batch"], req["new_tokens"]
        self.params = weights(cfg, self.arch, seed)
        n = pool_batches * self.batch
        self.pool = p2m_vgg.frame_pool(cfg["frontend"], n, seed)
        self.prompts = prompt_pool(cfg, n, seed)
        self.pool_n = n
        self.obs = Obs() if trace else None
        self.engine = serving_engine.ServingEngine(
            self.arch, self.params,
            max_len=self.prompts.shape[1] + self.new_tokens,
            frontend=frontend_config(cfg),
            seed=p2m_vgg.reference.engine_seed(seed), microbatch=self.mb,
            obs=self.obs)
        self.keep = reference.kept_steps(self.new_tokens)
        self.index = 0           # items served
        self.microbatches = 0    # frame microbatches served
        self._gather = jax.jit(lambda a, i: jnp.take(a, i, axis=0))
        self._picked = jax.jit(_picked)

    def blocks(self, n: int) -> List:
        """The pool cut into consecutive (frames, prompts) batches."""
        if n != self.batch:
            raise ValueError(f"the configuration serves batches of "
                             f"{self.batch} requests, not {n}")
        return [(self.pool[i:i + n], self.prompts[i:i + n])
                for i in range(0, self.pool_n, n)]

    def window(self, idx: np.ndarray):
        i = jnp.asarray(idx, jnp.int32)
        return self._gather(self.pool, i), self._gather(self.prompts, i)

    def _rows(self, index: int) -> List[int]:
        """The request of each frame microbatch of item ``index`` that the
        check may compare, chosen from the seed: its row in the batch."""
        return [j * self.mb + int(loops.hash_u01(
            self.seed ^ 0x2545F491, index * 4096 + j) * self.mb)
            for j in range(self.batch // self.mb)]

    def serve(self, block):
        """Serve one batch; returns (tokens on the host, the engine's
        output, each frame microbatch's frontend outputs, item index).
        The kept logits and routes of the requests ``_rows`` chooses are
        cut out on the device, in one program compiled in warm-up."""
        frames, prompts = block
        i, self.index = self.index, self.index + 1
        out = self.engine.serve(prompts, self.new_tokens, frames=frames,
                                keep=self.keep)
        rows = np.asarray(self._rows(i), np.int32)
        out["picked"] = self._picked(out.pop("logits"), out.pop("routes"),
                                     prompts, rows)
        self.microbatches += len(out["frontend"])
        return out["tokens"], out, out["frontend"], i

    def samples(self, index: int, idx: np.ndarray, real: int, out: Dict,
                parts: List[Dict]) -> List[loops.Sample]:
        """One sample per frame microbatch, holding what the check compares
        of its chosen request (prompt, tokens, logits and routes at the
        kept steps) and the microbatch's fresh threshold and channel
        rates, on the host."""
        logits, routes, prompts = jax.tree.map(np.asarray, out["picked"])
        views = []
        for j, (part, g) in enumerate(zip(parts, self._rows(index))):
            views.append({
                "theta": float(part["theta"]),
                "channel_rates": np.asarray(part["channel_rates"]),
                "row": g - j * self.mb, "prompt": prompts[j],
                "tokens": out["tokens"][g],
                "logits": {k: logits[k][j] for k in self.keep},
                "routes": {k: routes[k][j] for k in self.keep}})
        return loops.item_samples(index, idx, real, views)

    def counters(self) -> Dict[str, Optional[float]]:
        snap = self.obs.registry.snapshot() if self.obs is not None else {}
        return {k: None if k not in snap
                else float(snap[k].get("value", 0.0)) for k in COUNTERS}

    def step_programs(self) -> List[str]:
        """The optimized HLO text of the engine's three programs at the
        served shapes: frames to image embeddings, prefill, decode step."""
        eng, fe = self.engine, self.cfg["frontend"]
        sds = lambda t: jax.tree.map(  # noqa: E731
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), t)
        params = sds(self.params)
        frames = jax.ShapeDtypeStruct(
            (self.mb, fe["in_hw"], fe["in_hw"], fe["p2m"]["in_channels"]),
            jnp.float32)
        key = jax.random.PRNGKey(0)
        enc = eng._encode.lower(params, frames, key)
        images = [jax.eval_shape(eng._encode, params, frames, key)[0]] * (
            self.batch // self.mb)
        prompts = jax.ShapeDtypeStruct((self.batch, self.prompts.shape[1]),
                                       jnp.int32)
        pre = eng._prefill.lower(params, prompts, images, None, None,
                                 new_tokens=self.new_tokens)
        state = jax.eval_shape(eng._prefill, params, prompts, images, None,
                               None, new_tokens=self.new_tokens)[0]
        dec = eng._decode.lower(params, state)
        return [low.compile().as_text() for low in (enc, pre, dec)]


def _picked(logits: Dict, routes: Dict, prompts: jax.Array,
            rows: jax.Array):
    """The chosen requests' logits (step: (n, V)), routes (step 0: (n, MoE
    layers, S, K); a decode step: (n, MoE layers, K)) and prompts."""
    return ({k: v[rows] for k, v in logits.items()},
            {k: jnp.moveaxis(v[:, rows] if k == 0 else v[:, rows, 0], 1, 0)
             for k, v in routes.items()},
            prompts[rows])


def readings(cfg: Dict, server: Server, seed: int, samples,
             control: bool = False) -> Dict[str, float]:
    """The compared numbers of ``samples`` (``p2m_vlm_reference``)."""
    return reference.readings(cfg, reference_weights(server.params,
                                                     server.arch),
                              server.pool, seed, samples, control)


# --- work of one request, counted from the configuration's shapes ------------

def _shapes(cfg: Dict) -> Dict[str, int]:
    req = cfg["requests"]
    return {"d": cfg["hidden_size"], "h": cfg["num_attention_heads"],
            "dn": cfg["qk_nope_head_dim"], "dr": cfg["qk_rope_head_dim"],
            "dv": cfg["v_head_dim"], "r": cfg["kv_lora_rank"],
            "f": cfg["moe_intermediate_size"], "fd": cfg["intermediate_size"],
            "e": cfg["n_routed_experts"], "k": cfg["num_experts_per_tok"],
            "fs": cfg["moe_intermediate_size"] * cfg["n_shared_experts"],
            "v": cfg["vocab_size"], "dense": cfg["first_k_dense_replace"],
            "moe": cfg["num_hidden_layers"] - cfg["first_k_dense_replace"],
            "b": req["batch"], "s": image_tokens(cfg) + req["text_tokens"],
            "n": req["new_tokens"]}


def _attn_params(x: Dict[str, int]) -> int:
    """MLA matrices of one layer: queries, latent and rotary key, the
    latent's up-projections to keys and values, the output."""
    return (x["d"] * x["h"] * (x["dn"] + x["dr"]) + x["d"] * (x["r"] + x["dr"])
            + x["r"] * x["h"] * (x["dn"] + x["dv"]) + x["h"] * x["dv"] * x["d"])


def _attn_scores_flops(x: Dict[str, int], ctx: int) -> int:
    """Scores and weighted values of one token against ``ctx`` positions
    in one layer (expanded form)."""
    return 2 * ctx * x["h"] * (x["dn"] + x["dr"] + x["dv"])


def _token_flops(x: Dict[str, int]) -> int:
    """FLOPs of one position through every layer, the head apart: 2 per
    weight the token meets (attention, dense MLP, router, shared and its
    k routed experts)."""
    dense = _attn_params(x) + 3 * x["d"] * x["fd"]
    moe = (_attn_params(x) + x["d"] * x["e"] + 3 * x["d"] * x["fs"]
           + x["k"] * 3 * x["d"] * x["f"])
    return 2 * (x["dense"] * dense + x["moe"] * moe)


def expert_bytes(cfg: Dict) -> int:
    """bf16 bytes of every routed expert's weights, all MoE layers."""
    x = _shapes(cfg)
    return x["moe"] * x["e"] * 3 * x["d"] * x["f"] * 2


def step_weight_bytes(cfg: Dict) -> int:
    """bf16 bytes of the weights a decode step reads: every matrix but the
    embedding table (of which it gathers one row per request), every
    routed expert (a batch of requests picks nearly all of them), the f32
    routers and biases."""
    x = _shapes(cfg)
    layers = x["dense"] + x["moe"]
    matrices = (layers * _attn_params(x) + x["dense"] * 3 * x["d"] * x["fd"]
                + x["moe"] * 3 * x["d"] * x["fs"] + x["d"] * x["v"])
    return (2 * matrices + expert_bytes(cfg)
            + 4 * x["moe"] * (x["d"] + 1) * x["e"])


def work(cfg: Dict) -> Dict[str, Dict[str, int]]:
    """The work of one request (a frame and its prompt in, ``new_tokens``
    out) by part: FLOPs (2 per MAC) of the whole pass (``model``: sensor,
    projector, the prompt's prefill with the head at its last position, the
    decode steps with theirs); the sensor's FLOPs and HBM bytes per frame
    (``p2m_frontend``, as ``p2m_vgg`` counts them); the routed experts'
    FLOPs (2 x 3 x d x f per token-expert assignment) and their weights'
    bytes, read once per prefill and once per decode step and divided over
    the batch, apart for prefill (``experts_prefill``) and decode
    (``experts_decode``); and the decode steps' FLOPs and bytes
    (``decode``: the weights a step reads and the latent cache, over the
    batch)."""
    x = _shapes(cfg)
    s, n, b = x["s"], x["n"], x["b"]
    head = 2 * x["d"] * x["v"]
    layers = x["dense"] + x["moe"]
    fe = cfg["frontend"]
    sensor = 2 * p2m_vgg.frontend_macs(fe)
    proj = 2 * image_tokens(cfg) * (
        _patch_dim(cfg) * cfg["projector"]["hidden"]
        + cfg["projector"]["hidden"] * x["d"])
    prefill = s * _token_flops(x) + head + layers * sum(
        _attn_scores_flops(x, p + 1) for p in range(s))
    decode_flops = sum(_token_flops(x) + head + layers * _attn_scores_flops(
        x, s + t + 1) for t in range(n - 1))
    # a step reads the latent cache of every position so far and writes one
    cache = sum(layers * (s + t + 1) * (x["r"] + x["dr"]) * 2 * b
                for t in range(n - 1))
    assign = 2 * 3 * x["d"] * x["f"] * x["k"] * x["moe"]
    return {"model": {"flops": sensor + proj + prefill + decode_flops},
            "p2m_frontend": {"flops": sensor,
                             "bytes": p2m_vgg.frontend_bytes_per_frame(fe)},
            "experts_prefill": {"flops": s * assign,
                                "bytes": expert_bytes(cfg) // b},
            "experts_decode": {"flops": (n - 1) * assign,
                               "bytes": (n - 1) * expert_bytes(cfg) // b},
            "decode": {"flops": decode_flops,
                       "bytes": ((n - 1) * step_weight_bytes(cfg) + cache)
                       // b}}
