"""Batched serving engine: (frames +) prompts -> prefill -> KV cache ->
greedy/sampled decode.

Cache kinds per mixer (see models/lm.py cache specs):
  * full attention: (B, max_len, Hkv, Dh) K/V, sharded kv_heads on "model";
  * local attention: ring buffer of size ``window`` (long_500k feasible);
  * MLA: rank-r latent cache (B, max_len, kv_lora) — the DeepSeek trick;
  * RG-LRU / mLSTM / sLSTM: O(1) recurrent state.

With ``frontend=`` the engine also takes camera frames: each request's
frame goes through the P2M sensor (the same ``SensorFrontend`` call, backend,
draws and key folds as ``VisionEngine``: per item a key folded from the
engine's seed and item counter, and per ``microbatch`` of frames that key
folded with the microbatch's place), then the projector
(``models/projector.py``); its image embeddings take the place of the first
positions of the request's prompt. Text-only requests skip both.

``make_prefill_step`` / ``make_decode_step`` are the engine's two programs;
the multi-pod dry-run lowers them for the prefill_32k / decode_32k /
long_500k shapes.
"""
from __future__ import annotations

import contextlib
import functools
from typing import ContextManager, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro import frontend as frontend_mod
from repro import sharding
from repro.configs.base import ArchConfig
from repro.models import lm, projector
from repro.serving.vision import _named

# one count per host wait on the device (the token fetch of each request
# batch); each also marks the profiler's clock
HOST_SYNCS = "serving_host_syncs_total"
HOST_SYNC_MARK = "host_sync"
DECODE_STEPS = "lm_decode_steps_total"
TOKENS = "lm_tokens_generated_total"


def make_prefill_step(cfg: ArchConfig, mesh: Optional[Mesh] = None,
                      rules=None, *, max_len: int, temperature: float = 0.0):
    """The served prefill: ``(params, prompts, images, encoder_embeddings,
    rng, *, new_tokens)`` -> (decode state, last position's logits f32,
    routes), the state's cache grown to ``max_len`` (``_prefill``)."""
    return functools.partial(_prefill, cfg=cfg, mesh=mesh, rules=rules,
                             max_len=max_len, temperature=temperature)


def make_decode_step(cfg: ArchConfig, mesh: Optional[Mesh] = None,
                     rules=None, temperature: float = 0.0):
    """The served decode step: ``(params, state)`` -> (state, logits f32,
    routes) (``_decode``)."""
    return functools.partial(_decode, cfg=cfg, mesh=mesh, rules=rules,
                             temperature=temperature)


def _pick(logits: jax.Array, temperature: float,
          rng: Optional[jax.Array]) -> jax.Array:
    """(B,) next tokens: greedy, or sampled at ``temperature`` from ``rng``."""
    if temperature > 0.0 and rng is not None:
        return jax.random.categorical(rng, logits / temperature,
                                      axis=-1).astype(jnp.int32)
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def pad_prefill_cache(cfg: ArchConfig, prefill_cache, batch: int,
                      max_len: int):
    """Grow a seq-sized prefill cache into a max_len decode cache."""
    target = lm.init_cache(cfg, batch, max_len)

    def merge(dst, src):
        if dst.ndim == 0 or dst.shape == src.shape:
            return jnp.asarray(src, dst.dtype).reshape(dst.shape)
        sl = tuple(slice(0, min(a, b)) for a, b in zip(dst.shape, src.shape))
        src_sl = tuple(slice(0, min(a, b)) for a, b in
                       zip(dst.shape, src.shape))
        return dst.at[sl].set(src[src_sl].astype(dst.dtype))

    return jax.tree.map(merge, target, prefill_cache)


class ServingEngine:
    """Batched engine: prefill a batch of prompts (and frames), then decode
    it token by token through the cache, one program per step.

    ``obs`` (a ``repro.obs.Obs``) adds spans (``vlm_prefill``: frames,
    projector and prefill of a batch; ``vlm_decode``: one per decode step;
    ``vlm_fetch``: the tokens to the host), the counters ``DECODE_STEPS``,
    ``TOKENS`` and ``HOST_SYNCS`` and a ``HOST_SYNC_MARK`` mark per wait;
    with ``obs=None`` each is one ``is None`` check."""

    def __init__(self, cfg: ArchConfig, params, max_len: int = 512,
                 mesh: Optional[Mesh] = None, temperature: float = 0.0,
                 frontend: Optional[frontend_mod.FrontendConfig] = None,
                 seed: int = 0,
                 microbatch: Optional[int] = None, obs=None):
        self.cfg = cfg
        self.params = params
        self.max_len = max_len
        self.frontend = frontend
        self.microbatch = microbatch
        self.rules = sharding.ShardingRules.make(dict(cfg.rule_overrides))
        self._obs = obs
        self._key = jax.random.PRNGKey(seed)
        self._frame_count = 0
        step = make_prefill_step(cfg, mesh, self.rules, max_len=max_len,
                                 temperature=temperature)
        self._prefill = jax.jit(_named(step, "lm_prefill"),
                                static_argnames=("new_tokens",))
        step = make_decode_step(cfg, mesh, self.rules, temperature)
        self._decode = jax.jit(_named(step, "lm_decode"), donate_argnums=(1,))
        if frontend is not None:
            step = functools.partial(_encode, fcfg=frontend, dtype=cfg.dtype)
            self._encode = jax.jit(_named(step, "vlm_encode"))

    # --- telemetry ------------------------------------------------------------

    def _span(self, name: str, **args) -> ContextManager[None]:
        return (self._obs.span(name, **args) if self._obs is not None
                else contextlib.nullcontext())

    def _count(self, name: str, n: int = 1) -> None:
        if self._obs is not None:
            self._obs.counter(name).inc(n)

    def _host_sync(self) -> None:
        if self._obs is not None:
            self._obs.counter(HOST_SYNCS).inc()
            self._obs.mark(HOST_SYNC_MARK)

    # --- serving --------------------------------------------------------------

    def _images(self, frames: jax.Array) -> Tuple[List[jax.Array], List[Dict]]:
        """Each microbatch of frames through the sensor and the projector:
        (image embeddings per microbatch, the frontend's aux per
        microbatch). The keys fold as ``VisionEngine.stream`` folds them."""
        b, mb = frames.shape[0], self.microbatch
        key = jax.random.fold_in(self._key, self._frame_count)
        self._frame_count += 1
        if not mb or b <= mb:
            parts = [(frames, key)]
        else:
            parts = [(frames[i:i + mb], jax.random.fold_in(key, j))
                     for j, i in enumerate(range(0, b, mb))]
        outs = [self._encode(self.params, f, k) for f, k in parts]
        return [e for e, _ in outs], [a for _, a in outs]

    def serve(self, prompts: jax.Array, max_new_tokens: int,
              frames: Optional[jax.Array] = None,
              encoder_embeddings: Optional[jax.Array] = None,
              rng: Optional[jax.Array] = None,
              keep: Sequence[int] = ()) -> Dict:
        """One batch of requests. prompts: (B, S) int32; frames: (B, H, W, C)
        in [0, 1], whose image embeddings take the place of the first
        positions of each prompt (the ids there are not read).

        Returns {"tokens": (B, max_new_tokens) int32 on the host,
        "logits": {step: (B, V) f32}, "routes": {step: (MoE layers, B, S,
        K)}, "frontend": [aux per microbatch]} where step 0 is prefill's
        last position and step s the s-th decode step, kept for the steps
        in ``keep`` only (device arrays)."""
        b, s = prompts.shape
        if s + max_new_tokens > self.max_len:
            raise ValueError(f"{s} prompt + {max_new_tokens} new tokens "
                             f"exceed max_len {self.max_len}")
        keep = set(keep)
        out: Dict = {"logits": {}, "routes": {}, "frontend": []}
        with self._span("vlm_prefill", requests=b):
            images = None
            if frames is not None:
                images, out["frontend"] = self._images(frames)
            state, logits, routes = self._prefill(
                self.params, prompts, images, encoder_embeddings, rng,
                new_tokens=max_new_tokens)
        self._count(TOKENS, b)
        if 0 in keep:
            out["logits"][0], out["routes"][0] = logits, routes
        for step in range(1, max_new_tokens):
            with self._span("vlm_decode", step=step):
                state, logits, routes = self._decode(self.params, state)
            self._count(DECODE_STEPS)
            self._count(TOKENS, b)
            if step in keep:
                out["logits"][step], out["routes"][step] = logits, routes
        with self._span("vlm_fetch"):
            out["tokens"] = np.asarray(state["tokens"])
        self._host_sync()
        return out

    def generate(self, prompts: jax.Array, max_new_tokens: int,
                 encoder_embeddings: Optional[jax.Array] = None,
                 rng: Optional[jax.Array] = None,
                 frames: Optional[jax.Array] = None) -> jax.Array:
        """prompts: (B, S) int32. Returns (B, max_new_tokens)."""
        return jnp.asarray(self.serve(prompts, max_new_tokens, frames,
                                      encoder_embeddings, rng)["tokens"])


def _encode(params, frames, key, *, fcfg, dtype):
    """Frames -> (image embeddings, the frontend's aux): the sensor call of
    ``models/vision.py``'s forward, then the projector."""
    with jax.named_scope("p2m_frontend"):
        spikes, aux = frontend_mod.SensorFrontend(fcfg)(
            params["p2m"], frames, key=key, mode=fcfg.backend)
    with jax.named_scope("projector"):
        emb = projector.apply(params["projector"], spikes, dtype)
    return emb, {"theta": aux["theta"], "channel_rates": aux["channel_rates"]}


def _prefill(params, prompts, images, encoder_embeddings, rng, *,
             new_tokens, cfg, mesh, rules, max_len, temperature):
    """Prefill a batch: the decode state (cache grown to ``max_len``, the
    token buffer with the first generated token, the step), the last
    position's logits (f32) and the MoE layers' routes."""
    b = prompts.shape[0]
    if images is not None:
        images = jnp.concatenate(images, axis=0)
    logits, cache, routes = lm.forward_with_routes(
        params, prompts, cfg, mesh, rules, mode="prefill",
        encoder_embeddings=encoder_embeddings, image_embeddings=images)
    logits = logits[:, -1].astype(jnp.float32)
    with jax.named_scope("lm_head"):
        first = _pick(logits, 0.0, None)    # the first token is greedy
    tokens = jnp.zeros((b, new_tokens), jnp.int32).at[:, 0].set(first)
    state = {"cache": pad_prefill_cache(cfg, cache, b, max_len),
             "tokens": tokens, "step": jnp.asarray(1, jnp.int32)}
    if rng is not None:
        state["rng"] = rng
    return state, logits, routes


def _decode(params, state, *, cfg, mesh, rules, temperature):
    """One decode step: the previous token through the cache; the next
    token into the buffer. Returns (state, logits f32, routes)."""
    step = state["step"]
    b = state["tokens"].shape[0]
    tok = jax.lax.dynamic_slice(state["tokens"], (0, step - 1), (b, 1))
    logits, cache, routes = lm.forward_with_routes(
        params, tok, cfg, mesh, rules, mode="decode", cache=state["cache"])
    logits = logits[:, -1].astype(jnp.float32)
    rng = state.get("rng")
    with jax.named_scope("decode"), jax.named_scope("lm_head"):
        nxt = _pick(logits, temperature,
                    None if rng is None else jax.random.fold_in(rng, step - 1))
        tokens = jax.lax.dynamic_update_slice(state["tokens"], nxt[:, None],
                                              (0, step))
    new = {**state, "cache": cache, "tokens": tokens, "step": step + 1}
    return new, logits, routes
