"""Kimi-VL-A3B's decoder behind the P2M sensor, at a reduced size on the CPU.

Sigmoid routing with a selection bias (DeepSeek-V3 ``noaux_tc``) against
a NumPy reference; the dropless grouped dispatch under a planted
imbalance and split over expert shares; image embeddings at their
positions; prefill then decode through the MLA latent cache against the
plain reference's teacher-forced full forward pass (``bench/kinds/
p2m_vlm_reference.py``); the frame path's key folds; the softmax configs
routing as before; ``init_tree``'s draws.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs, frontend
from repro.core import p2m
from repro.configs.reduced import reduced
from repro.models import blocks, lm, projector
from repro.models.params import init_tree
from repro.obs import Obs
from repro.serving import ServingEngine

VLM = dataclasses.replace(reduced(configs.get_arch("kimi-vl-a3b")),
                          top_k=3, num_shared_experts=2)


def _np_route(logits, bias, k, scale):
    scores = 1.0 / (1.0 + np.exp(-logits))
    idx = np.argsort(-(scores + bias), axis=-1, kind="stable")[:, :k]
    w = np.take_along_axis(scores, idx, -1)
    return w / w.sum(-1, keepdims=True) * scale, idx


def _moe_params(cfg, seed=0):
    return init_tree(jax.random.PRNGKey(seed), blocks.moe_spec(cfg),
                     jnp.float32)


def _np_moe(params, x, weights, idx):
    """Each token's chosen experts' SwiGLU, weighted, plus the shared
    expert, one token at a time."""
    p = jax.tree.map(np.asarray, params)
    silu = lambda a: a / (1.0 + np.exp(-a))  # noqa: E731
    out = np.zeros_like(x)
    for t in range(x.shape[0]):
        for w, e in zip(weights[t], idx[t]):
            h = silu(x[t] @ p["w1"][e]) * (x[t] @ p["w3"][e])
            out[t] += w * (h @ p["w2"][e])
    sh = p["shared"]
    return out + (silu(x @ sh["w1"]) * (x @ sh["w3"])) @ sh["w2"]


class TestSigmoidRouting:
    def test_matches_numpy(self):
        logits = np.asarray(jax.random.normal(jax.random.PRNGKey(0),
                                              (16, 8)), np.float32)
        bias = np.asarray(jax.random.normal(jax.random.PRNGKey(1), (8,)),
                          np.float32) * 0.1
        w, idx = blocks.route_sigmoid(jnp.asarray(logits), jnp.asarray(bias),
                                      3, 2.446)
        w_ref, idx_ref = _np_route(logits, bias, 3, 2.446)
        np.testing.assert_array_equal(np.asarray(idx), idx_ref)
        np.testing.assert_allclose(np.asarray(w), w_ref, rtol=1e-6)
        np.testing.assert_allclose(np.asarray(w).sum(-1), 2.446, rtol=1e-6)

    def test_bias_chooses_but_does_not_weight(self):
        """A bias that lifts expert 5 into every token's top-k changes the
        choice, and its weight is its unbiased score's share."""
        logits = np.zeros((4, 8), np.float32)
        logits[:, :3] = 2.0                      # experts 0-2 score highest
        logits[:, 5] = -1.0
        bias = np.zeros(8, np.float32)
        bias[5] = 1.0                            # ... 5 is chosen instead
        w, idx = blocks.route_sigmoid(jnp.asarray(logits), jnp.asarray(bias),
                                      3, 1.0)
        idx, w = np.asarray(idx), np.asarray(w)
        assert all(5 in row for row in idx)
        s = 1.0 / (1.0 + np.exp(-logits[0]))
        chosen = [int(e) for e in idx[0]]
        want = s[chosen] / s[chosen].sum()
        np.testing.assert_allclose(w[0], want, rtol=1e-6)
        assert w[0][chosen.index(5)] < w[0][chosen.index(0)]


class TestDropless:
    # either side of the all-experts / grouped-matmul switch
    TOKENS = [blocks.DENSE_TOKENS // 16, blocks.DENSE_TOKENS + 16]

    @pytest.mark.parametrize("tokens", TOKENS)
    def test_moe_matches_per_token_reference(self, tokens):
        params = _moe_params(VLM)
        params["bias"] = 0.2 * jax.random.normal(jax.random.PRNGKey(3), (8,))
        x = jax.random.normal(jax.random.PRNGKey(1), (2, tokens // 2,
                                                      VLM.d_model))
        out = blocks.moe_apply(params, x, VLM, None, None)
        xf = np.asarray(x.reshape(-1, VLM.d_model))
        logits = np.asarray(jnp.dot(jnp.asarray(xf), params["router"],
                                    precision="highest"))
        w, idx = _np_route(logits, np.asarray(params["bias"]), VLM.top_k,
                           VLM.routed_scaling)
        np.testing.assert_allclose(
            np.asarray(out).reshape(-1, VLM.d_model),
            _np_moe(params, xf, w, idx), atol=2e-4)

    @pytest.mark.parametrize("tokens", TOKENS)
    def test_every_token_to_one_expert_drops_nothing(self, tokens):
        """A planted imbalance: the bias sends every token to expert 6
        (where a capacity of 1.25 x the mean would keep under half of
        them); each still gets its full share."""
        params = _moe_params(VLM, seed=4)
        params["bias"] = jnp.zeros((8,)).at[6].set(10.0)
        x = jax.random.normal(jax.random.PRNGKey(5), (4, tokens // 4,
                                                      VLM.d_model))
        out = blocks.moe_apply(params, x, VLM, None, None)
        xf = np.asarray(x.reshape(-1, VLM.d_model))
        logits = np.asarray(jnp.dot(jnp.asarray(xf), params["router"],
                                    precision="highest"))
        w, idx = _np_route(logits, np.asarray(params["bias"]), VLM.top_k,
                           VLM.routed_scaling)
        assert (idx == 6).any(-1).all()
        np.testing.assert_allclose(
            np.asarray(out).reshape(-1, VLM.d_model),
            _np_moe(params, xf, w, idx), atol=2e-4)

    @pytest.mark.parametrize("tokens", TOKENS)
    @pytest.mark.parametrize("shares", [2, 4])
    def test_expert_shares_add_up_to_the_layer(self, shares, tokens):
        """Each share of experts computes its part for the tokens routed
        to it over all experts; the parts add up to the whole."""
        params = _moe_params(VLM)
        x = jax.random.normal(jax.random.PRNGKey(6), (tokens, VLM.d_model))
        logits = jnp.dot(x, params["router"], precision="highest")
        w, idx = blocks.route_sigmoid(logits, params["bias"], VLM.top_k,
                                      VLM.routed_scaling)
        full = blocks._moe_dropless(x, w, idx, params["w1"], params["w2"],
                                    params["w3"], e_start=0, e_local=8)
        n = 8 // shares
        parts = sum(blocks._moe_dropless(
            x, w, idx, params["w1"][i:i + n], params["w2"][i:i + n],
            params["w3"][i:i + n], e_start=i, e_local=n)
            for i in range(0, 8, n))
        np.testing.assert_allclose(np.asarray(parts), np.asarray(full),
                                   atol=1e-5)


class TestSoftmaxConfigsUnchanged:
    @pytest.mark.parametrize("arch", ["deepseek-v2-236b", "kimi-k2-1t-a32b"])
    def test_route_by_softmax_with_capacity(self, arch):
        cfg = reduced(configs.get_arch(arch))
        assert cfg.moe_routing == "softmax" and cfg.scale_embeddings
        params = _moe_params(cfg)
        assert "bias" not in params
        x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, cfg.d_model))
        out = blocks.moe_apply(params, x, cfg, None, None)
        xf = x.reshape(-1, cfg.d_model)
        cap = int(np.ceil(16 * cfg.top_k / cfg.num_experts
                          * cfg.capacity_factor))
        ref = blocks._moe_local(xf, xf @ params["router"], params["w1"],
                                params["w2"], params["w3"], e_start=0,
                                e_local=cfg.num_experts, top_k=cfg.top_k,
                                capacity=cap)
        if "shared" in params:
            ref = ref + blocks.mlp_apply(params["shared"], xf, cfg, None,
                                         None)
        np.testing.assert_array_equal(np.asarray(out).reshape(xf.shape),
                                      np.asarray(ref))


class TestLM:
    def test_embeddings_scale_by_architecture(self):
        cfg = reduced(configs.get_arch("deepseek-v2-236b"))
        params = lm.init_params(jax.random.PRNGKey(0), cfg)
        toks = jnp.arange(8)[None] % cfg.vocab_size
        unscaled = dataclasses.replace(cfg, scale_embeddings=False)
        emb = params["embed"]["w"]
        scaled_params = {**params, "embed": {"w": emb * cfg.d_model ** 0.5}}
        np.testing.assert_allclose(
            np.asarray(lm.forward(params, toks, cfg)[0]),
            np.asarray(lm.forward(scaled_params, toks, unscaled)[0]),
            rtol=1e-5, atol=1e-5)

    def test_image_embeddings_land_at_their_positions(self):
        params = lm.init_params(jax.random.PRNGKey(0), VLM)
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0,
                                  VLM.vocab_size)
        want, _ = lm.forward(params, toks, VLM)
        images = jnp.take(params["embed"]["w"], toks[:, :4], axis=0)
        placeholders = toks.at[:, :4].set(0)
        got, _ = lm.forward(params, placeholders, VLM,
                            image_embeddings=images)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-6)
        other, _ = lm.forward(params, placeholders, VLM,
                              image_embeddings=images[::-1])
        assert not np.allclose(np.asarray(other), np.asarray(want))

    def test_prefill_logits_at_the_last_position_only(self):
        params = lm.init_params(jax.random.PRNGKey(0), VLM)
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 10), 0,
                                  VLM.vocab_size)
        full, _ = lm.forward(params, toks, VLM)
        last, cache = lm.forward(params, toks, VLM, mode="prefill")
        assert last.shape == (2, 1, VLM.vocab_size)
        np.testing.assert_allclose(np.asarray(last[:, 0]),
                                   np.asarray(full[:, -1]), atol=1e-5)
        assert int(cache["pos"]) == 10


    def test_sigmoid_routed_layers_scan_like_any_other(self):
        """The layer plan does not look at the routing: Kimi-VL's 26 MoE
        layers are one scanned body behind its dense layer, and the scan
        returns each layer's routes in layer order, as unrolled layers do."""
        plan = lm.segment_plan(configs.get_arch("kimi-vl-a3b"))
        assert [(s.name, s.repeats) for s in plan] == [("prefix0", 1),
                                                       ("body", 26)]
        cfg = dataclasses.replace(VLM, num_layers=4)
        params = lm.init_params(jax.random.PRNGKey(0), cfg)
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 6), 0,
                                  cfg.vocab_size)
        _, _, scanned = lm.forward_with_routes(params, toks, cfg,
                                               mode="prefill")
        _, _, unrolled = lm.forward_with_routes(
            params, toks, dataclasses.replace(cfg, force_unroll=True),
            mode="prefill")
        assert scanned.shape == (4 - cfg.first_dense_layers, 2, 6, cfg.top_k)
        np.testing.assert_array_equal(np.asarray(scanned),
                                      np.asarray(unrolled))
        assert not np.array_equal(np.asarray(scanned[0]),
                                  np.asarray(scanned[-1]))


@pytest.fixture(scope="module")
def vlm_kind():
    from bench import run
    return run.load_kind(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "p2m_vlm")


def _served(vlm_kind, frames=True):
    cfg = dataclasses.replace(VLM, num_layers=3)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    for seg in lm.segment_plan(cfg):
        if seg.kinds[0][1] == "moe":
            moe = params["decoder"][seg.name]["l0"]["mlp"]
            moe["bias"] = 0.1 * jax.random.normal(jax.random.PRNGKey(9),
                                                  moe["bias"].shape)
    params["projector"] = init_tree(
        jax.random.PRNGKey(2), projector.spec(14 * 14 * 8, 32, cfg.d_model),
        jnp.float32)
    params["p2m"] = {"w": 0.5 * jax.random.normal(jax.random.PRNGKey(3),
                                                  (3, 3, 3, 8)),
                     "v_th": jnp.ones(())}
    fcfg = frontend.FrontendConfig(p2m=p2m.P2MConfig(out_channels=8),
                                   backend="device")
    obs = Obs(device_annotations=False)
    eng = ServingEngine(cfg, params, max_len=20, frontend=fcfg,
                        seed=7, microbatch=2, obs=obs)
    return cfg, params, fcfg, eng, obs


def test_prefill_then_decode_match_the_reference(vlm_kind):
    """Frames + prompts served (prefill, then decode through the MLA
    latent cache) against the plain reference's teacher-forced full
    forward pass over the prompt and the generated tokens: logits at
    prefill's last position and at every decode step, and every layer's
    routes."""
    ref = vlm_kind.reference
    cfg, params, fcfg, eng, obs = _served(vlm_kind)
    frames = jax.random.uniform(jax.random.PRNGKey(4), (4, 56, 56, 3))
    prompts = jax.random.randint(jax.random.PRNGKey(5), (4, 10), 0,
                                 cfg.vocab_size).at[:, :4].set(0)
    steps = tuple(range(8))
    out = eng.serve(prompts, 8, frames=frames, keep=steps)
    assert out["tokens"].shape == (4, 8)
    # the sensor and projector of each microbatch, as the engine ran them
    images = jnp.concatenate([
        eng._encode(params, frames[i:i + 2],
                    jax.random.fold_in(jax.random.fold_in(
                        jax.random.PRNGKey(7), 0), j))[0]
        for j, i in enumerate((0, 2))])
    w = vlm_kind.reference_weights(params, cfg)
    spec = {"num_attention_heads": cfg.num_heads,
            "qk_nope_head_dim": cfg.resolved_head_dim,
            "qk_rope_head_dim": cfg.rope_head_dim,
            "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.norm_eps,
            "num_experts_per_tok": cfg.top_k, "norm_topk_prob": True,
            "routed_scaling_factor": cfg.routed_scaling,
            "precision": {"weights": "float32", "router": "float32"}}
    ids = jnp.concatenate([prompts, jnp.asarray(out["tokens"][:, :7])], 1)
    logits, routes = ref.decoder(w, spec, ids, images,
                                 [9 + s for s in steps])
    for s in steps:
        np.testing.assert_allclose(np.asarray(out["logits"][s]),
                                   logits[:, s], rtol=1e-4, atol=1e-4)
        got = np.sort(np.asarray(out["routes"][s]), -1)
        want = np.sort(routes[:, :, :10] if s == 0
                       else routes[:, :, 9 + s:10 + s], -1)
        np.testing.assert_array_equal(got, want)
    snap = obs.registry.snapshot()
    assert snap["lm_decode_steps_total"]["value"] == 7
    assert snap["lm_tokens_generated_total"]["value"] == 32
    assert snap["serving_host_syncs_total"]["value"] == 1
    assert len(obs.tracer.spans("vlm_decode")) == 7
    assert len(obs.tracer.spans("vlm_prefill")) == 1


def test_frames_take_vision_engines_key_folds(vlm_kind):
    """Item i's microbatch j draws on fold_in(fold_in(key, i), j): the
    second item's spikes differ from the first's on the same frames, and
    match the frontend called with that key."""
    cfg, params, fcfg, eng, _ = _served(vlm_kind)
    frames = jax.random.uniform(jax.random.PRNGKey(4), (4, 56, 56, 3))
    prompts = jnp.zeros((4, 6), jnp.int32)
    first = eng.serve(prompts, 2, frames=frames)["frontend"]
    second = eng.serve(prompts, 2, frames=frames)["frontend"]
    key = jax.random.fold_in(jax.random.fold_in(jax.random.PRNGKey(7), 1), 1)
    _, aux = frontend.SensorFrontend(fcfg)(params["p2m"], frames[2:], key=key,
                                           mode="device")
    np.testing.assert_array_equal(np.asarray(second[1]["channel_rates"]),
                                  np.asarray(aux["channel_rates"]))
    assert not np.array_equal(np.asarray(first[1]["channel_rates"]),
                              np.asarray(second[1]["channel_rates"]))


def test_one_engine_serves_text_only_requests(vlm_kind):
    cfg, params, _, eng, _ = _served(vlm_kind)
    prompts = jax.random.randint(jax.random.PRNGKey(5), (2, 6), 0,
                                 cfg.vocab_size)
    out = eng.generate(prompts, 5)
    assert out.shape == (2, 5)
    full, _ = lm.forward(params, prompts, cfg)
    np.testing.assert_array_equal(np.asarray(out[:, 0]),
                                  np.asarray(jnp.argmax(full[:, -1], -1)))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_init_tree_draws_as_before(dtype):
    """Each leaf is N(0, 1/fan_in) drawn in f32 from its own key and cast:
    the same values as drawing the whole leaf in f32 eagerly."""
    from repro.models.params import ParamSpec
    spec = {"a": ParamSpec((3, 16, 24), ("stack", None, None)),
            "b": ParamSpec((40, 8), (None, None), scale=2.0)}
    got = init_tree(jax.random.PRNGKey(11), spec, dtype)
    keys = jax.random.split(jax.random.PRNGKey(11), 2)
    for k, (name, fan) in zip(keys, (("a", 48), ("b", 40))):
        s = spec[name]
        want = (jax.random.normal(k, s.shape, jnp.float32)
                * (s.scale / fan ** 0.5)).astype(dtype)
        assert got[name].dtype == dtype
        np.testing.assert_array_equal(np.asarray(got[name], np.float32),
                                      np.asarray(want, np.float32))
