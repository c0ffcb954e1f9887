"""Compile rehearsals for a described TPU v5e (no chip attached).

The TPU compiler is installed with JAX, and it compiles for a chip that is
described, not present: every kernel of the served frontend, and the whole
jitted ``VisionEngine`` exact and fused steps and one ``FleetEngine`` step
at ``vgg16`` widths, are lowered by Mosaic and XLA:TPU here, at the served
shape (64 frames of 32x32x3, K=27, C=32).
Interpret-mode tests cannot see what these catch: block layouts the TPU
lowering refuses, strided value slices, and grid steps over the scoped-VMEM
limit. Each test asserts that the compiled program holds the kernel
(``tpu_custom_call``).

The topology is described inside a module fixture, never while a module is
imported: only one process at a time may load the TPU library, so a
description at import time would make parallel test workers collect
different tests.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import platform
from repro.kernels import p2m_conv as pk

B, HW, CIN, C = 64, 32, 3, 32          # the served microbatch
K = 3 * 3 * CIN
N = B * (HW // 2) * (HW // 2)


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    # the persistent cache would store these executables but can never
    # read them back without a chip: keep it out of the way
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def _images(s):
    return _sds(s, (B, HW, HW, CIN))


def _q8_operands(s):
    return _sds(s, (K, 2 * C), jnp.int8), _sds(s, (1, 2 * C))


class TestKernelsCompileForV5e:
    def test_kernel_a_f32(self, one_chip):
        hlo = _compiled_text(
            lambda im, w, v: pk.p2m_phase_a_implicit_pallas(
                im, w, v, kernel=3, stride=2, interpret=False),
            _images(one_chip), _sds(one_chip, (K, 2 * C)),
            _sds(one_chip, (1, 1)))
        assert "tpu_custom_call" in hlo

    def test_kernel_a_q8(self, one_chip):
        wq, dq = _q8_operands(one_chip)
        hlo = _compiled_text(
            lambda im, w, d, v: pk.p2m_phase_a_implicit_q8_pallas(
                im, w, d, v, kernel=3, stride=2, interpret=False),
            _images(one_chip), wq, dq, _sds(one_chip, (1, 1)))
        assert "tpu_custom_call" in hlo

    def test_kernel_b(self, one_chip):
        hlo = _compiled_text(
            lambda u, th, bits: pk.p2m_phase_b_pallas(
                u, th, bits, n_valid=N, c_valid=C, block_n=N,
                interpret=False),
            _sds(one_chip, (N, C)), _sds(one_chip, (1, 1)),
            _sds(one_chip, (N, C), jnp.uint16))
        assert "tpu_custom_call" in hlo

    @pytest.mark.parametrize("on_device_rng", [False, True])
    def test_fused_f32(self, one_chip, on_device_rng):
        def step(im, w, v, th, src):
            bits, seed = (None, src) if on_device_rng else (src, None)
            return pk.p2m_fused_stream_pallas(
                im, w, v, th, bits, kernel=3, stride=2, block_n=N,
                rng_seed=seed, interpret=False)
        src = (_sds(one_chip, (1, 2), jnp.int32) if on_device_rng
               else _sds(one_chip, (N, C), jnp.uint16))
        hlo = _compiled_text(step, _images(one_chip),
                             _sds(one_chip, (K, 2 * C)),
                             _sds(one_chip, (1, 1)), _sds(one_chip, (1, 1)),
                             src)
        assert "tpu_custom_call" in hlo

    @pytest.mark.parametrize("on_device_rng", [False, True])
    def test_fused_q8(self, one_chip, on_device_rng):
        def step(im, w, d, v, th, src):
            bits, seed = (None, src) if on_device_rng else (src, None)
            return pk.p2m_fused_stream_q8_pallas(
                im, w, d, v, th, bits, kernel=3, stride=2, block_n=N,
                rng_seed=seed, interpret=False)
        wq, dq = _q8_operands(one_chip)
        src = (_sds(one_chip, (1, 2), jnp.int32) if on_device_rng
               else _sds(one_chip, (N, C), jnp.uint16))
        hlo = _compiled_text(step, _images(one_chip), wq, dq,
                             _sds(one_chip, (1, 1)), _sds(one_chip, (1, 1)),
                             src)
        assert "tpu_custom_call" in hlo


@pytest.fixture(scope="module")
def vgg16(one_chip):
    """(cfg, params) of vgg16 at its published widths, as shapes placed on
    the described chip."""
    from repro.models import vision
    cfg = vision.VisionConfig(arch="vgg16", frontend_backend="pallas")
    shapes = jax.eval_shape(lambda k: vision.init_params(k, cfg),
                            jax.random.PRNGKey(0))
    return cfg, jax.tree.map(lambda a: _sds(one_chip, a.shape, a.dtype),
                             shapes)


def _key(sharding, *lead):
    key = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    return _sds(sharding, lead + key.shape, key.dtype)


# The program picks compiled Pallas from the platform; this process runs on
# the CPU, so the engine tests tell it the platform is a TPU.

def test_vision_engine_exact_step_vgg16(one_chip, vgg16, monkeypatch):
    """The served exact step — P2M frontend (kernels A + B) feeding the
    vgg16 backbone at its published widths — compiles for the chip."""
    from repro.serving import VisionEngine
    monkeypatch.setattr(platform, "on_tpu", lambda: True)
    cfg, params = vgg16
    eng = VisionEngine(cfg, params, backend="pallas", microbatch=B)
    hlo = eng._step.lower(params, _images(one_chip),
                          _key(one_chip)).compile().as_text()
    assert "tpu_custom_call" in hlo


def test_vision_engine_fused_step_vgg16(one_chip, vgg16, monkeypatch):
    """The steady-state streaming step (fused kernel at a carried theta)."""
    from repro.serving import VisionEngine
    monkeypatch.setattr(platform, "on_tpu", lambda: True)
    cfg, params = vgg16
    eng = VisionEngine(cfg, params, backend="pallas", microbatch=B)
    hlo = eng._fused_step.lower(params, _images(one_chip), _key(one_chip),
                                _sds(one_chip, ())).compile().as_text()
    assert "tpu_custom_call" in hlo


def test_fleet_step_vgg16(one_chip, vgg16, monkeypatch):
    """One FleetEngine step: 4 chips x 16 frames, per-chip variation
    operands vmapped through the kernels."""
    from repro.serving import FleetEngine
    from repro.variation import chip as chip_mod
    monkeypatch.setattr(platform, "on_tpu", lambda: True)
    cfg, params = vgg16
    g = 4
    fleet = FleetEngine(cfg, params, backend="pallas", chips_per_step=g)
    chips = jax.eval_shape(lambda: jax.tree.map(
        lambda a: jnp.stack([a] * g),
        chip_mod.identity_chip(C, cfg.p2m.mtj.n_redundant)))
    chips = jax.tree.map(lambda a: _sds(one_chip, a.shape, a.dtype), chips)
    hlo = fleet._step.lower(params, chips, _sds(one_chip, (g, C)),
                            _sds(one_chip, (g, 16, HW, HW, CIN)),
                            _key(one_chip, g)).compile().as_text()
    assert "tpu_custom_call" in hlo


def _on_chip(sharding, tree):
    return jax.tree.map(lambda a: _sds(sharding, a.shape, a.dtype), tree)


@pytest.mark.parametrize("positions", [1, 128])
def test_sigmoid_moe_layer_kimi_vl(one_chip, positions):
    """Kimi-VL-A3B's MoE layer at published widths for 64 requests, as a
    decode step (64 tokens: every expert on every token, plain dots) and as
    a prefill (8,192 tokens: sorted assignments, the grouped matmul as a
    TPU kernel); sigmoid routing over 64 experts, top-6."""
    import dataclasses

    from repro import configs
    from repro.models import blocks, params as params_mod
    cfg = dataclasses.replace(configs.get_arch("kimi-vl-a3b"), num_layers=2)
    params = _on_chip(one_chip, params_mod.abstract_tree(
        blocks.moe_spec(cfg), cfg.pdtype))
    x = _sds(one_chip, (64, positions, cfg.d_model), jnp.bfloat16)
    hlo = jax.jit(lambda p, x: blocks.moe_apply(p, x, cfg, None, None)).lower(
        params, x).compile().as_text()
    grouped = 64 * positions > blocks.DENSE_TOKENS
    assert ("ragged" in hlo) == grouped
    assert ("tpu_custom_call" in hlo) == grouped


def _unfused(hlo: str, shape: str) -> list:
    """Instructions of ``hlo`` outside any fusion that make a new buffer of
    ``shape`` (a ``dtype[dims]`` regex): not a parameter, a tuple element
    or a bitcast of one."""
    fused = set(re.findall(r"calls=(%[\w.\-]+)", hlo))
    comp, out = None, []
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY )?(%[\w.\-]+) .*\{$", line)
        if head:
            comp = head.group(1)
            continue
        made = re.match(r"^\s*(?:ROOT )?%[\w.\-]+ = (" + shape
                        + r")\{[^}]*\} ([\w\-]+)\(", line)
        if made and comp not in fused and made.group(2) not in (
                "parameter", "get-tuple-element", "bitcast"):
            out.append(line.strip())
    return out


def test_decode_step_kimi_vl_fits_one_chip(one_chip):
    """The served decode step of ``kimi_vl_a3b.stream`` (the dense layer + 8
    MoE layers, 64 requests, a 256-position latent cache) compiles for one
    v5e and, with its 10.9 GB of weights, fits the chip's 16 GB. The MoE
    layers are one scan over stacked weights, and the step reads each
    layer's experts where they lie: no expert matrix is copied out of the
    stack (only prefill's grouped-matmul kernel takes such a copy)."""
    import dataclasses

    from repro import configs
    from repro.models import lm
    from repro.serving import ServingEngine
    cfg = dataclasses.replace(configs.get_arch("kimi-vl-a3b"), num_layers=9)
    params = _on_chip(one_chip, lm.abstract_params(cfg))
    eng = ServingEngine(cfg, None, max_len=256)
    prompts = _sds(one_chip, (64, 128), jnp.int32)
    state = jax.eval_shape(eng._prefill, params, prompts, None, None, None,
                           new_tokens=128)[0]
    compiled = eng._decode.lower(params, _on_chip(one_chip, state)).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert 10.9e9 < used < 16e9
    assert _unfused(compiled.as_text(),
                    r"bf16\[64,(?:2048,1408|1408,2048)\]") == []
    pre = eng._prefill.lower(params, prompts, None, None, None,
                             new_tokens=128).compile().as_text()
    assert _unfused(pre, r"bf16\[64,(?:2048,1408|1408,2048)\]")
