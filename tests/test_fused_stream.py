"""Fused streaming frontend tests (DESIGN.md §9): implicit-im2col kernel A
parity under non-default geometry, fused-kernel bit-parity at a pinned
theta, the VisionEngine theta-EMA drift guard (key-free determinism, exact
fallback), and the zero-recompile streaming property."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import frontend
from repro.analysis import tracecheck
from repro.core import p2m
from repro.kernels import autotune, blocking, ops, ref
from repro.kernels import p2m_conv as pk
from repro.models import vision
from repro.serving import VisionEngine

CFG = p2m.P2MConfig()


def _setup(seed=0, b=2, hw=32, cfg=CFG):
    params = p2m.init_params(jax.random.PRNGKey(seed), cfg)
    frame = jax.random.uniform(jax.random.PRNGKey(seed + 1), (b, hw, hw, 3))
    return params, frame


class TestImplicitIm2col:
    """The in-kernel patch gather must reproduce the explicit im2col rows
    (and through them ``p2m_phase_a_ref``) for every SAME geometry."""

    @pytest.mark.parametrize("kernel,stride,h,w", [
        (3, 2, 32, 32),    # the paper geometry
        (3, 1, 16, 16),    # non-default stride
        (3, 3, 18, 18),    # stride > half kernel
        (5, 2, 12, 12),    # larger kernel
        (3, 2, 15, 15),    # odd extent: asymmetric SAME padding
        (3, 2, 14, 10),    # non-square frames
        (5, 3, 13, 11),    # everything non-default at once
    ])
    def test_matches_phase_a_ref(self, kernel, stride, h, w):
        key = jax.random.PRNGKey(0)
        images = jax.random.uniform(key, (2, h, w, 3))
        wt = jax.random.normal(jax.random.fold_in(key, 1),
                               (kernel, kernel, 3, 8)) * 0.3
        wm = wt.reshape(-1, 8)
        uk, hk = pk.p2m_phase_a_implicit_pallas(
            images, pk.pack_phase_weights(wm), jnp.ones((1, 1)),
            kernel=kernel, stride=stride, block_n=64)
        n = uk.shape[0]
        patches = ops.im2col(images, kernel, stride)
        assert patches.shape[0] == n
        ur, _ = ref.p2m_phase_a_ref(patches.astype(jnp.float32),
                                    wm.astype(jnp.float32), jnp.asarray(1.0),
                                    block_n=n)
        np.testing.assert_allclose(np.asarray(uk), np.asarray(ur), atol=3e-6)
        # the combined Hoyer threshold agrees regardless of the blocking
        theta_k = pk.combine_hoyer_partials(hk, jnp.asarray(1.0))
        from repro.core import hoyer
        theta_r = hoyer.hoyer_extremum(hoyer.clip01(ur))
        np.testing.assert_allclose(float(theta_k), float(theta_r), rtol=1e-5)

    def test_block_geometry_invariants(self):
        for (b, ho, wo, bn) in ((16, 16, 16, 2048), (2, 16, 16, 64),
                                (3, 7, 5, 512), (4, 8, 8, 1)):
            bb, boh = blocking.a_block_geometry(b, ho, wo, bn)
            assert b % bb == 0 and ho % boh == 0
            assert bb == 1 or boh == ho     # frames batch only on full rows
            assert bb * boh * wo <= max(bn, wo)

    def test_grid_steps_capped_by_vmem_budget(self):
        """Any patch-row target — the fused default asks for the whole
        microbatch — yields steps within the VMEM budget: 64 served
        frames of 32x32x3 run 4 frames per step."""
        assert blocking.implicit_block(64, 32, 32, 3, 3, 2, 32,
                                       64 * 256) == (4, 16)
        # a small target is left alone
        assert blocking.implicit_block(64, 32, 32, 3, 3, 2, 32, 512) == (2,
                                                                         16)
        cap = blocking.elem_rows_cap(32)
        assert cap * blocking.row_vmem_bytes(0, 32) <= blocking.VMEM_BUDGET
        u = jnp.zeros((4 * cap, 32))
        bits = jnp.zeros((4 * cap, 32), jnp.uint16)
        _, stats = pk.p2m_phase_b_pallas(u, jnp.ones((1, 1)), bits,
                                         n_valid=4 * cap, c_valid=32,
                                         block_n=4 * cap)
        assert stats.shape[0] >= 4      # the oversized block was split

    def test_u_invariant_to_block_rows(self):
        params, frame = _setup(seed=3, b=4, hw=16)
        wq = p2m.quantize_weights(params["w"], CFG.weight_bits)
        wp = pk.pack_phase_weights(wq.reshape(-1, CFG.out_channels))
        outs = [pk.p2m_phase_a_implicit_pallas(
            frame, wp, jnp.ones((1, 1)), kernel=3, stride=2, block_n=bn)[0]
            for bn in (64, 256, 1024)]
        for u in outs[1:]:
            np.testing.assert_array_equal(np.asarray(u), np.asarray(outs[0]))


class TestFusedKernelParity:
    def test_fused_pinned_theta_bit_exact_vs_two_kernel(self):
        """With the carried theta pinned to the exact pipeline's own
        threshold the fused single-kernel step reproduces the two-kernel
        activations bit-for-bit (and the V_CONV stats to reduction order)."""
        params, frame = _setup(seed=5, b=2, hw=32)
        key = jax.random.PRNGKey(9)
        wq = p2m.quantize_weights(params["w"], CFG.weight_bits)
        o, aux = ops.p2m_frontend(frame, wq, params["v_th"], key)
        of, auxf = ops.p2m_frontend_fused(frame, wq, params["v_th"],
                                          aux["theta"], key)
        np.testing.assert_array_equal(np.asarray(of), np.asarray(o))
        np.testing.assert_allclose(float(auxf["theta"]),
                                   float(aux["theta"]), rtol=1e-6)
        for k in ("v_conv_mean", "v_conv_min", "v_conv_max"):
            np.testing.assert_allclose(float(auxf[k]), float(aux[k]),
                                       rtol=1e-6, err_msg=k)

    def test_fused_pinned_theta_with_variation_operand(self):
        """The (4, C) chip operand rides the fused kernel identically."""
        from repro.variation.chip import (VariationConfig, channel_operands,
                                          sample_chip)
        vcfg = VariationConfig(sigma_logit_offset=0.3, sigma_pixel_gain=0.05,
                               sigma_pixel_offset=0.05)
        chip = sample_chip(vcfg, CFG.out_channels, 8, chip_id=3)
        chan = channel_operands(chip, jnp.linspace(-0.05, 0.05,
                                                   CFG.out_channels))
        params, frame = _setup(seed=7, b=2, hw=16)
        key = jax.random.PRNGKey(11)
        wq = p2m.quantize_weights(params["w"], CFG.weight_bits)
        o, aux = ops.p2m_frontend(frame, wq, params["v_th"], key, chan=chan)
        of, _ = ops.p2m_frontend_fused(frame, wq, params["v_th"],
                                       aux["theta"], key, chan=chan)
        np.testing.assert_array_equal(np.asarray(of), np.asarray(o))

    def test_fused_channel_rates_match_activation_map(self):
        params, frame = _setup(seed=8, b=2, hw=16)
        wq = p2m.quantize_weights(params["w"], CFG.weight_bits)
        of, auxf = ops.p2m_frontend_fused(frame, wq, params["v_th"],
                                          jnp.asarray(0.7),
                                          jax.random.PRNGKey(0))
        rates = jnp.mean(of, axis=(0, 1, 2))
        np.testing.assert_allclose(np.asarray(auxf["channel_rates"]),
                                   np.asarray(rates), atol=1e-6)


def _vis_engine(**kw):
    cfg = vision.VisionConfig(name="t", arch="vgg_tiny", num_classes=10)
    params = vision.init_params(jax.random.PRNGKey(0), cfg)
    return VisionEngine(cfg, params, backend="pallas", **kw), cfg, params


class TestStreamDriftGuard:
    def test_first_microbatch_is_exact_and_seeds_carry(self):
        eng, _, _ = _vis_engine(microbatch=2)
        frames = jax.random.uniform(jax.random.PRNGKey(1), (2, 32, 32, 3))
        assert eng._theta_carry is None
        (out,) = list(eng.stream([frames]))
        assert float(out["stream_fused"]) == 0.0     # exact first microbatch
        assert eng._theta_carry is not None

    def test_zero_tolerance_falls_back_to_exact_everywhere(self):
        """tol = 0 forces the guard on every post-seed microbatch, so the
        whole stream must be bit-identical to a fused_stream=False engine —
        the fallback really is the exact path and really is served."""
        frames = jax.random.uniform(jax.random.PRNGKey(2), (6, 32, 32, 3))
        eng, _, _ = _vis_engine(microbatch=2, fused_stream=True,
                                fused_theta_tol=0.0)
        ref_eng, _, _ = _vis_engine(microbatch=2, fused_stream=False)
        (a,) = list(eng.stream([frames]))
        (b,) = list(ref_eng.stream([frames]))
        np.testing.assert_array_equal(np.asarray(a["probs"]),
                                      np.asarray(b["probs"]))
        assert eng.fused_fallback_count == eng.fused_step_count > 0

    def test_guard_is_key_deterministic(self):
        """The drift guard depends on the frames only: engines with
        different rng seeds fire the identical fallback pattern."""
        frames = jnp.concatenate([
            0.1 * jax.random.uniform(jax.random.PRNGKey(3), (2, 32, 32, 3)),
            jax.random.uniform(jax.random.PRNGKey(4), (2, 32, 32, 3)),
            0.1 * jax.random.uniform(jax.random.PRNGKey(5), (2, 32, 32, 3)),
        ])
        runs = []
        for seed in (0, 1234):
            eng, _, _ = _vis_engine(microbatch=2, fused_stream=True,
                                    fused_theta_tol=0.05, seed=seed)
            list(eng.stream([frames]))
            runs.append((eng.fused_step_count, eng.fused_fallback_count))
        assert runs[0] == runs[1]
        # the bright/dark scene change really moved theta beyond 5%
        assert runs[0][1] >= 1

    def test_huge_tolerance_never_falls_back(self):
        frames = jax.random.uniform(jax.random.PRNGKey(6), (6, 32, 32, 3))
        eng, _, _ = _vis_engine(microbatch=2, fused_stream=True,
                                fused_theta_tol=1e9)
        (out,) = list(eng.stream([frames]))
        assert eng.fused_fallback_count == 0
        assert eng.fused_step_count == 2            # mb 2 and 3 (1 seeds)
        assert 0.0 < float(out["stream_fused"]) < 1.0

    def test_classify_is_untouched_by_fused_machinery(self):
        """Non-streaming calls never plant the carry and never emit the
        streaming telemetry keys — bit-identical to a plain engine."""
        frames = jax.random.uniform(jax.random.PRNGKey(7), (4, 32, 32, 3))
        key = jax.random.PRNGKey(8)
        a, _, _ = _vis_engine(fused_stream=True)
        b, _, _ = _vis_engine(fused_stream=False)
        oa = a.classify(frames, key=key)
        ob = b.classify(frames, key=key)
        np.testing.assert_array_equal(np.asarray(oa["probs"]),
                                      np.asarray(ob["probs"]))
        assert "stream_fused" not in oa
        assert a._theta_carry is None

    def test_fused_stream_requires_pallas_backend(self):
        cfg = vision.VisionConfig(name="t", arch="vgg_tiny", num_classes=10)
        params = vision.init_params(jax.random.PRNGKey(0), cfg)
        with pytest.raises(ValueError, match="pallas"):
            VisionEngine(cfg, params, backend="device", fused_stream=True)

    def test_stream_compiles_each_path_exactly_once(self):
        """Zero-recompile streaming: across many microbatches (exact seed +
        fused steady state + a forced fallback) the exact step and the
        fused step each compile exactly once — the carried theta is an
        array operand, never a static."""
        frames = jnp.concatenate([
            jax.random.uniform(jax.random.PRNGKey(9), (4, 32, 32, 3)),
            0.05 * jax.random.uniform(jax.random.PRNGKey(10),
                                      (2, 32, 32, 3)),
        ])
        eng, _, _ = _vis_engine(microbatch=2, fused_stream=True,
                                fused_theta_tol=0.05)
        with tracecheck.capture() as rec:
            list(eng.stream([frames, frames]))
        assert eng.fused_step_count >= 2
        assert eng.fused_fallback_count >= 1
        tracecheck.assert_jit_cache(eng._step, 1, recorder=rec,
                                    what="eng._step")
        tracecheck.assert_jit_cache(eng._fused_step, 1, recorder=rec,
                                    what="eng._fused_step")
