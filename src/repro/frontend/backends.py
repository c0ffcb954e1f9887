"""The four standard SensorFrontend backends (DESIGN.md §2).

All four consume the same ``P2MConfig`` (pixel circuit + MTJ device params)
and produce the same ``(activations, aux)`` contract; they differ only in
which physical effects they model:

  ideal    linear conv (no circuit curve) + Hoyer spike — the algorithmic
           upper bound used for ablations.
  analog   train-time path: two-phase circuit-curve conv + Hoyer spike with
           straight-through gradients, optional Fig. 8 stochastic-switching
           noise injection. Differentiable end to end.
  device   hardware-eval path: Monte-Carlo per-MTJ Bernoulli switching at
           the threshold-matched V_CONV, n-device majority vote (Fig. 5).
  pallas   the single-pass two-kernel TPU pipeline (kernels/p2m_conv.py) —
           same math as ``device`` with the majority vote folded into one
           Bernoulli draw (distributionally identical; bit-exact vs
           kernels/ref.py). The patch matmul runs exactly once; the Hoyer
           threshold and V_CONV stats come from in-kernel partial
           reductions, not a shadow conv pass.

``hoyer_loss`` in aux is the RAW regularizer value — consumers scale by
``hoyer_coeff`` exactly once (see models/vision.py).

Device variation (DESIGN.md §7): ``cfg.variation`` + ``cfg.chip_id`` select
a sampled chip instance; ``device`` runs it exactly per-device, ``pallas``
folds it into kernel B's per-channel operand rows, ``analog`` draws its
Fig. 8 flips from the chip's error maps. A programmed calibration trim
travels as ``params["cal_trim"]`` (variation/calibrate.py).

Lifetime (DESIGN.md §8): a chip that *ages* cannot be a jit static — so a
``ChipMaps`` pytree riding in ``params["chip"]`` overrides the
config-sampled chip as a plain ARRAY OPERAND. ``repro.serving.VisionEngine``
evolves the maps per microbatch (lifetime/drift.py) and injects them here;
because only array values change, the jitted step compiles exactly once for
the whole life of the sensor. The ``ideal`` backend models no device at all
and ignores the override (it is the algorithmic upper bound).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import hoyer, mtj, p2m, pixel
from repro.frontend.api import FrontendConfig, register_backend
from repro.variation import chip as chip_mod


def _theta(u: jax.Array, v_th: jax.Array) -> jax.Array:
    """Hardware-mapped algorithmic threshold, in conv-output units."""
    return hoyer.effective_threshold(u, v_th) * v_th


def _v_conv_stats(v: jax.Array) -> Dict:
    """Statistics of the subtractor voltage driving the VC-MTJ (paper Fig. 4b).

    Takes the voltage map itself so every backend — including ``device``,
    which already has V_CONV in hand (possibly chip-perturbed) — reduces
    through this ONE implementation instead of re-deriving the stats inline.
    """
    return {"v_conv_mean": jnp.mean(v), "v_conv_min": jnp.min(v),
            "v_conv_max": jnp.max(v)}


def _sampled_chip(cfg: FrontendConfig) -> Optional[chip_mod.ChipMaps]:
    """The chip this frontend simulates, or None for the nominal device.

    An all-sigma-zero profile is treated as no variation at all (it samples
    exact identity maps anyway) so the nominal paths stay byte-for-byte the
    pre-subsystem code — including the analog backend, which would otherwise
    start drawing the nominal chip's tiny-but-nonzero Fig. 5 error flips.
    """
    if cfg.variation is None or not cfg.variation.enabled:
        return None
    return chip_mod.sample_chip(cfg.variation, cfg.p2m.out_channels,
                                cfg.p2m.mtj.n_redundant, cfg.chip_id)


def _resolve_chip(cfg: FrontendConfig,
                  params: dict) -> Optional[chip_mod.ChipMaps]:
    """The chip this call simulates: ``params["chip"]`` wins over config.

    The config-sampled chip is frozen at fabrication time (a jit static);
    ``params["chip"]`` is the runtime override the lifetime subsystem uses
    to thread an *aged* ``ChipMaps`` pytree through as array operands
    (DESIGN.md §8) — the config-sampled instance is its t = 0 base.
    """
    chip = params.get("chip")
    if chip is not None:
        return (chip if isinstance(chip, chip_mod.ChipMaps)
                else chip_mod.ChipMaps(*chip))
    return _sampled_chip(cfg)


def _ste_flip(o: jax.Array, key: jax.Array, p_fail, p_false) -> jax.Array:
    """Fig. 8 bit flips with a straight-through gradient (scalar or mapped
    probabilities — arrays broadcast against the activation map)."""
    k1, k2 = jax.random.split(key)
    fail = jax.random.bernoulli(k1, p_fail, o.shape)
    false = jax.random.bernoulli(k2, p_false, o.shape)
    noisy = jnp.where(o > 0.5, 1.0 - fail.astype(o.dtype),
                      false.astype(o.dtype))
    return o + jax.lax.stop_gradient(noisy - o)   # STE through the flips


@register_backend("ideal", differentiable=True)
def ideal_backend(cfg: FrontendConfig, params: dict, images: jax.Array,
                  key: Optional[jax.Array]) -> Tuple[jax.Array, Dict]:
    """Ideal (no circuit curve, deterministic) reference for ablations."""
    pcfg = cfg.p2m
    wq = p2m.quantize_weights(params["w"], pcfg.weight_bits)
    u = p2m.phase_conv(images, wq, pcfg.stride)
    o, hl = hoyer.hoyer_spike(u, params["v_th"])
    theta = _theta(u, params["v_th"])
    aux = {"hoyer_loss": hl, "theta": theta,
           **_v_conv_stats(pixel.conv_voltage(u, theta, pcfg.pixel))}
    return o, aux


@register_backend("analog", differentiable=True)
def analog_backend(cfg: FrontendConfig, params: dict, images: jax.Array,
                   key: Optional[jax.Array]) -> Tuple[jax.Array, Dict]:
    """Training path: circuit-curve conv + Hoyer spike + STE.

    If cfg.p2m.noise_p_fail / noise_p_false are set (Fig. 8 robustness study)
    and a key is given, activation bits are flipped with those probabilities
    via a straight-through perturbation. With ``cfg.variation`` set the flip
    probabilities come from the sampled chip instead — per-channel
    (fail, false) maps derived from each channel's heterogeneous majority
    error at the Fig. 5 operating points (spatial mismatch structure, not
    i.i.d. scalars), so variation-aware training sees the same chip the
    hardware backends simulate. A ``params["chip"]`` override (the aged
    chip of the lifetime subsystem) supplies those maps the same way.
    """
    pcfg = cfg.p2m
    chip = _resolve_chip(cfg, params)
    u = p2m.hardware_conv(images, params["w"], pcfg)
    o, hl = hoyer.hoyer_spike(u, params["v_th"])
    if key is not None and chip is not None:
        # per-channel (C,) chip maps broadcast over the activation's channel
        # axis; any CONFIGURED scalar Fig. 8 noise still applies — the two
        # are independent flip sources, combined as 1 - (1-a)(1-b) (a
        # variation profile must not silently cancel an explicit noise study)
        p_fail, p_false = chip_mod.noise_maps(chip, pcfg.mtj, pcfg.pixel)
        p_fail = 1.0 - (1.0 - p_fail) * (1.0 - pcfg.noise_p_fail)
        p_false = 1.0 - (1.0 - p_false) * (1.0 - pcfg.noise_p_false)
        o = _ste_flip(o, key, p_fail, p_false)
    elif key is not None and (pcfg.noise_p_fail > 0
                              or pcfg.noise_p_false > 0):
        o = _ste_flip(o, key, pcfg.noise_p_fail, pcfg.noise_p_false)
    theta = _theta(u, params["v_th"])
    aux = {"hoyer_loss": hl, "theta": theta,
           **_v_conv_stats(pixel.conv_voltage(u, theta, pcfg.pixel))}
    return o, aux


@register_backend("device", stateful=True)
def device_backend(cfg: FrontendConfig, params: dict, images: jax.Array,
                   key: Optional[jax.Array]) -> Tuple[jax.Array, Dict]:
    """Hardware-eval path: full Monte-Carlo device simulation.

    conv -> threshold-matching voltage -> per-MTJ stochastic switching
    (switching_probability at the applied V_CONV) x n_redundant -> majority.

    With ``cfg.variation`` set (or a programmed ``params["cal_trim"]``) the
    chain runs at the sampled chip's corners: pixel gain/offset (+ trim) on
    u, then each of the n redundant MTJs switches at its OWN logit corner
    and the majority is taken over the heterogeneous draws — the exact
    per-device reference the channel-aggregated pallas kernel approximates.
    theta stays derived from the unperturbed u (the algorithmic threshold is
    digital — kernel A's semantics).
    """
    if key is None:
        raise ValueError("the 'device' backend is stochastic — pass key=")
    pcfg = cfg.p2m
    chip = _resolve_chip(cfg, params)
    trim = params.get("cal_trim")
    u = p2m.hardware_conv(images, params["w"], pcfg)
    theta = _theta(u, params["v_th"])
    if chip is None and trim is None:
        v_conv = pixel.conv_voltage(u, theta, pcfg.pixel)
        p_sw = mtj.switching_probability(v_conv, pcfg.mtj.write_pulse_ps,
                                         pcfg.mtj)
        o = mtj.sample_majority_activation(
            key, p_sw, pcfg.mtj.n_redundant, pcfg.mtj.majority)
    else:
        if chip is None:
            chip = chip_mod.identity_chip(pcfg.out_channels,
                                          pcfg.mtj.n_redundant)
        v_conv, p_dev = chip_mod.device_chain(u, theta, chip, trim,
                                              pcfg.pixel, pcfg.mtj)
        o = mtj.sample_majority_activation_per_device(
            key, p_dev, pcfg.mtj.majority)
    aux = {"hoyer_loss": jnp.zeros(()), "theta": theta,
           **_v_conv_stats(v_conv)}
    return o, aux


@register_backend("pallas", stateful=True)
def pallas_backend(cfg: FrontendConfig, params: dict, images: jax.Array,
                   key: Optional[jax.Array]) -> Tuple[jax.Array, Dict]:
    """Single-pass Pallas TPU kernel pipeline (compiled on a TPU,
    interpreted elsewhere — ``repro.platform.pallas_interpret``).

    The patch matmul runs exactly once, in kernel A, which also emits the
    per-block partial reductions for the *global* Hoyer threshold; a scalar
    host combine produces theta; kernel B consumes the cached pre-activation
    through voltage map -> switching probability -> folded majority draw and
    emits the V_CONV partials (DESIGN.md §5). No shadow pure-JAX conv, no
    duplicate weight quantization — every aux stat comes out of the kernels.
    """
    if key is None:
        raise ValueError("the 'pallas' backend is stochastic — pass key=")
    from repro.kernels import ops   # deferred: keep core import-light
    pcfg = cfg.p2m
    chip = _resolve_chip(cfg, params)
    trim = params.get("cal_trim")
    chan = None
    if chip is not None or trim is not None:
        if chip is None:
            chip = chip_mod.identity_chip(pcfg.out_channels,
                                          pcfg.mtj.n_redundant)
        # fold the chip (+ programmed trim) into kernel B's per-channel
        # operand rows — the variation-aware kernel costs two fused
        # multiply-adds, nothing else changes (DESIGN.md §7)
        chan = chip_mod.channel_operands(chip, trim)
    wq = p2m.quantize_weights(params["w"], pcfg.weight_bits)
    kw = dict(kernel=pcfg.kernel_size, stride=pcfg.stride, chan=chan,
              pixel_params=pcfg.pixel, mtj_params=pcfg.mtj,
              block_n=cfg.block_n,
              block_n_elem=cfg.block_n_elem, precision=cfg.precision)
    carry = params.get("theta_carry")
    if carry is not None:
        # fused streaming step (DESIGN.md §9): one kernel, the draws run at
        # the CARRIED threshold riding in params (an array operand — the
        # streaming engine injects a fresh EMA every microbatch against ONE
        # compilation). aux still carries the FRESH theta for the engine's
        # drift guard. Only VisionEngine.stream() plants this key; every
        # other call path takes the exact two-kernel pipeline below,
        # bit-identical to the non-streaming contract.
        o, kernel_aux = ops.p2m_frontend_fused(
            images, wq, params["v_th"], carry, key,
            on_device_rng=cfg.on_device_rng, **kw)
    else:
        o, kernel_aux = ops.p2m_frontend(
            images, wq, params["v_th"], key, **kw)
    return o, {"hoyer_loss": jnp.zeros(()), **kernel_aux}
