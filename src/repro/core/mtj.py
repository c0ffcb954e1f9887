"""VC-MTJ device model (paper §2.1, Figs. 1-2, 5).

Models the fabricated 70 nm voltage-controlled MTJ used as the binary
thresholding neuron + non-volatile global-shutter memory:

* ``switching_probability(V, pulse_ps)`` — precessional VCMA switching
  probability. The voltage dependence is a monotone piecewise-linear fit *in
  logit space* through the paper's three measured AP->P points at 700 ps
  (P_sw = 6.2% @ 0.7 V, 92.4% @ 0.8 V, 97.17% @ 0.9 V); the pulse-width
  dependence is a sin^2 precession envelope peaking at half the precession
  period (700 ps for AP->P, 500 ps for the 0.9 V P->AP reset pulse, Fig. 2).
* multi-MTJ redundancy (8 devices / kernel) + majority vote, both analytic
  (binomial tail) and Monte-Carlo (for the hardware-eval path), reproducing
  Fig. 5's < 0.1% activation error.
* resistance model (R_P / R_AP, TMR > 150%) for the burst-read comparator.

Everything is pure JAX and differentiable where it needs to be (probabilities
feed straight-through estimators in ``core/p2m.py``).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

# --- measured device points (paper §2.2.3 / Fig. 5 caption) -----------------
MEASURED_VOLTAGES = (0.70, 0.80, 0.90)          # volts, 700 ps AP->P pulses
MEASURED_P_SW = (0.062, 0.924, 0.9717)          # switching probabilities


def _logit(p: float) -> float:
    return float(np.log(p / (1.0 - p)))


@dataclasses.dataclass(frozen=True)
class MTJParams:
    """Device parameters for the fabricated VC-MTJ stack.

    The measured switching points live here (not as free-floating module
    constants) so that every consumer — the core device model, the pure-jnp
    kernel oracle, and the fused Pallas kernel — derives the logit fit from
    one source (DESIGN.md §3).
    """
    r_p: float = 4.0e3            # ohms, parallel state
    tmr: float = 1.55             # (R_AP - R_P)/R_P > 150% near zero bias
    diameter_nm: float = 70.0
    write_pulse_ps: float = 700.0  # AP->P activation pulse (paper)
    reset_pulse_ps: float = 500.0  # P->AP reset pulse @ 0.9 V (paper)
    reset_voltage: float = 0.9
    precession_period_ps: float = 1400.0   # write envelope peak @ 700 ps
    reset_precession_period_ps: float = 1000.0  # reset envelope peak @ 500 ps
    read_voltage: float = 0.1     # |V| well below disturb threshold
    n_redundant: int = 8          # MTJs per kernel (paper uses 8)
    measured_voltages: Tuple[float, ...] = MEASURED_VOLTAGES
    measured_p_sw: Tuple[float, ...] = MEASURED_P_SW

    @property
    def r_ap(self) -> float:
        return self.r_p * (1.0 + self.tmr)

    @property
    def majority(self) -> int:
        """Votes needed to activate — majority of n_redundant."""
        return self.n_redundant // 2

    @property
    def measured_logits(self) -> Tuple[float, ...]:
        return tuple(_logit(p) for p in self.measured_p_sw)


DEFAULT_MTJ = MTJParams()


def switching_logit(voltage: jax.Array,
                    params: MTJParams = DEFAULT_MTJ,
                    *,
                    logit_offset: jax.Array | float = 0.0,
                    logit_gain: jax.Array | float = 1.0) -> jax.Array:
    """Monotone logit(P_sw) vs applied voltage, 700 ps pulse, AP->P.

    Piecewise-linear in logit space through the three measured points, with
    end-segment extrapolation. Written in closed form (where/arithmetic only,
    no gather) so the exact same function traces inside the Pallas kernel.

    ``logit_offset`` / ``logit_gain`` are the device-variation hooks
    (repro/variation): per-device or per-channel arrays broadcast against the
    voltage map perturb the fit as ``gain * logit + offset`` — an additive
    VCMA-coefficient offset and a multiplicative slope spread — without
    forking the physics. The defaults (0, 1) are bit-exact no-ops.
    """
    v = jnp.asarray(voltage)
    (v0, v1, v2) = params.measured_voltages
    (l0, l1, l2) = params.measured_logits
    slope_lo = (l1 - l0) / (v1 - v0)
    slope_hi = (l2 - l1) / (v2 - v1)
    # the low line covers v < v1 (including the extrapolation below v0);
    # the high line covers v >= v1 (including the extrapolation above v2)
    lo = l0 + slope_lo * (v - v0)
    hi = l1 + slope_hi * (v - v1)
    return logit_gain * jnp.where(v < v1, lo, hi) + logit_offset


def pulse_envelope(pulse_ps: jax.Array, period_ps: float) -> jax.Array:
    """Precessional sin^2 envelope: peak switching at odd half-periods."""
    return jnp.sin(jnp.pi * jnp.asarray(pulse_ps) / period_ps) ** 2


def switching_probability(
    voltage: jax.Array,
    pulse_ps: float | jax.Array = 700.0,
    params: MTJParams = DEFAULT_MTJ,
    *,
    logit_offset: jax.Array | float = 0.0,
    logit_gain: jax.Array | float = 1.0,
) -> jax.Array:
    """P(AP->P switch) for a voltage pulse of given width.

    Exactly reproduces the three measured points at 700 ps.
    ``logit_offset`` / ``logit_gain`` forward to ``switching_logit`` — the
    device-variation perturbation hooks (defaults are bit-exact no-ops).
    """
    p_v = jax.nn.sigmoid(switching_logit(voltage, params,
                                         logit_offset=logit_offset,
                                         logit_gain=logit_gain))
    env = pulse_envelope(pulse_ps, params.precession_period_ps)
    # normalise so the envelope is 1 at the nominal write pulse
    env_ref = pulse_envelope(params.write_pulse_ps, params.precession_period_ps)
    return p_v * jnp.clip(env / env_ref, 0.0, 1.0)


def reset_probability(params: MTJParams = DEFAULT_MTJ) -> jax.Array:
    """P(P->AP reset) at the nominal 0.9 V / 500 ps reset pulse."""
    p_v = jax.nn.sigmoid(switching_logit(jnp.asarray(params.reset_voltage), params))
    return p_v  # envelope is at its peak for the reset pulse by construction


# --- folded Bernoulli draw (kernels + oracles) ------------------------------

# dtype of the pre-generated uniform words feeding the folded majority draw.
# 16 bits per draw: the probability is quantized to 1/65536 (bias <= 1.5e-5,
# far below the Monte-Carlo noise of any statistic this repo reports, and
# far more entropy than a physical in-sensor RNG would budget per pixel),
# and generating half the random words halves the dominant rng cost of the
# pallas serving step (threefry is ~0.2 ms per 131k uint32 words on the
# interpret-mode CPU target — DESIGN.md §9).
DRAW_BITS_DTYPE = jnp.uint16
_DRAW_SCALE = 1.0 / 2 ** 16


def bernoulli_from_bits(bits: jax.Array, q: jax.Array) -> jax.Array:
    """One Bernoulli(q) draw per element from pre-generated uniform words.

    ``bits`` is ``DRAW_BITS_DTYPE``; the draw fires when the word, mapped to
    [0, 1), falls below q. The SINGLE source of the draw expression for the
    Pallas kernels, their oracles (kernels/ref.py), and the legacy baseline
    — kernel<->oracle bit-parity rests on all of them tracing this one
    function. Returns float {0,1}. The word widens through int32 (exact)
    because the TPU kernel compiler has no unsigned-to-float conversion.
    """
    u = bits.astype(jnp.int32).astype(jnp.float32)
    return ((u * _DRAW_SCALE) < q).astype(jnp.float32)


# --- multi-MTJ majority statistics (Fig. 5) ---------------------------------

def _binom_pmf(k: jax.Array, n: int, p: jax.Array) -> jax.Array:
    log_c = (
        jax.scipy.special.gammaln(n + 1.0)
        - jax.scipy.special.gammaln(k + 1.0)
        - jax.scipy.special.gammaln(n - k + 1.0)
    )
    eps = jnp.finfo(jnp.result_type(p, jnp.float32)).eps
    pc = jnp.clip(p, eps, 1.0 - eps)       # avoid 0*inf NaNs at the edges
    return jnp.exp(log_c + k * jnp.log(pc) + (n - k) * jnp.log1p(-pc))


def majority_prob_poly(p: jax.Array, n: int = 8, m: int = 4) -> jax.Array:
    """P(Binomial(n, p) >= m) as an explicit polynomial.

    Algebraically identical to ``majority_activation_probability`` but uses
    only multiply/add (no gammaln, no log of p near 0/1), so it is safe to
    trace inside a Pallas kernel and exact at p in {0, 1}. This is the single
    source for the majority fold used by kernels/{ref,p2m_conv}.py.
    """
    out = jnp.zeros_like(p)
    for k in range(m, n + 1):
        out = out + math.comb(n, k) * (p ** k) * ((1 - p) ** (n - k))
    return out


def majority_activation_probability(
    p_single: jax.Array, n: int = 8, majority: int = 4
) -> jax.Array:
    """P(>= majority of n MTJs switch) given per-device P_sw.

    This is the effective activation probability of the redundant neuron.
    """
    ks = jnp.arange(majority, n + 1, dtype=jnp.float32)
    pmf = _binom_pmf(ks, n, jnp.asarray(p_single)[..., None])
    return jnp.sum(pmf, axis=-1)


def majority_prob_hetero(p_devices: jax.Array, majority: int) -> jax.Array:
    """P(>= majority of n *heterogeneous* devices switch) — Poisson binomial.

    ``p_devices`` carries the per-device probabilities on its LAST axis
    (..., n); unlike ``majority_prob_poly`` the devices need not share one
    P_sw, which is exactly the device-variation case (repro/variation): each
    of the n redundant MTJs in a kernel sits at its own process corner.

    Computed by a *batched pairwise tree* convolution of the per-device PMFs
    (multiply/add only — exact at p in {0, 1}): devices are padded to a
    power of two with phantom p = 0 devices (a delta at 0 — an exact no-op
    for the tail sum), then each level multiplies all polynomial pairs AT
    ONCE on a vectorized pair axis. Depth is ceil(log2 n) levels instead of
    the old scan-shaped DP's n sequential full-width steps — the DP made
    ``majority_prob_hetero`` the hot spot of the device/calibration paths
    (8 sequential (..., n+1)-wide multiply-adds per call at n = 8); the tree
    runs 3 batched levels. The legacy DP is retained as
    ``majority_prob_hetero_dp`` (benchmark baseline + property-test cross
    check). For identical devices both reduce to ``majority_prob_poly``
    (property-tested).
    """
    n = p_devices.shape[-1]
    dtype = jnp.result_type(p_devices, jnp.float32)
    p = jnp.asarray(p_devices, dtype)
    n2 = 1 << max(n - 1, 0).bit_length()          # next power of two
    if n2 > n:
        # phantom devices with p = 0: PMF is a delta at 0 successes, so the
        # padded Poisson binomial has the identical tail probabilities
        p = jnp.concatenate(
            [p, jnp.zeros(p.shape[:-1] + (n2 - n,), dtype)], axis=-1)
    # per-device degree-1 PMFs on a trailing coefficient axis: (..., n2, 2)
    pmf = jnp.stack([1.0 - p, p], axis=-1)
    m = n2
    while m > 1:
        half = m // 2
        a = pmf[..., :half, :]                    # (..., half, L)
        b = pmf[..., half:, :]
        length = a.shape[-1]
        out = jnp.zeros(a.shape[:-1] + (2 * length - 1,), dtype)
        # polynomial product of every pair at once; the short loop runs over
        # the (small, static) coefficient count, not over devices
        for i in range(length):
            out = out.at[..., i:i + length].add(a[..., i:i + 1] * b)
        pmf = out
        m = half
    pmf = pmf[..., 0, :]                          # (..., n2 + 1)
    return jnp.sum(pmf[..., majority:], axis=-1)


def majority_prob_hetero_dp(p_devices: jax.Array, majority: int) -> jax.Array:
    """The pre-vectorization scan-shaped DP (BENCHMARK/TEST-ONLY).

    n sequential full-width multiply-add steps over the (..., n+1) PMF —
    retained so ``benchmarks/frontend_bench.py`` can measure the tree
    rewrite against it and the property tests can cross-check both against
    ``majority_prob_poly``. Production callers use ``majority_prob_hetero``.
    """
    n = p_devices.shape[-1]
    pmf = jnp.zeros(p_devices.shape[:-1] + (n + 1,),
                    jnp.result_type(p_devices, jnp.float32))
    pmf = pmf.at[..., 0].set(1.0)
    for i in range(n):
        p = p_devices[..., i:i + 1]
        shifted = jnp.concatenate(
            [jnp.zeros_like(pmf[..., :1]), pmf[..., :-1]], axis=-1)
        pmf = pmf * (1.0 - p) + shifted * p
    return jnp.sum(pmf[..., majority:], axis=-1)


def majority_error_rates(
    p_should_switch: float | jax.Array,
    p_should_not: float | jax.Array,
    n: int = 8,
    majority: int = 4,
) -> Tuple[jax.Array, jax.Array]:
    """(fail-to-activate, false-activate) error rates of the majority neuron.

    Fig. 5: with the measured single-device probabilities these both fall
    below 0.1%.
    """
    fail = 1.0 - majority_activation_probability(p_should_switch, n, majority)
    false = majority_activation_probability(p_should_not, n, majority)
    return fail, false


def sample_majority_activation(
    key: jax.Array,
    p_single: jax.Array,
    n: int = 8,
    majority: int = 4,
) -> jax.Array:
    """Monte-Carlo hardware path: draw n Bernoulli switches, majority vote.

    p_single may have any shape; returns a float {0,1} array of that shape.
    """
    draws = jax.random.bernoulli(key, p_single[..., None], p_single.shape + (n,))
    votes = jnp.sum(draws.astype(jnp.int32), axis=-1)
    return (votes >= majority).astype(p_single.dtype)


def sample_majority_activation_per_device(
    key: jax.Array, p_devices: jax.Array, majority: int = 4
) -> jax.Array:
    """Monte-Carlo majority vote over *heterogeneous* devices.

    ``p_devices`` is (..., n) with the per-device switching probabilities on
    the last axis (the device-variation path — each redundant MTJ at its own
    corner). Returns a float {0,1} array of shape ``p_devices.shape[:-1]``.
    With ``p_devices = p_single[..., None]`` broadcast to (..., n) and the
    same key this is bit-identical to ``sample_majority_activation``.
    """
    draws = jax.random.bernoulli(key, p_devices, p_devices.shape)
    votes = jnp.sum(draws.astype(jnp.int32), axis=-1)
    return (votes >= majority).astype(p_devices.dtype)


# --- burst read (Fig. 6) -----------------------------------------------------

def read_voltage_divider(
    state_parallel: jax.Array, params: MTJParams = DEFAULT_MTJ,
    r_load: float = 6.0e3,
    *,
    r_p_scale: jax.Array | float = 1.0,
    tmr_scale: jax.Array | float = 1.0,
) -> jax.Array:
    """V_MTJ seen by the comparator for P / AP states (resistive divider).

    The > 150% TMR gives a wide sense margin; the comparator threshold is
    placed mid-way between the two levels. ``r_p_scale`` / ``tmr_scale`` are
    the device-variation hooks: relative per-device R_P and TMR spreads
    (arrays broadcast against the state map) perturb the divider levels —
    the yield-analysis read-margin model (repro/variation). Defaults (1, 1)
    are bit-exact no-ops.
    """
    r_p = params.r_p * r_p_scale
    r_ap = r_p * (1.0 + params.tmr * tmr_scale)
    r = jnp.where(state_parallel > 0.5, r_p, r_ap)
    return params.read_voltage * r_load / (r + r_load)


def comparator_threshold(params: MTJParams = DEFAULT_MTJ, r_load: float = 6.0e3) -> float:
    v_p = params.read_voltage * r_load / (params.r_p + r_load)
    v_ap = params.read_voltage * r_load / (params.r_ap + r_load)
    return float(0.5 * (v_p + v_ap))


def burst_read(states: jax.Array, params: MTJParams = DEFAULT_MTJ,
               r_load: float = 6.0e3) -> jax.Array:
    """Sequential burst read of MTJ states -> binary activations (Fig. 6).

    ``states`` is {0,1} (1 = parallel = activated). A parallel device pulls
    V_MTJ *above* the comparator threshold -> output spike. Disturb-free by
    VCMA polarity (read voltage raises the barrier).

    ``r_load`` is forwarded to BOTH the divider and the comparator threshold
    so the two can never disagree. (History: the divider used its default
    load while the threshold was computed independently — a caller-chosen
    r_load would have silently compared against the wrong mid-point.)
    """
    v = read_voltage_divider(states, params, r_load)
    return (v > comparator_threshold(params, r_load)).astype(jnp.float32)
