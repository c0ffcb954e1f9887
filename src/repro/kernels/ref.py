"""Pure-jnp oracles for every Pallas kernel (the allclose reference)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import mtj as mtj_model
from repro.core import pixel as pixel_model


# ---------------------------------------------------------------------------
# p2m_conv oracle: fused in-pixel conv -> curve -> subtract -> MTJ majority
# ---------------------------------------------------------------------------

# single-sourced in core/mtj.py; re-exported because tests/benchmarks import
# the oracle's majority fold from here
majority_prob_poly = mtj_model.majority_prob_poly


def p2m_conv_ref(patches: jax.Array, w: jax.Array, theta: jax.Array,
                 bits: jax.Array, *,
                 pixel_params: pixel_model.PixelCircuitParams =
                 pixel_model.DEFAULT_PIXEL,
                 mtj_params: mtj_model.MTJParams = mtj_model.DEFAULT_MTJ
                 ) -> jax.Array:
    """Oracle for the fused P2M kernel — the core ``device`` reference.

    patches: (N, K) im2col rows; w: (K, C) signed quantized weights;
    theta: () algorithmic threshold (Hoyer extremum x v_th, in conv units);
    bits: (N, C) ``mtj.DRAW_BITS_DTYPE`` random words (one Bernoulli draw;
    the n-MTJ majority is folded into the probability — distributionally
    identical). Returns float {0,1} activations (N, C).

    Calls the *same* ``core/pixel.py`` / ``core/mtj.py`` functions the Pallas
    kernel traces, so kernel-vs-ref parity is bit-exact at the operand
    level. NOTE (DESIGN.md §9): the implicit-im2col kernel's matmul is not
    *operand-identical* to this oracle's (in-kernel gather vs materialized
    patches), so u may differ by an ulp — an end-to-end activation
    comparison should therefore allow mismatches that sit within one
    uint16 word of the draw threshold (``p2m_conv_ref_q`` exposes q for
    exactly that check; given the same q the draw itself is bit-exact).
    """
    return mtj_model.bernoulli_from_bits(
        bits, p2m_conv_ref_q(patches, w, theta, pixel_params=pixel_params,
                             mtj_params=mtj_params))


def p2m_conv_ref_q(patches: jax.Array, w: jax.Array, theta: jax.Array, *,
                   pixel_params: pixel_model.PixelCircuitParams =
                   pixel_model.DEFAULT_PIXEL,
                   mtj_params: mtj_model.MTJParams = mtj_model.DEFAULT_MTJ
                   ) -> jax.Array:
    """The fused oracle's folded-majority activation probability (N, C) —
    everything in ``p2m_conv_ref`` up to (but not including) the draw."""
    mac_pos = jnp.dot(patches, jnp.maximum(w, 0.0),
                      preferred_element_type=jnp.float32)
    mac_neg = jnp.dot(patches, jnp.maximum(-w, 0.0),
                      preferred_element_type=jnp.float32)
    g = pixel_model.get_curve(pixel_params.curve, pixel_params)
    u = g(mac_pos) - g(mac_neg)
    v = pixel_model.conv_voltage(u, theta, pixel_params)
    p_sw = mtj_model.switching_probability(
        v, mtj_params.write_pulse_ps, mtj_params)
    return mtj_model.majority_prob_poly(
        p_sw, mtj_params.n_redundant, mtj_params.majority)


def draw_mismatches(acts, q_ref, bits) -> tuple:
    """``(flips, off_boundary)``: how many kernel activations differ from
    the oracle's draw, and how many of those differences do NOT sit within
    one uint16 word of the draw threshold.

    acts (N, C) float {0,1} from a kernel pipeline; q_ref (N, C) the
    oracle's folded activation probability (``p2m_conv_ref_q``); bits the
    (N, C) draw words both sides consumed. Given the same q the draw is
    bit-exact, and the implicit-im2col gather makes u differ from the
    oracle's by ulps at most, so the only legitimate mismatch is a q pushed
    across a word boundary: flips must be rare and ``off_boundary`` zero.
    """
    expected = np.asarray(mtj_model.bernoulli_from_bits(bits, q_ref))
    mismatch = np.asarray(acts) != expected
    boundary = np.abs(np.asarray(q_ref, np.float64) * 65536.0
                      - np.asarray(bits, np.float64)) <= 1.0
    return int(mismatch.sum()), int((mismatch & ~boundary).sum())


# ---------------------------------------------------------------------------
# single-pass pipeline oracles: kernel A (matmul + Hoyer partials) and
# kernel B (cached u -> voltage -> draw + masked V_CONV partials)
# ---------------------------------------------------------------------------

def _block_rows(x: jax.Array, block_n: int) -> jax.Array:
    n = x.shape[0]
    return x.reshape(n // block_n, block_n, *x.shape[1:])


def p2m_phase_a_ref(patches: jax.Array, w: jax.Array, v_th: jax.Array, *,
                    pixel_params: pixel_model.PixelCircuitParams =
                    pixel_model.DEFAULT_PIXEL,
                    block_n: int = 256):
    """Oracle for kernel A: the single patch matmul.

    Returns ``(u, hoyer_partials)`` exactly as ``p2m_phase_a_pallas`` does —
    the pre-activation (N, C) plus per-block (sum |z_clip|, sum z_clip^2)
    rows (N/block_n, STAT_LANES), reduced block-by-block in the same order so
    interpret-mode parity is bit-exact.
    """
    from repro.core import hoyer
    from repro.kernels import p2m_conv as k

    mac_pos = jnp.dot(patches, jnp.maximum(w, 0.0),
                      preferred_element_type=jnp.float32)
    mac_neg = jnp.dot(patches, jnp.maximum(-w, 0.0),
                      preferred_element_type=jnp.float32)
    g = pixel_model.get_curve(pixel_params.curve, pixel_params)
    u = g(mac_pos) - g(mac_neg)
    zc = hoyer.clip01(u / jnp.maximum(v_th, 1e-6))
    zb = _block_rows(zc, block_n)
    lane = jnp.arange(k.STAT_LANES)
    partials = (
        jnp.where(lane == k.LANE_ABS,
                  jnp.sum(jnp.abs(zb), axis=(1, 2))[:, None], 0.0)
        + jnp.where(lane == k.LANE_SQ,
                    jnp.sum(jnp.square(zb), axis=(1, 2))[:, None], 0.0))
    return u, partials


def _device_chain_q(u: jax.Array, theta: jax.Array,
                    chan: jax.Array | None,
                    pixel_params: pixel_model.PixelCircuitParams,
                    mtj_params: mtj_model.MTJParams):
    """(u, theta, variation operand) -> ``(q, v)``: the folded-majority
    activation probability and the subtractor voltage map.

    Mirrors the kernels' ``_device_epilogue`` expression-for-expression,
    including the widened (CHAN_ROWS, N_pix, C) per-spatial-pixel operand
    (u rows reshape frame-major onto the pixel axis and broadcast).
    """
    from repro.variation import chip as chip_mod

    if chan is None:
        chan = chip_mod.identity_operands(u.shape[1])
    chan = jnp.asarray(chan, jnp.float32)
    flat_shape = None
    if chan.ndim == 3:
        flat_shape = u.shape
        u = u.reshape(-1, chan.shape[1], chan.shape[2])
    u = u * chan[chip_mod.CHAN_U_GAIN] + chan[chip_mod.CHAN_U_OFFSET]
    v = pixel_model.conv_voltage(u, theta, pixel_params)
    p_sw = mtj_model.switching_probability(
        v, mtj_params.write_pulse_ps, mtj_params,
        logit_offset=chan[chip_mod.CHAN_LOGIT_OFFSET],
        logit_gain=chan[chip_mod.CHAN_LOGIT_GAIN])
    q = mtj_model.majority_prob_poly(
        p_sw, mtj_params.n_redundant, mtj_params.majority)
    if flat_shape is not None:
        q = q.reshape(flat_shape)
        v = v.reshape(flat_shape)
    return q, v


def p2m_phase_b_ref(u: jax.Array, theta: jax.Array, bits: jax.Array, *,
                    n_valid: int, c_valid: int,
                    chan: jax.Array | None = None,
                    pixel_params: pixel_model.PixelCircuitParams =
                    pixel_model.DEFAULT_PIXEL,
                    mtj_params: mtj_model.MTJParams = mtj_model.DEFAULT_MTJ,
                    block_n: int = 1024):
    """Oracle for kernel B: cached u through the device chain.

    Returns ``(activations, v_conv_partials)`` as ``p2m_phase_b_pallas``
    does: float {0,1} (N, C) plus per-block masked (sum, min, max) of the
    subtractor voltage (N/block_n, STAT_LANES). ``chan`` is the same
    (CHAN_ROWS, C) per-channel — or (CHAN_ROWS, N_pix, C) per-spatial-pixel
    — variation operand the kernel consumes; identical expressions in
    identical order, so parity stays bit-exact for non-default maps too.
    For a 3-D ``chan``, pass the kernel's CLAMPED block size (the kernel
    rounds ``block_n`` down to whole frames of the pixel map).
    """
    from repro.kernels import p2m_conv as k

    q, v = _device_chain_q(u, theta, chan, pixel_params, mtj_params)
    draw = mtj_model.bernoulli_from_bits(bits, q)

    n, c = u.shape
    valid = ((jnp.arange(n)[:, None] < n_valid)
             & (jnp.arange(c)[None, :] < c_valid))
    vb = _block_rows(v, block_n)
    mb = _block_rows(valid, block_n)
    lane = jnp.arange(k.STAT_LANES)
    partials = (
        jnp.where(lane == k.LANE_VSUM,
                  jnp.sum(jnp.where(mb, vb, 0.0), axis=(1, 2))[:, None], 0.0)
        + jnp.where(lane == k.LANE_VMIN,
                    jnp.min(jnp.where(mb, vb, jnp.inf),
                            axis=(1, 2))[:, None], 0.0)
        + jnp.where(lane == k.LANE_VMAX,
                    jnp.max(jnp.where(mb, vb, -jnp.inf),
                            axis=(1, 2))[:, None], 0.0))
    return draw.astype(jnp.float32), partials


# ---------------------------------------------------------------------------
# int8 quantized-path oracles (DESIGN.md §14)
# ---------------------------------------------------------------------------

def q8_mac_ref(patches: jax.Array, wq_packed: jax.Array,
               dequant_row: jax.Array) -> jax.Array:
    """The quantized packed MAC in plain f32: quantize -> dot -> dequant.

    The int8 operands are integer-valued, every product is < 2^14, and the
    contraction depth keeps partial sums < 2^24, so the ACCUMULATOR of this
    f32 GEMM is exact — bit-identical to the kernel's s8 x s8 dot under any
    accumulation order or dtype (core/p2m.py, property-tested). The
    subsequent dequant multiply is NOT order-pinned, however: XLA may fold
    the per-column scale into a GEMM operand (``dot(x, w * s)`` vs
    ``dot(x, w) * s``), which reassociates the non-power-of-two scale and
    moves u by an ulp — so end-to-end q8 kernel-vs-oracle comparisons go
    through the draw-boundary machinery like the f32 path, EXCEPT when every
    scale is a power of two (then both orders are exact and parity is
    bit-for-bit; tests/test_quantized.py constructs exactly that).
    """
    from repro.core import p2m as p2m_core
    xq = p2m_core.quantize_acts_q8(patches).astype(jnp.float32)
    # the oracle INTENTIONALLY accumulates the integer-valued operands in
    # f32 (exact; see docstring)
    a = jnp.dot(xq, wq_packed.astype(jnp.float32),  # analysis: waive=q8-f32-dot
                preferred_element_type=jnp.float32)
    return a * jnp.asarray(dequant_row, jnp.float32)


def p2m_phase_a_q8_ref(patches: jax.Array, wq_packed: jax.Array,
                       dequant_row: jax.Array, v_th: jax.Array, *,
                       pixel_params: pixel_model.PixelCircuitParams =
                       pixel_model.DEFAULT_PIXEL,
                       block_n: int = 256):
    """Oracle for the quantized kernel A: ``(u, hoyer_partials)``.

    ``wq_packed`` (K, 2C) int8 + ``dequant_row`` (1, 2C) come from
    ``core.p2m.quantize_packed_weights`` / ``packed_dequant_row`` over the
    packed relu-split weights; activations quantize onto the 1/128 grid
    exactly as the kernel does in VMEM.
    """
    from repro.core import hoyer
    from repro.kernels import p2m_conv as k

    a = q8_mac_ref(patches, wq_packed, dequant_row)
    c_out = wq_packed.shape[1] // 2
    g = pixel_model.get_curve(pixel_params.curve, pixel_params)
    u = g(a[:, :c_out]) - g(a[:, c_out:])
    zc = hoyer.clip01(u / jnp.maximum(v_th, 1e-6))
    zb = _block_rows(zc, block_n)
    lane = jnp.arange(k.STAT_LANES)
    partials = (
        jnp.where(lane == k.LANE_ABS,
                  jnp.sum(jnp.abs(zb), axis=(1, 2))[:, None], 0.0)
        + jnp.where(lane == k.LANE_SQ,
                    jnp.sum(jnp.square(zb), axis=(1, 2))[:, None], 0.0))
    return u, partials


def p2m_conv_ref_q8_q(patches: jax.Array, wq_packed: jax.Array,
                      dequant_row: jax.Array, theta: jax.Array, *,
                      chan: jax.Array | None = None,
                      pixel_params: pixel_model.PixelCircuitParams =
                      pixel_model.DEFAULT_PIXEL,
                      mtj_params: mtj_model.MTJParams = mtj_model.DEFAULT_MTJ
                      ) -> jax.Array:
    """Folded-majority activation probability q of the FULL quantized chain
    (quantized MAC -> curve/subtract -> voltage -> switching -> majority).

    The q the draw thresholds against — ``tests/draw_asserts.py`` compares
    a quantized run's activations to the f32 oracle through this q to
    verify that flips are rare AND sit on uint16 draw-word boundaries.
    """
    a = q8_mac_ref(patches, wq_packed, dequant_row)
    c_out = wq_packed.shape[1] // 2
    g = pixel_model.get_curve(pixel_params.curve, pixel_params)
    u = g(a[:, :c_out]) - g(a[:, c_out:])
    q, _ = _device_chain_q(u, theta, chan, pixel_params, mtj_params)
    return q


# ---------------------------------------------------------------------------
# flash attention oracle
# ---------------------------------------------------------------------------

def flash_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True) -> jax.Array:
    """q,k,v: (B, S, H, D) (no GQA in the kernel API — callers expand)."""
    b, s, h, d = q.shape
    sc = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                    k.astype(jnp.float32)) * d ** -0.5
    if causal:
        mask = jnp.tril(jnp.ones((s, s), bool))
        sc = jnp.where(mask[None, None], sc, -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)
