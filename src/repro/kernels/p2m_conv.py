"""The P2M in-pixel layer as an implicit-im2col, single-matmul Pallas pipeline.

TPU adaptation of the paper's analog pipeline (DESIGN.md §5/§9). The paper's
headline is that the first conv is computed *once*, in the sensor, at minimal
data movement — so the frontend must perform the patch matmul exactly once,
must not materialize the patch matrix in HBM, and must read each patch block
through the MXU exactly once. The production pipeline:

  kernel A (``p2m_phase_a_implicit_pallas``) — implicit im2col + ONE matmul:
      each grid step takes a blocked view of the SAME-padded image and
      gathers its own (block_n x k*k*C_in) patch rows in VMEM (one strided
      ref load per tap — the zero-padded-to-128-lanes HBM patch matrix of
      the old path never exists), then performs a single MXU matmul against
      the (K, 2C) *packed* relu-split weights [w+, w-]. The epilogue splits
      the two integration phases in-register, applies the per-phase circuit
      curve, and forms the passive-subtractor difference u, plus block-wise
      partial reductions for the *global* Hoyer threshold (sum |z_clip|,
      sum z_clip^2 with z = u / v_th).

  host combine (``combine_hoyer_partials``) — a tiny scalar reduction over
      the per-block partials: theta = (sum z^2 / sum |z|) * v_th.

  kernel B (``p2m_phase_b_pallas``) — consumes the cached ``u``:
      threshold-matching voltage map (``pixel.conv_voltage``)
        -> VC-MTJ switching probability (``mtj.switching_probability``)
        -> n-MTJ majority folded into a single Bernoulli draw
           (P(Binom(n,p) >= n//2) — distributionally identical, in-register)
        -> binary activation out,
      plus masked block-wise partials (sum / min / max) of the subtractor
      voltage, so the ``v_conv_*`` aux stats also come out of the kernel
      instead of a shadow pass (``combine_v_conv_partials``).

  fused streaming kernel (``p2m_fused_stream_pallas``) — kernels A and B in
      ONE pass for steady-state streaming: the kernel-B epilogue runs
      directly on the in-register u against a *carried* theta (the previous
      microbatch's EMA, supplied by ``serving.VisionEngine.stream()``),
      while the fresh Hoyer partials still come out so the caller can audit
      theta drift and fall back to the exact two-kernel path when the
      carried threshold has moved beyond tolerance (DESIGN.md §9). u never
      round-trips through HBM on this path.

Since kernel B is purely elementwise it runs with a much larger row block
than the MXU-tiled kernel A (``block_n_elem`` vs ``block_n``).

``p2m_phase_a_pallas`` — the explicit-patch kernel A (consumes a
pre-materialized (N, K) patch matrix) — is retained as the kernel-level
regression surface against ``ref.p2m_phase_a_ref`` and for operand-level
variation/lifetime tests; the frontend no longer calls it.

The kernels own NO physics constants: the circuit curve, voltage map, and
logit fit all trace in from ``core/pixel.py`` / ``core/mtj.py`` via the
``PixelCircuitParams`` / ``MTJParams`` dataclasses (DESIGN.md §3), so the
fused path can never drift from the reference device model.

Tiling: grids over patch-row blocks, each step capped by a VMEM budget
(``blocking.implicit_block`` / ``elem_rows_cap``). Zero padding is
invisible to the Hoyer partials (padded rows/cols give u = g(0) - g(0) = 0
exactly, contributing 0 to both sums); kernel B's voltage partials mask
padding explicitly because ``conv_voltage(0, theta) != 0``.
Partial-reduction outputs are one 128-lane row per block (stat lanes
selected by iota), stored as (steps, 1, W) arrays so each step's block
equals the array in its last two dims (``_row_out``).

Randomness enters as one ``mtj.DRAW_BITS_DTYPE`` (uint16) word per output
(generated by the caller — ``ops.draw_bits``; the draw expression itself is
single-sourced in ``mtj.bernoulli_from_bits``). The fused kernels can
instead draw on device (``pltpu.prng_random_bits``, compiled kernels only);
the streamed words keep interpret-mode validation bit-exact vs ref.py.

Execution mode: kernels compile on a TPU and run in interpret mode
elsewhere (``repro.platform.pallas_interpret``); ``interpret=`` pins it.

``p2m_conv_pallas`` — the pre-split fused single kernel — is retained as the
measured baseline for the double-conv fix (benchmarks/frontend_bench.py) and
for kernel-level regression tests; the frontend no longer calls it.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro import platform
from repro.core import hoyer
from repro.core import mtj as mtj_model
from repro.core import p2m as p2m_core
from repro.core import pixel as pixel_model
from repro.kernels import blocking
# the (4, C) per-channel variation operand layout is owned by the variation
# subsystem (repro/variation/chip.py defines the rows; no import cycle —
# chip.py depends only on core)
from repro.variation.chip import (CHAN_LOGIT_GAIN, CHAN_LOGIT_OFFSET,
                                  CHAN_ROWS, CHAN_U_GAIN, CHAN_U_OFFSET,
                                  identity_operands)

# f32 MXU passes at full precision: the TPU default rounds operands to
# bfloat16, which would move u far beyond the oracle's ulp-level agreement
_F32_DOT = jax.lax.Precision.HIGHEST

# lanes of the per-block partial-reduction rows
STAT_LANES = 128
# kernel A stat lanes
LANE_ABS = 0      # sum |z_clip|   (Hoyer denominator)
LANE_SQ = 1       # sum z_clip^2   (Hoyer numerator)
# kernel B stat lanes
LANE_VSUM = 0     # masked sum of V_CONV
LANE_VMIN = 1     # masked min of V_CONV
LANE_VMAX = 2     # masked max of V_CONV


def _stat_row(values) -> jax.Array:
    """Pack scalars into one (1, STAT_LANES) row: lane i = values[i], rest 0.

    Written with iota + where (no scatter) so it lowers on TPU unchanged.
    """
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, STAT_LANES), 1)
    row = jnp.zeros((1, STAT_LANES), jnp.float32)
    for i, v in enumerate(values):
        row = jnp.where(lane == i, v, row)
    return row


def _row_out(steps: int, width: int, index_map):
    """(spec, shape) of a one-row-per-grid-step output.

    The array is (steps, 1, width) and each step writes its (1, width) row
    through a squeezed leading block dim: the block's last two dims equal
    the array's, which is what the TPU lowering requires of a block that is
    not (8, 128)-aligned. Callers flatten it back to (steps, width).
    """
    return (pl.BlockSpec((None, 1, width), index_map),
            jax.ShapeDtypeStruct((steps, 1, width), jnp.float32))


# ---------------------------------------------------------------------------
# kernel A — the one patch matmul: u + Hoyer partials
# ---------------------------------------------------------------------------

def _phase_a_kernel(patches_ref, w_ref, v_th_ref, u_ref, stats_ref, *,
                    pixel_params: pixel_model.PixelCircuitParams):
    x = patches_ref[...]                     # (bn, K)
    w = w_ref[...]                           # (K, C)
    mac_pos = jnp.dot(x, jnp.maximum(w, 0.0),
                      preferred_element_type=jnp.float32)
    mac_neg = jnp.dot(x, jnp.maximum(-w, 0.0),
                      preferred_element_type=jnp.float32)
    g = pixel_model.get_curve(pixel_params.curve, pixel_params)
    u = g(mac_pos) - g(mac_neg)              # analog subtractor output
    u_ref[...] = u
    # Hoyer-threshold partials: same expressions as core/hoyer.py
    # (hoyer_extremum of clip01(u / v_th)); zero padding contributes 0.
    zc = hoyer.clip01(u / jnp.maximum(v_th_ref[0, 0], 1e-6))
    stats_ref[...] = _stat_row([jnp.sum(jnp.abs(zc)),
                                jnp.sum(jnp.square(zc))])


def p2m_phase_a_pallas(patches: jax.Array, w: jax.Array, v_th: jax.Array, *,
                       pixel_params: pixel_model.PixelCircuitParams =
                       pixel_model.DEFAULT_PIXEL,
                       block_n: int = 256,
                       interpret: Optional[bool] = None):
    """patches (N, K) f32, w (K, C) f32, v_th (1, 1) f32.

    Returns ``(u, hoyer_partials)``: the cached pre-activation (N, C) and the
    per-block Hoyer partial sums (N / block_n, STAT_LANES). N must divide by
    block_n; K, C should be 128-multiples for MXU alignment (ops.py pads).
    VMEM per block: bn*K + K*C + bn*C floats.
    """
    n, kk = patches.shape
    c = w.shape[1]
    assert n % block_n == 0, (n, block_n)
    steps = n // block_n
    kernel = functools.partial(_phase_a_kernel, pixel_params=pixel_params)
    stats_spec, stats_shape = _row_out(steps, STAT_LANES,
                                       lambda i: (i, 0, 0))
    u, stats = pl.pallas_call(
        kernel,
        grid=(steps,),
        in_specs=[
            pl.BlockSpec((block_n, kk), lambda i: (i, 0)),
            pl.BlockSpec((kk, c), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=[pl.BlockSpec((block_n, c), lambda i: (i, 0)), stats_spec],
        out_shape=[jax.ShapeDtypeStruct((n, c), jnp.float32), stats_shape],
        interpret=platform.pallas_interpret(interpret),
    )(patches, w, v_th)
    return u, stats.reshape(steps, STAT_LANES)


def combine_hoyer_partials(partials: jax.Array, v_th: jax.Array) -> jax.Array:
    """Per-block partials -> the global algorithmic threshold theta.

    The tiny host-side combine between kernels A and B: the Hoyer extremum
    E = sum(z_clip^2) / sum(|z_clip|) over the whole frame batch, mapped to
    conv-output units by v_th (same guard as ``core/hoyer.py``).
    """
    abs_sum = jnp.sum(partials[:, LANE_ABS])
    sq_sum = jnp.sum(partials[:, LANE_SQ])
    return sq_sum / jnp.maximum(abs_sum, 1e-9) * v_th


# ---------------------------------------------------------------------------
# implicit-im2col kernel A — blocked image view, ONE packed matmul
# ---------------------------------------------------------------------------

def pack_phase_weights(wm: jax.Array) -> jax.Array:
    """(K, C) signed weights -> the (K, 2C) packed two-phase operand.

    The packing convention itself is single-sourced in
    ``core/p2m.relu_split_pack`` (shared with the analog/device backends'
    packed convolution): ONE MXU pass computes both integration phases and
    each patch block is read exactly once (``dot_count: 1``; the old kernel
    issued two dots over the same block).
    """
    return p2m_core.relu_split_pack(wm)


class _Implicit(NamedTuple):
    """Static geometry of one implicit-im2col pallas_call."""
    imgs: jax.Array       # (B, Hp, Wp, Cin) SAME-padded f32 frames
    bb: int               # frames per grid step
    block_oh: int         # output rows per grid step
    wo: int               # output columns (every step holds whole rows)
    row_groups: int       # grid axis 1: output-row groups per frame
    n: int                # patch rows of the whole call
    bn: int               # patch rows per grid step
    kk: int               # contraction k*k*Cin

    @property
    def grid(self):
        return (self.imgs.shape[0] // self.bb, self.row_groups)

    @property
    def steps(self) -> int:
        return self.n // self.bn

    def img_spec(self):
        _, hp, wp, cin = self.imgs.shape
        return pl.BlockSpec((self.bb, hp, wp, cin),
                            lambda bi, i: (bi, 0, 0, 0))

    def rows_spec(self, width: int):
        """(bn, width) blocks of a (n, width) patch-row-major operand."""
        return pl.BlockSpec((self.bn, width),
                            lambda bi, i, rg=self.row_groups: (bi * rg + i, 0))

    def row_out(self, width: int):
        return _row_out(self.steps, width, lambda bi, i, rg=self.row_groups:
                        (bi * rg + i, 0, 0))


def _implicit(images: jax.Array, kernel: int, stride: int, c_out: int,
              block_n: int) -> _Implicit:
    """Pad the frames and fix the grid of an implicit-im2col kernel.

    The patch-row target ``block_n`` is capped by the VMEM budget
    (``blocking.implicit_block``), so any target — including the tuner's
    whole-N fused default — yields steps the TPU compiler accepts.
    """
    if kernel % 2 == 0:
        raise ValueError(
            f"implicit im2col only supports odd kernel sizes (got "
            f"kernel={kernel}): even kernels cannot reproduce SAME "
            "convolution placement and would silently mis-pad")
    b, h, wd, cin = images.shape
    ho = blocking.conv_out_hw(h, stride)
    wo = blocking.conv_out_hw(wd, stride)
    bb, block_oh = blocking.implicit_block(b, h, wd, cin, kernel, stride,
                                           c_out, block_n)
    return _Implicit(
        imgs=blocking.pad_same(images.astype(jnp.float32), kernel, stride),
        bb=bb, block_oh=block_oh, wo=wo, row_groups=ho // block_oh,
        n=b * ho * wo, bn=bb * block_oh * wo, kk=kernel * kernel * cin)


def _gather_patches(img_ref, *, kernel: int, stride: int, block_oh: int,
                    wo: int) -> jax.Array:
    """Implicit im2col: the (bb*block_oh*wo, k*k*C_in) patch rows of this
    grid step, gathered from the padded-image block in VMEM.

    ``img_ref`` holds (bb, Hp, Wp, C_in); grid axis 1 indexes the output-row
    group, so the window starts at ``program_id(1) * block_oh * stride``.
    Each of the k*k taps is ONE strided ref load (rows and columns stepping
    by ``stride`` — no strided value slice, which the TPU compiler refuses,
    no gather op, no HBM patch matrix); concatenating the taps on the
    channel axis reproduces ``ops.im2col``'s (tap-major, channel-minor) row
    layout, so the packed weights reshape straight from the (k, k, C_in, C)
    tensor.
    """
    base = pl.program_id(1) * (block_oh * stride)
    taps = [img_ref[:, pl.ds(base + di, block_oh, stride=stride),
                    pl.ds(dj, wo, stride=stride), :]  # (bb, block_oh, wo, Cin)
            for di in range(kernel) for dj in range(kernel)]
    x = jnp.concatenate(taps, axis=-1)            # (bb, block_oh, wo, kk*Cin)
    return x.reshape(-1, x.shape[-1])


def _phase_a_epilogue(a: jax.Array, v_th, c_out: int,
                      pixel_params: pixel_model.PixelCircuitParams):
    """Packed-matmul result (bn, 2C) -> (u, hoyer_stat_row).

    Splits the two integration phases in-register, applies the per-phase
    circuit curve, forms the passive-subtractor difference, and reduces the
    Hoyer-threshold partials — identical expressions to core/hoyer.py.
    """
    g = pixel_model.get_curve(pixel_params.curve, pixel_params)
    u = g(a[:, :c_out]) - g(a[:, c_out:])
    zc = hoyer.clip01(u / jnp.maximum(v_th, 1e-6))
    return u, _stat_row([jnp.sum(jnp.abs(zc)), jnp.sum(jnp.square(zc))])


def _phase_a_implicit_kernel(img_ref, w_ref, v_th_ref, u_ref, stats_ref, *,
                             pixel_params: pixel_model.PixelCircuitParams,
                             kernel: int, stride: int,
                             block_oh: int, wo: int, c_out: int):
    x = _gather_patches(img_ref, kernel=kernel, stride=stride,
                        block_oh=block_oh, wo=wo)
    a = jnp.dot(x, w_ref[...], precision=_F32_DOT,
                preferred_element_type=jnp.float32)
    u, stats = _phase_a_epilogue(a, v_th_ref[0, 0], c_out, pixel_params)
    u_ref[...] = u
    stats_ref[...] = stats


def p2m_phase_a_implicit_pallas(images: jax.Array, w_packed: jax.Array,
                                v_th: jax.Array, *, kernel: int, stride: int,
                                pixel_params: pixel_model.PixelCircuitParams =
                                pixel_model.DEFAULT_PIXEL,
                                block_n: int = 2048,
                                interpret: Optional[bool] = None):
    """Implicit-im2col kernel A: images (B, H, W, Cin) UNPADDED in [0, 1],
    w_packed (k*k*Cin, 2C) from ``pack_phase_weights``, v_th (1, 1) f32.

    Returns ``(u, hoyer_partials)``: the cached pre-activation
    (B*H'*W', C) and the per-block Hoyer partial sums
    (grid_steps, STAT_LANES). The only HBM copy this path makes is the
    SAME-padded image itself — the (N, K->128 lanes) patch matrix of the
    old explicit pipeline never exists (DESIGN.md §9).

    ``block_n`` is the target patch-rows per grid step, capped by the VMEM
    budget; the actual block is ``bb * block_oh * wo`` with ``block_oh`` a
    divisor of H' (whole output rows only) and ``bb > 1`` whole frames per
    step only when a step covers full frames (``blocking.a_block_geometry``)
    — both restrictions keep the patch-row order identical to
    ``ops.im2col``.
    """
    c2 = w_packed.shape[1]
    c_out = c2 // 2
    g = _implicit(images, kernel, stride, c_out, block_n)
    assert w_packed.shape[0] == g.kk, (w_packed.shape, g.kk)
    kern = functools.partial(
        _phase_a_implicit_kernel, pixel_params=pixel_params, kernel=kernel,
        stride=stride, block_oh=g.block_oh, wo=g.wo, c_out=c_out)
    stats_spec, stats_shape = g.row_out(STAT_LANES)
    call = pl.pallas_call(
        kern,
        grid=g.grid,
        in_specs=[
            g.img_spec(),
            pl.BlockSpec((g.kk, c2), lambda bi, i: (0, 0)),
            pl.BlockSpec((1, 1), lambda bi, i: (0, 0)),
        ],
        out_specs=[g.rows_spec(c_out), stats_spec],
        out_shape=[jax.ShapeDtypeStruct((g.n, c_out), jnp.float32),
                   stats_shape],
        interpret=platform.pallas_interpret(interpret),
    )
    # device profiles carry the same stage names as the host spans
    # (repro.obs.trace): kernel A = exposure matmul + Hoyer partials
    with jax.named_scope("p2m_kernel_a"):
        u, stats = call(g.imgs, w_packed.astype(jnp.float32), v_th)
    return u, stats.reshape(g.steps, STAT_LANES)


# ---------------------------------------------------------------------------
# kernel B — cached u -> voltage -> switching draw + V_CONV partials
# ---------------------------------------------------------------------------

def _device_epilogue(u: jax.Array, theta, bits: jax.Array, chan: jax.Array,
                     pixel_params: pixel_model.PixelCircuitParams,
                     mtj_params: mtj_model.MTJParams):
    """The in-register device chain shared by kernel B and the fused
    streaming kernels: (u, theta, random words, variation rows) ->
    (binary draw, subtractor voltage map).

    Per-channel chip mismatch (repro/variation): pixel gain/offset (+ the
    programmed calibration trim) on u, then the channel-aggregate MTJ logit
    corner inside the switching fit. Identity rows (1,0,1,0) are a bit-exact
    pass-through — the nominal chip costs nothing. One implementation means
    the fused path can never drift from the two-kernel path (DESIGN.md §3).

    ``chan`` is either the (CHAN_ROWS, C) per-channel operand or the widened
    (CHAN_ROWS, rows_pix, C) per-SPATIAL-PIXEL operand, indexed by the patch
    row's position within its frame: u rows are (frame-major, pixel-minor),
    so a reshape to (frames, rows_pix, C) lines every row up with its pixel's
    operand column and the arithmetic broadcasts — same expressions, same
    order, so a per-pixel map that happens to be constant across pixels is
    bit-identical to the per-channel path (regression-tested).
    """
    flat_shape = None
    if chan.ndim == 3:
        flat_shape = u.shape
        rows_pix, c = chan.shape[1], chan.shape[2]
        u = u.reshape(-1, rows_pix, c)
        bits = bits.reshape(-1, rows_pix, c)
    u = u * chan[CHAN_U_GAIN] + chan[CHAN_U_OFFSET]
    v = pixel_model.conv_voltage(u, theta, pixel_params)
    p_sw = mtj_model.switching_probability(
        v, mtj_params.write_pulse_ps, mtj_params,
        logit_offset=chan[CHAN_LOGIT_OFFSET],
        logit_gain=chan[CHAN_LOGIT_GAIN])
    q = mtj_model.majority_prob_poly(
        p_sw, mtj_params.n_redundant, mtj_params.majority)
    draw = mtj_model.bernoulli_from_bits(bits, q)
    if flat_shape is not None:
        draw = draw.reshape(flat_shape)
        v = v.reshape(flat_shape)
    return draw, v


# 0x9E3779B1 (the golden-ratio hash constant) as an int32
_SEED_MIX = -0x61C8864F


def _draw_words(bits_ref, shape, on_device_rng: bool) -> jax.Array:
    """The per-activation uint16 draw words of one grid step.

    ``on_device_rng=False`` (interpret mode, and the oracle-parity path on
    TPUs): the words stream in as a normal operand (``ops.draw_bits``).
    ``on_device_rng=True`` (real TPUs only — the prng primitives have no
    interpret-mode lowering): ``bits_ref`` is instead a (1, 2) int32 seed
    derived from the caller's key; the per-core generator is seeded per
    (key, block) — the TPU generator takes two seed words, so the linear
    grid-step index is mixed into the second (golden-ratio multiply), every
    block draws an independent stream, and a re-run of the same key replays
    the same words. The words come back as int32 in [0, 2^16), which the
    shared draw expression widens exactly like streamed uint16 words. This
    removes the last HBM read of draw words from the fused path.
    """
    if not on_device_rng:
        return bits_ref[...]
    from jax.experimental.pallas import tpu as pltpu
    step = pl.program_id(0) * pl.num_programs(1) + pl.program_id(1)
    pltpu.prng_seed(bits_ref[0, 0], bits_ref[0, 1] ^ (step * _SEED_MIX))
    raw = pltpu.prng_random_bits(shape)
    return raw.astype(jnp.int32) & 0xFFFF


def _phase_b_kernel(u_ref, theta_ref, bits_ref, chan_ref, out_ref, stats_ref,
                    *,
                    pixel_params: pixel_model.PixelCircuitParams,
                    mtj_params: mtj_model.MTJParams,
                    n_valid: int, c_valid: int, block_n: int):
    u = u_ref[...]                           # (bn, C)
    draw, v = _device_epilogue(u, theta_ref[0, 0], bits_ref[...],
                               chan_ref[...], pixel_params, mtj_params)
    out_ref[...] = draw
    # V_CONV partials over REAL entries only: unlike u, the voltage of a
    # zero-padded entry is not zero (conv_voltage(0, theta) = V_OFS-ish),
    # so padding must be masked out of the stats.
    bn, c = u.shape
    row = (jax.lax.broadcasted_iota(jnp.int32, (bn, c), 0)
           + pl.program_id(0) * block_n)
    col = jax.lax.broadcasted_iota(jnp.int32, (bn, c), 1)
    valid = (row < n_valid) & (col < c_valid)
    stats_ref[...] = _stat_row([
        jnp.sum(jnp.where(valid, v, 0.0)),
        jnp.min(jnp.where(valid, v, jnp.inf)),
        jnp.max(jnp.where(valid, v, -jnp.inf)),
    ])


def p2m_phase_b_pallas(u: jax.Array, theta: jax.Array, bits: jax.Array, *,
                       n_valid: int, c_valid: int,
                       chan: jax.Array | None = None,
                       pixel_params: pixel_model.PixelCircuitParams =
                       pixel_model.DEFAULT_PIXEL,
                       mtj_params: mtj_model.MTJParams = mtj_model.DEFAULT_MTJ,
                       block_n: int = 1024,
                       interpret: Optional[bool] = None):
    """u (N, C) f32 cached pre-activation, theta (1, 1) f32, bits (N, C)
    ``mtj.DRAW_BITS_DTYPE`` (uint16) draw words.

    Returns ``(activations, v_conv_partials)``: float {0,1} (N, C) plus the
    per-block masked (sum, min, max) of the subtractor voltage,
    (N / block_n, STAT_LANES). ``n_valid`` / ``c_valid`` are the un-padded
    extents (static — shapes are known at trace time). A ``block_n`` over
    the VMEM budget (``blocking.elem_rows_cap``) shrinks to the largest
    divisor of N within it.

    ``chan`` is the per-chip device-variation operand for the epilogue:
    either the (CHAN_ROWS, C) per-channel rows
    (``repro.variation.chip.channel_operands``) — u-gain/offset for the
    pixel mismatch + calibration trim, logit-gain/offset for the MTJ
    corner — or the widened (CHAN_ROWS, N_pix, C) per-SPATIAL-PIXEL maps
    (``repro.variation.chip.pixel_operands``), indexed by each patch row's
    position within its frame (``block_n`` is clamped to a multiple of
    N_pix so a block always covers whole frames of the row-major map).
    ``None`` uses the identity rows — a bit-exact pass-through.
    ``chan`` is deliberately a runtime ARRAY, never folded into the trace as
    a constant: the lifetime subsystem (DESIGN.md §8) re-derives it from the
    aged chip every microbatch, and the no-recompilation guarantee of a
    streaming engine rests on this kernel treating it as data.
    """
    n, c = u.shape
    if chan is None:
        chan = identity_operands(c)
    cap = blocking.elem_rows_cap(c)
    if block_n > cap:
        block_n = blocking.largest_divisor_at_most(n, cap)
    if chan.ndim == 3:
        n_pix = chan.shape[1]
        assert chan.shape == (CHAN_ROWS, n_pix, c), (chan.shape, c)
        assert n % n_pix == 0, (n, n_pix)
        # whole frames per block: the per-pixel map tiles row-major
        block_n = n_pix * blocking.largest_divisor_at_most(
            n // n_pix, max(block_n // n_pix, 1))
        chan_spec = pl.BlockSpec((CHAN_ROWS, n_pix, c), lambda i: (0, 0, 0))
    else:
        assert chan.shape == (CHAN_ROWS, c), (chan.shape, c)
        chan_spec = pl.BlockSpec((CHAN_ROWS, c), lambda i: (0, 0))
    assert n % block_n == 0, (n, block_n)
    steps = n // block_n
    kernel = functools.partial(
        _phase_b_kernel, pixel_params=pixel_params, mtj_params=mtj_params,
        n_valid=n_valid, c_valid=c_valid, block_n=block_n)
    stats_spec, stats_shape = _row_out(steps, STAT_LANES,
                                       lambda i: (i, 0, 0))
    call = pl.pallas_call(
        kernel,
        grid=(steps,),
        in_specs=[
            pl.BlockSpec((block_n, c), lambda i: (i, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            pl.BlockSpec((block_n, c), lambda i: (i, 0)),
            chan_spec,
        ],
        out_specs=[pl.BlockSpec((block_n, c), lambda i: (i, 0)), stats_spec],
        out_shape=[jax.ShapeDtypeStruct((n, c), jnp.float32), stats_shape],
        interpret=platform.pallas_interpret(interpret),
    )
    # kernel B = draw + V_CONV voltage-map epilogue
    with jax.named_scope("p2m_kernel_b"):
        out, stats = call(u, theta, bits, chan.astype(jnp.float32))
    return out, stats.reshape(steps, STAT_LANES)


def combine_v_conv_partials(partials: jax.Array, n_valid: int,
                            c_valid: int) -> dict:
    """Per-block masked (sum, min, max) -> the standard v_conv_* aux stats."""
    return {
        "v_conv_mean": jnp.sum(partials[:, LANE_VSUM]) / (n_valid * c_valid),
        "v_conv_min": jnp.min(partials[:, LANE_VMIN]),
        "v_conv_max": jnp.max(partials[:, LANE_VMAX]),
    }


# ---------------------------------------------------------------------------
# fused streaming kernel — implicit im2col + device chain in ONE pass
# ---------------------------------------------------------------------------

def _fused_stream_kernel(img_ref, w_ref, v_th_ref, theta_ref, bits_ref,
                         chan_ref, out_ref, hoyer_ref, vstats_ref, rates_ref,
                         *,
                         pixel_params: pixel_model.PixelCircuitParams,
                         mtj_params: mtj_model.MTJParams,
                         kernel: int, stride: int,
                         block_oh: int, wo: int, c_out: int,
                         on_device_rng: bool = False):
    x = _gather_patches(img_ref, kernel=kernel, stride=stride,
                        block_oh=block_oh, wo=wo)
    a = jnp.dot(x, w_ref[...], precision=_F32_DOT,
                preferred_element_type=jnp.float32)
    u, stats = _phase_a_epilogue(a, v_th_ref[0, 0], c_out, pixel_params)
    hoyer_ref[...] = stats                   # FRESH partials: the drift audit
    bits = _draw_words(bits_ref, u.shape, on_device_rng)
    # the device chain runs at the CARRIED theta — u never leaves registers
    draw, v = _device_epilogue(u, theta_ref[0, 0], bits,
                               chan_ref[...], pixel_params, mtj_params)
    out_ref[...] = draw
    # no padding exists on the implicit path, so the V_CONV stats need no
    # validity mask (kernel B masks only because its legacy callers pad)
    vstats_ref[...] = _stat_row([jnp.sum(v), jnp.min(v), jnp.max(v)])
    # per-channel activation partial sums: the lifetime scheduler's rate
    # monitor comes out of the kernel too, sparing the serving path a
    # whole-map host reduction per microbatch
    rates_ref[...] = jnp.sum(draw, axis=0, keepdims=True)


def p2m_fused_stream_pallas(images: jax.Array, w_packed: jax.Array,
                            v_th: jax.Array, theta: jax.Array,
                            bits: jax.Array, chan: jax.Array | None = None, *,
                            kernel: int, stride: int,
                            pixel_params: pixel_model.PixelCircuitParams =
                            pixel_model.DEFAULT_PIXEL,
                            mtj_params: mtj_model.MTJParams =
                            mtj_model.DEFAULT_MTJ,
                            block_n: int = 2048,
                            rng_seed: jax.Array | None = None,
                            interpret: Optional[bool] = None):
    """Steady-state streaming step: the whole sensor frontend in ONE kernel.

    images (B, H, W, Cin) unpadded; w_packed (k*k*Cin, 2C); v_th (1, 1);
    theta (1, 1) the CARRIED threshold (previous-microbatch EMA — kernel A's
    global Hoyer reduction is the one true serialization point of the exact
    pipeline, and a streaming sensor breaks it by using last frame's
    threshold); bits (B*H'*W', C) ``mtj.DRAW_BITS_DTYPE`` (uint16) draw
    words (``ops.draw_bits``); chan the optional
    (CHAN_ROWS, C) per-channel — or (CHAN_ROWS, H'*W', C) per-spatial-pixel —
    variation operand (None = identity rows).

    ``rng_seed`` (real TPUs only): a (1, 2) int32 seed derived from the
    caller's key. When given, the draw words are generated ON DEVICE by the
    per-core PRNG (``pltpu.prng_random_bits`` seeded per (key, block) — see
    ``_draw_words``) and ``bits`` is ignored entirely (pass None): the last
    HBM read of draw words disappears from the fused path. Interpret mode
    rejects it — the prng primitives have no interpret lowering, and
    ``ops.draw_bits`` stays the oracle the TPU stream is validated against.

    Returns ``(activations, hoyer_partials, v_conv_partials,
    channel_sum_partials)``: float {0,1} (N, C) plus the per-block FRESH
    Hoyer partials (for the caller's theta-drift guard / EMA update), the
    V_CONV partials, and per-block per-channel activation sums (the
    (grid, C) rate-monitor partials — sum / N is the streamed
    ``channel_rates`` telemetry). With ``theta``
    pinned to the exact two-kernel pipeline's theta the activations are
    bit-identical to that pipeline (same gather, same packed dot, same
    epilogue expressions — regression-tested); u and the patch matrix never
    touch HBM at all on this path (DESIGN.md §9).
    """
    c2 = w_packed.shape[1]
    c_out = c2 // 2
    g = _implicit(images, kernel, stride, c_out, block_n)
    assert w_packed.shape[0] == g.kk, (w_packed.shape, g.kk)
    interpret = platform.pallas_interpret(interpret)
    bits_arg, bits_spec = _bits_operand(g, bits, rng_seed, c_out, interpret)
    chan, chan_spec = _chan_operand(g, chan, c_out)
    kern = functools.partial(
        _fused_stream_kernel, pixel_params=pixel_params,
        mtj_params=mtj_params, kernel=kernel, stride=stride,
        block_oh=g.block_oh, wo=g.wo, c_out=c_out,
        on_device_rng=rng_seed is not None)
    row_outs = [g.row_out(w) for w in (STAT_LANES, STAT_LANES, c_out)]
    call = pl.pallas_call(
        kern,
        grid=g.grid,
        in_specs=[
            g.img_spec(),
            pl.BlockSpec((g.kk, c2), lambda bi, i: (0, 0)),
            pl.BlockSpec((1, 1), lambda bi, i: (0, 0)),
            pl.BlockSpec((1, 1), lambda bi, i: (0, 0)),
            bits_spec,
            chan_spec,
        ],
        out_specs=[g.rows_spec(c_out)] + [spec for spec, _ in row_outs],
        out_shape=([jax.ShapeDtypeStruct((g.n, c_out), jnp.float32)]
                   + [shape for _, shape in row_outs]),
        interpret=interpret,
    )
    # fused single-kernel streaming step (DESIGN.md §9)
    with jax.named_scope("p2m_kernel_fused"):
        out, hoyer_rows, v_rows, rate_rows = call(
            g.imgs, w_packed.astype(jnp.float32), v_th, theta, bits_arg,
            chan)
    return (out, hoyer_rows.reshape(g.steps, STAT_LANES),
            v_rows.reshape(g.steps, STAT_LANES),
            rate_rows.reshape(g.steps, c_out))


def _bits_operand(g: _Implicit, bits, rng_seed, c_out: int,
                  interpret: bool):
    """(operand, spec) of a fused kernel's draw source: the streamed (N, C)
    uint16 words, or the (1, 2) int32 seed of the on-device generator."""
    if rng_seed is None:
        assert bits.shape == (g.n, c_out), (bits.shape, g.n, c_out)
        return bits, g.rows_spec(c_out)
    if interpret:
        raise ValueError(
            "rng_seed (on-device pltpu prng) has no interpret-mode "
            "lowering; interpret runs must stream ops.draw_bits words")
    assert rng_seed.shape == (1, 2), rng_seed.shape
    return (rng_seed.astype(jnp.int32),
            pl.BlockSpec((1, 2), lambda bi, i: (0, 0)))


def _chan_operand(g: _Implicit, chan, c_out: int):
    """(operand, spec) of a fused kernel's variation rows: (CHAN_ROWS, C)
    per channel, or (CHAN_ROWS, H'*W', C) per spatial pixel — row group i
    of any frame covers pixel rows [i*block_oh*wo, ...)."""
    if chan is None:
        chan = identity_operands(c_out)
    if chan.ndim == 3:
        assert chan.shape == (CHAN_ROWS, g.row_groups * g.block_oh * g.wo,
                              c_out), (chan.shape,)
        spec = pl.BlockSpec((CHAN_ROWS, g.block_oh * g.wo, c_out),
                            lambda bi, i: (0, i, 0))
    else:
        assert chan.shape == (CHAN_ROWS, c_out), (chan.shape, c_out)
        spec = pl.BlockSpec((CHAN_ROWS, c_out), lambda bi, i: (0, 0))
    return chan.astype(jnp.float32), spec


# ---------------------------------------------------------------------------
# int8 quantized path (DESIGN.md §14) — the MXU sees the binary frontend
# ---------------------------------------------------------------------------
#
# Both packed-matmul operands are int8 (per-output-column weight scales +
# the fixed 1/128 activation grid — single-sourced in core/p2m.py) and the
# combined dequant row folds into the epilogue as ONE multiply before the
# already-nonlinear circuit curve, so dequantization costs zero extra passes
# over u. The accumulator dtype switches on the execution mode:
#
#   * interpret (CPU validation): float32 — EXACT for this contraction
#     (products < 2^14, depth k*k*C_in keeps sums < 2^24; see core/p2m.py),
#     and ~6x faster than int32 through XLA:CPU.
#   * compiled (real TPU): int32 — the native MXU s8xs8->s32 systolic path,
#     bit-identical to the f32 accumulator by the same exactness argument
#     (property-tested in tests/test_quantized.py).
#
# The fused q8 kernel also repacks its FOUR per-block stat outputs
# (hoyer row, v row, rates row + draws) into draws + ONE stats row: on the
# interpret path every extra kernel output re-inlines the whole epilogue
# chain into its consumer, so fewer outputs is the measured perf fix, and on
# TPUs it is one store instead of three.

# lanes of the fused-q8 single stats row: scalar stats first, then the
# per-channel rate partials starting at lane STAT_LANES
QLANE_ABS = 0     # sum |z_clip|   (Hoyer denominator)
QLANE_SQ = 1      # sum z_clip^2   (Hoyer numerator)
QLANE_VSUM = 2    # sum of V_CONV
QLANE_VMIN = 3    # min of V_CONV
QLANE_VMAX = 4    # max of V_CONV
QLANE_RATES = STAT_LANES   # lanes [STAT_LANES, STAT_LANES+C): sum of draws


def q8_stat_width(c_out: int) -> int:
    """Lane width of the fused-q8 stats row: 128 scalar lanes + padded C."""
    return STAT_LANES + -(-c_out // STAT_LANES) * STAT_LANES


def _q8_dot(x: jax.Array, wq: jax.Array, dq: jax.Array, acc_dtype):
    """The quantized packed matmul: f32 patch rows -> dequantized (bn, 2C).

    Activations quantize onto the 1/128 grid in VMEM (the f32 patch rows
    never leave the block), the dot runs s8 x s8 with ``acc_dtype``
    accumulation, and the per-column dequant row maps the integer result
    back to MAC units in one multiply.
    """
    xq = p2m_core.quantize_acts_q8(x)
    acc = jnp.dot(xq, wq, preferred_element_type=acc_dtype)
    return acc.astype(jnp.float32) * dq


def _phase_a_implicit_q8_kernel(img_ref, wq_ref, dq_ref, v_th_ref,
                                u_ref, stats_ref, *,
                                pixel_params: pixel_model.PixelCircuitParams,
                                kernel: int, stride: int,
                                block_oh: int, wo: int, c_out: int,
                                acc_dtype):
    x = _gather_patches(img_ref, kernel=kernel, stride=stride,
                        block_oh=block_oh, wo=wo)
    a = _q8_dot(x, wq_ref[...], dq_ref[...], acc_dtype)
    u, stats = _phase_a_epilogue(a, v_th_ref[0, 0], c_out, pixel_params)
    u_ref[...] = u
    stats_ref[...] = stats


def p2m_phase_a_implicit_q8_pallas(images: jax.Array, wq_packed: jax.Array,
                                   dequant_row: jax.Array, v_th: jax.Array, *,
                                   kernel: int, stride: int,
                                   pixel_params:
                                   pixel_model.PixelCircuitParams =
                                   pixel_model.DEFAULT_PIXEL,
                                   block_n: int = 2048,
                                   interpret: Optional[bool] = None):
    """Quantized implicit-im2col kernel A.

    Same contract as ``p2m_phase_a_implicit_pallas`` but the packed weight
    operand is int8 (``core.p2m.quantize_packed_weights`` over the (K, 2C)
    relu-split matrix) with its (1, 2C) combined dequant row
    (``core.p2m.packed_dequant_row``); activations quantize in-kernel.
    Returns ``(u, hoyer_partials)``. Kernel B consumes this u UNCHANGED —
    the downstream device chain is literally the same kernel as the f32
    path, which is what makes phase-B parity bit-exact at the operand level.
    """
    c2 = wq_packed.shape[1]
    c_out = c2 // 2
    g = _implicit(images, kernel, stride, c_out, block_n)
    assert wq_packed.shape[0] == g.kk, (wq_packed.shape, g.kk)
    assert wq_packed.dtype == jnp.int8, wq_packed.dtype
    assert dequant_row.shape == (1, c2), (dequant_row.shape, c2)
    interpret = platform.pallas_interpret(interpret)
    kern = functools.partial(
        _phase_a_implicit_q8_kernel, pixel_params=pixel_params, kernel=kernel,
        stride=stride, block_oh=g.block_oh, wo=g.wo, c_out=c_out,
        acc_dtype=jnp.float32 if interpret else jnp.int32)
    stats_spec, stats_shape = g.row_out(STAT_LANES)
    call = pl.pallas_call(
        kern,
        grid=g.grid,
        in_specs=[
            g.img_spec(),
            pl.BlockSpec((g.kk, c2), lambda bi, i: (0, 0)),
            pl.BlockSpec((1, c2), lambda bi, i: (0, 0)),
            pl.BlockSpec((1, 1), lambda bi, i: (0, 0)),
        ],
        out_specs=[g.rows_spec(c_out), stats_spec],
        out_shape=[jax.ShapeDtypeStruct((g.n, c_out), jnp.float32),
                   stats_shape],
        interpret=interpret,
    )
    with jax.named_scope("p2m_kernel_a_q8"):
        u, stats = call(g.imgs, wq_packed, dequant_row.astype(jnp.float32),
                        v_th)
    return u, stats.reshape(g.steps, STAT_LANES)


def _fused_stream_q8_kernel(img_ref, wq_ref, dq_ref, v_th_ref, theta_ref,
                            bits_ref, chan_ref, out_ref, srow_ref, *,
                            pixel_params: pixel_model.PixelCircuitParams,
                            mtj_params: mtj_model.MTJParams,
                            kernel: int, stride: int,
                            block_oh: int, wo: int, c_out: int,
                            acc_dtype, on_device_rng: bool):
    x = _gather_patches(img_ref, kernel=kernel, stride=stride,
                        block_oh=block_oh, wo=wo)
    a = _q8_dot(x, wq_ref[...], dq_ref[...], acc_dtype)
    u, _ = _phase_a_epilogue(a, v_th_ref[0, 0], c_out, pixel_params)
    zc = hoyer.clip01(u / jnp.maximum(v_th_ref[0, 0], 1e-6))
    bits = _draw_words(bits_ref, u.shape, on_device_rng)
    draw, v = _device_epilogue(u, theta_ref[0, 0], bits,
                               chan_ref[...], pixel_params, mtj_params)
    out_ref[...] = draw
    # ONE packed stats row per block (scalar lanes + rate lanes): same
    # expressions and reduction order as the f32 kernel's three rows, so the
    # combined aux is bit-identical — only the packing differs.
    scal = _stat_row([jnp.sum(jnp.abs(zc)), jnp.sum(jnp.square(zc)),
                      jnp.sum(v), jnp.min(v), jnp.max(v)])
    rates = jnp.sum(draw, axis=0, keepdims=True)
    width = srow_ref.shape[1]
    pad = width - STAT_LANES - c_out
    parts = [scal, rates]
    if pad:
        parts.append(jnp.zeros((1, pad), jnp.float32))
    srow_ref[...] = jnp.concatenate(parts, axis=1)


def p2m_fused_stream_q8_pallas(images: jax.Array, wq_packed: jax.Array,
                               dequant_row: jax.Array, v_th: jax.Array,
                               theta: jax.Array, bits: jax.Array,
                               chan: jax.Array | None = None, *,
                               kernel: int, stride: int,
                               pixel_params: pixel_model.PixelCircuitParams =
                               pixel_model.DEFAULT_PIXEL,
                               mtj_params: mtj_model.MTJParams =
                               mtj_model.DEFAULT_MTJ,
                               block_n: int = 2048,
                               rng_seed: jax.Array | None = None,
                               interpret: Optional[bool] = None):
    """Quantized fused streaming step: the int8 frontend in ONE kernel.

    Same contract as ``p2m_fused_stream_pallas`` with the quantized weight
    operands of ``p2m_phase_a_implicit_q8_pallas``, but TWO outputs instead
    of four: ``(activations, stats_row)`` where ``stats_row`` is
    (grid_steps, ``q8_stat_width(C)``) — Hoyer partials, V_CONV partials and
    per-channel activation sums packed into one row per block
    (``combine_q8_stream_stats`` unpacks it). The repack is a measured
    interpret-mode win (every extra output re-inlines the epilogue chain
    into its consumer) and one store instead of three on TPUs.
    """
    c2 = wq_packed.shape[1]
    c_out = c2 // 2
    g = _implicit(images, kernel, stride, c_out, block_n)
    assert wq_packed.shape[0] == g.kk, (wq_packed.shape, g.kk)
    assert wq_packed.dtype == jnp.int8, wq_packed.dtype
    assert dequant_row.shape == (1, c2), (dequant_row.shape, c2)
    interpret = platform.pallas_interpret(interpret)
    bits_arg, bits_spec = _bits_operand(g, bits, rng_seed, c_out, interpret)
    chan, chan_spec = _chan_operand(g, chan, c_out)
    width = q8_stat_width(c_out)
    kern = functools.partial(
        _fused_stream_q8_kernel, pixel_params=pixel_params,
        mtj_params=mtj_params, kernel=kernel, stride=stride,
        block_oh=g.block_oh, wo=g.wo, c_out=c_out,
        acc_dtype=jnp.float32 if interpret else jnp.int32,
        on_device_rng=rng_seed is not None)
    stats_spec, stats_shape = g.row_out(width)
    call = pl.pallas_call(
        kern,
        grid=g.grid,
        in_specs=[
            g.img_spec(),
            pl.BlockSpec((g.kk, c2), lambda bi, i: (0, 0)),
            pl.BlockSpec((1, c2), lambda bi, i: (0, 0)),
            pl.BlockSpec((1, 1), lambda bi, i: (0, 0)),
            pl.BlockSpec((1, 1), lambda bi, i: (0, 0)),
            bits_spec,
            chan_spec,
        ],
        out_specs=[g.rows_spec(c_out), stats_spec],
        out_shape=[jax.ShapeDtypeStruct((g.n, c_out), jnp.float32),
                   stats_shape],
        interpret=interpret,
    )
    with jax.named_scope("p2m_kernel_fused_q8"):
        out, srow = call(g.imgs, wq_packed, dequant_row.astype(jnp.float32),
                         v_th, theta, bits_arg, chan)
    return out, srow.reshape(g.steps, width)


def combine_q8_stream_stats(srow: jax.Array, v_th: jax.Array,
                            n_valid: int, c_valid: int):
    """Unpack the fused-q8 stats row -> ``(theta, v_conv_stats, rate_sums)``.

    Identical reductions (same lanes-then-blocks order) as
    ``combine_hoyer_partials`` / ``combine_v_conv_partials`` / the rate-row
    sum of the f32 fused path, so a caller switching precision sees
    bit-identical aux for identical per-block partials. ``rate_sums`` is the
    (C,) per-channel activation-count sum — divide by N for channel_rates.
    """
    abs_sum = jnp.sum(srow[:, QLANE_ABS])
    sq_sum = jnp.sum(srow[:, QLANE_SQ])
    theta = sq_sum / jnp.maximum(abs_sum, 1e-9) * v_th
    stats = {
        "v_conv_mean": jnp.sum(srow[:, QLANE_VSUM]) / (n_valid * c_valid),
        "v_conv_min": jnp.min(srow[:, QLANE_VMIN]),
        "v_conv_max": jnp.max(srow[:, QLANE_VMAX]),
    }
    rate_sums = jnp.sum(srow[:, QLANE_RATES:QLANE_RATES + c_valid], axis=0)
    return theta, stats, rate_sums


# ---------------------------------------------------------------------------
# legacy fused single kernel — the pre-fix baseline
# ---------------------------------------------------------------------------

def _fused_kernel(patches_ref, w_ref, theta_ref, bits_ref, out_ref, *,
                  pixel_params: pixel_model.PixelCircuitParams,
                  mtj_params: mtj_model.MTJParams):
    x = patches_ref[...]                     # (bn, K)
    w = w_ref[...]                           # (K, C)
    mac_pos = jnp.dot(x, jnp.maximum(w, 0.0),
                      preferred_element_type=jnp.float32)
    mac_neg = jnp.dot(x, jnp.maximum(-w, 0.0),
                      preferred_element_type=jnp.float32)
    g = pixel_model.get_curve(pixel_params.curve, pixel_params)
    u = g(mac_pos) - g(mac_neg)              # analog subtractor output
    v = pixel_model.conv_voltage(u, theta_ref[0, 0], pixel_params)
    p_sw = mtj_model.switching_probability(
        v, mtj_params.write_pulse_ps, mtj_params)
    q = mtj_model.majority_prob_poly(
        p_sw, mtj_params.n_redundant, mtj_params.majority)
    out_ref[...] = mtj_model.bernoulli_from_bits(bits_ref[...], q)


def p2m_conv_pallas(patches: jax.Array, w: jax.Array, theta: jax.Array,
                    bits: jax.Array, *,
                    pixel_params: pixel_model.PixelCircuitParams =
                    pixel_model.DEFAULT_PIXEL,
                    mtj_params: mtj_model.MTJParams = mtj_model.DEFAULT_MTJ,
                    block_n: int = 256,
                    interpret: Optional[bool] = None) -> jax.Array:
    """Pre-split fused kernel: patches (N, K) f32, w (K, C) f32,
    theta (1, 1) f32, bits (N, C) uint16 draw words -> float {0,1}
    activations (N, C).

    Requires theta up front, which is what forced the old backend into a
    second pure-JAX conv pass. Kept as the baseline the single-pass pipeline
    is benchmarked against, and as a fused-path regression target.
    """
    n, kk = patches.shape
    c = w.shape[1]
    assert n % block_n == 0, (n, block_n)
    grid = (n // block_n,)
    kernel = functools.partial(_fused_kernel, pixel_params=pixel_params,
                               mtj_params=mtj_params)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_n, kk), lambda i: (i, 0)),
            pl.BlockSpec((kk, c), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            pl.BlockSpec((block_n, c), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_n, c), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, c), jnp.float32),
        interpret=platform.pallas_interpret(interpret),
    )(patches, w, theta, bits)
