"""jit'd public wrappers around the Pallas kernels — the API the rest of the
framework calls.

The production frontend pipeline (``p2m_frontend`` and its streaming variant
``p2m_frontend_fused``) is the *implicit-im2col* path (DESIGN.md §9): the
only HBM tensor it creates ahead of kernel A is the SAME-padded image — the
(N, K->128 lanes) patch matrix of the old explicit path never exists, the
relu-split phase weights are packed into one (K, 2C) operand so each patch
block hits the MXU exactly once, and block sizes come from the per-shape
tile autotuner (``kernels/autotune.py``) unless the caller pins them.

``im2col`` and the explicit-patch ``p2m_conv`` remain ONLY as the
benchmark baseline (benchmarks/frontend_bench.py measures the pre-fix
double-conv path through them) and as test oracles — no production call
site materializes patches anymore.

Kernels compile on a TPU and run in interpret mode elsewhere
(``repro.platform.pallas_interpret``); an explicit ``interpret=`` pins the
mode (tests, compile rehearsals for a described chip).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import platform
from repro.core import mtj as mtj_model
from repro.core import p2m as p2m_core
from repro.core import pixel as pixel_model
from repro.kernels import autotune, blocking
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.p2m_conv import (combine_hoyer_partials,
                                    combine_q8_stream_stats,
                                    combine_v_conv_partials, p2m_conv_pallas,
                                    p2m_fused_stream_pallas,
                                    p2m_fused_stream_q8_pallas,
                                    p2m_phase_a_implicit_pallas,
                                    p2m_phase_a_implicit_q8_pallas,
                                    p2m_phase_b_pallas, pack_phase_weights)

# single-sourced in kernels/blocking.py; re-exported under the historical
# names because tests and the benchmark baseline build padded operands
_pad_to = blocking.pad_to
_elem_block = blocking.elem_block
conv_out_hw = blocking.conv_out_hw


def _fmix32(x: jax.Array) -> jax.Array:
    """murmur3's 32-bit finalizer: full avalanche in 5 integer ops."""
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> 13)
    x = x * jnp.uint32(0xC2B2AE35)
    return x ^ (x >> 16)


def draw_bits(key: jax.Array, n: int, c: int) -> jax.Array:
    """The (n, c) uniform words feeding the folded majority draw.

    One ``mtj.DRAW_BITS_DTYPE`` (uint16) word per activation, generated by a
    counter-based hash (two murmur3 finalizer rounds over ``index ^ key``) —
    the single source tests use to reproduce the frontend's draws when
    calling the oracles directly (the draw expression itself is
    ``mtj.bernoulli_from_bits``).

    Why not ``jax.random.bits``: the words model *thermal switching noise*
    in a simulated sensor, not cryptographic material, and the counter hash
    is one vectorized integer pass (~2.5x cheaper than threefry on the
    interpret-mode CPU target — it was the single largest pallas-only cost
    of the serving step, DESIGN.md §9). It is a pure function of
    ``(key, index)``: replaying a pinned key reproduces the draws exactly,
    and distinct fold_in keys give independent streams (uniformity and
    independence are regression-tested). On real TPUs
    ``pltpu.prng_random_bits`` — likewise a non-cryptographic generator —
    is the in-kernel drop-in (ROADMAP open item).
    """
    kd = jax.random.key_data(key).astype(jnp.uint32)
    # golden-ratio offset on the counter: without it the all-zero key (a
    # bare PRNGKey(0)) has a fixed point at index 0 (fmix32(0) == 0 -> a
    # word of exactly 0 -> that activation fires whenever q > 0)
    idx = jax.lax.iota(jnp.uint32, n * c) + jnp.uint32(0x9E3779B9)
    h = _fmix32(idx ^ kd[0])
    h = _fmix32(h ^ kd[1])
    return (h & jnp.uint32(0xFFFF)).astype(
        mtj_model.DRAW_BITS_DTYPE).reshape(n, c)


def rng_seed_from_key(key: jax.Array) -> jax.Array:
    """The (1, 2) int32 seed the on-device PRNG path derives from a key.

    Real TPUs only (``rng_seed=`` on the fused kernels): the two raw key
    words seed ``pltpu.prng_seed`` together with the grid coordinates, so
    distinct keys give independent streams and a replayed key replays its
    draws — the same contract ``draw_bits`` provides on the oracle path.
    """
    return jax.random.key_data(key).astype(jnp.int32).reshape(1, 2)


def quantize_frontend_weights(wm: jax.Array):
    """Packed (K, 2C) relu-split weights -> ``(wq int8, dequant_row (1, 2C))``.

    The one assembly point of the quantized kernel operands
    (``core.p2m.quantize_packed_weights`` + ``packed_dequant_row`` — the
    combined scale/128 row that folds activation and weight dequant into a
    single epilogue multiply).
    """
    wq, scale = p2m_core.quantize_packed_weights(wm)
    return wq, p2m_core.packed_dequant_row(scale)


def im2col(images: jax.Array, kernel: int, stride: int) -> jax.Array:
    """NHWC -> (B*H'*W', k*k*C) patch rows (SAME padding, odd kernels only).

    BENCHMARK/TEST-ONLY: the production pipeline gathers its patch rows
    in-kernel (implicit im2col — the patch matrix never exists in HBM); this
    explicit materialization remains as the oracle row-layout definition and
    for the legacy ``p2m_conv`` baseline.

    Window placement matches ``jax.lax.conv_general_dilated(..., "SAME")``
    exactly: output extent ceil(h/stride) and asymmetric padding with the
    extra element on the high side — so the patch matmul samples the same
    pixels as the pure-JAX conv backends. (The old symmetric ``kernel // 2``
    padding was off by one pixel for strided even-size inputs, silently
    misaligning the pallas backend against ``p2m.hardware_conv``.) An even
    kernel has no SAME-consistent symmetric interpretation at all, so it is
    rejected up front instead of silently mis-padding.
    """
    if kernel % 2 == 0:
        raise ValueError(
            f"im2col only supports odd kernel sizes (got kernel={kernel}): "
            "even kernels cannot reproduce SAME convolution placement and "
            "would silently mis-pad")
    b, h, w, c = images.shape
    ho, wo = conv_out_hw(h, stride), conv_out_hw(w, stride)
    x = blocking.pad_same(images, kernel, stride)
    idx = jnp.arange(ho) * stride
    jdx = jnp.arange(wo) * stride
    patches = []
    for di in range(kernel):
        for dj in range(kernel):
            patches.append(x[:, idx + di][:, :, jdx + dj])   # (B,H',W',C)
    out = jnp.stack(patches, axis=3)                          # (B,H',W',k*k,C)
    return out.reshape(b * ho * wo, kernel * kernel * c)


@functools.partial(jax.jit, static_argnames=("kernel", "stride",
                                             "pixel_params", "mtj_params",
                                             "interpret", "block_n"))
def p2m_conv(images: jax.Array, w: jax.Array, theta: jax.Array,
             key: jax.Array, *, kernel: int = 3, stride: int = 2,
             pixel_params: pixel_model.PixelCircuitParams =
             pixel_model.DEFAULT_PIXEL,
             mtj_params: mtj_model.MTJParams = mtj_model.DEFAULT_MTJ,
             interpret: Optional[bool] = None, block_n: int = 256
             ) -> jax.Array:
    """Legacy fused P2M layer (pre-split). images (B,H,W,C) in [0,1];
    w (k,k,C,Cout) signed quantized weights; theta () threshold. Returns
    (B,H',W',Cout) binary.

    Requires ``theta`` up front — the caller must run its own conv pass to
    produce it, which is exactly the double-conv the single-pass
    ``p2m_frontend`` pipeline removes. Kept as the benchmark baseline and a
    fused-path regression target; the frontend no longer calls it (this is
    also the last materialized-im2col call site, by design).

    ``pixel_params``/``mtj_params`` (frozen dataclasses, static for jit)
    carry every circuit/device constant into the kernel — nothing is baked.
    """
    b, h, wd, c = images.shape
    cout = w.shape[-1]
    ho, wo = conv_out_hw(h, stride), conv_out_hw(wd, stride)
    patches = im2col(images, kernel, stride)                 # (N, K)
    wm = w.reshape(kernel * kernel * c, cout)
    n = patches.shape[0]
    bits = draw_bits(key, n, cout)

    # MXU alignment: pad K and C to 128 lanes, N to the block size
    patches = _pad_to(patches, 1, 128)
    wm = _pad_to(_pad_to(wm, 0, 128), 1, 128)
    bits_p = _pad_to(bits, 1, 128)
    n_pad = -n % block_n
    if n_pad:
        patches = jnp.pad(patches, ((0, n_pad), (0, 0)))
        bits_p = jnp.pad(bits_p, ((0, n_pad), (0, 0)))
    out = p2m_conv_pallas(patches.astype(jnp.float32), wm.astype(jnp.float32),
                          theta.reshape(1, 1).astype(jnp.float32), bits_p,
                          pixel_params=pixel_params, mtj_params=mtj_params,
                          block_n=block_n, interpret=interpret)
    return out[:n, :cout].reshape(b, ho, wo, cout)


def _on_each_device(step):
    """``step`` (a frontend step over array operands) as run under the
    ambient mesh that a sharded engine sets (``jax.set_mesh``).

    The TPU compiler cannot partition a Mosaic kernel, so on a multi-device
    mesh the step runs inside a ``shard_map`` over every mesh axis with
    replicated operands: each device computes the frontend of the whole
    microbatch. The batch-global Hoyer threshold therefore stays ONE
    reduction over all frames — sharded serving is bit-identical to a
    single device — and the backbone after it is partitioned as before.
    The frontend is ~5% of a vgg16 step's MACs, so the redundancy is cheap.
    """
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.size == 1:
        return step
    return jax.shard_map(step, mesh=mesh, in_specs=P(), out_specs=P(),
                         check_vma=False)


def _frontend_shape(images: jax.Array, kernel: int, stride: int, w: jax.Array):
    """(N patch rows, K contraction, C_out) of a frontend call."""
    b, h, wd, cin = images.shape
    n = b * conv_out_hw(h, stride) * conv_out_hw(wd, stride)
    return n, kernel * kernel * cin, w.shape[-1]


@functools.partial(jax.jit, static_argnames=("kernel", "stride",
                                             "pixel_params", "mtj_params",
                                             "interpret", "block_n",
                                             "block_n_elem", "precision"))
def _p2m_frontend(images: jax.Array, w: jax.Array, v_th: jax.Array,
                  key: jax.Array, chan: Optional[jax.Array], *,
                  kernel: int, stride: int,
                  pixel_params: pixel_model.PixelCircuitParams,
                  mtj_params: mtj_model.MTJParams,
                  interpret: bool, block_n: int, block_n_elem: int,
                  precision: str = "f32"):
    b, h, wd, cin = images.shape
    cout = w.shape[-1]
    ho, wo = conv_out_hw(h, stride), conv_out_hw(wd, stride)
    n = b * ho * wo
    wm = pack_phase_weights(w.reshape(kernel * kernel * cin, cout))
    bits = draw_bits(key, n, cout)

    if precision == "int8":
        wq, dq = quantize_frontend_weights(wm)
        u, hoyer_partials = p2m_phase_a_implicit_q8_pallas(
            images, wq, dq, v_th.reshape(1, 1).astype(jnp.float32),
            kernel=kernel, stride=stride, pixel_params=pixel_params,
            block_n=block_n, interpret=interpret)
    else:
        u, hoyer_partials = p2m_phase_a_implicit_pallas(
            images, wm, v_th.reshape(1, 1).astype(jnp.float32),
            kernel=kernel, stride=stride, pixel_params=pixel_params,
            block_n=block_n, interpret=interpret)
    theta = combine_hoyer_partials(hoyer_partials, v_th.astype(jnp.float32))
    bb, block_oh = blocking.implicit_block(b, h, wd, cin, kernel, stride,
                                           cout, block_n)
    out, v_partials = p2m_phase_b_pallas(
        u, theta.reshape(1, 1), bits,
        n_valid=n, c_valid=cout,
        chan=None if chan is None else chan.astype(jnp.float32),
        pixel_params=pixel_params, mtj_params=mtj_params,
        block_n=_elem_block(n, bb * block_oh * wo, block_n_elem),
        interpret=interpret)
    aux = {"theta": theta,
           **combine_v_conv_partials(v_partials, n, cout)}
    return out.reshape(b, ho, wo, cout), aux


def p2m_frontend(images: jax.Array, w: jax.Array, v_th: jax.Array,
                 key: jax.Array, *, kernel: int = 3, stride: int = 2,
                 chan: Optional[jax.Array] = None,
                 pixel_params: pixel_model.PixelCircuitParams =
                 pixel_model.DEFAULT_PIXEL,
                 mtj_params: mtj_model.MTJParams = mtj_model.DEFAULT_MTJ,
                 interpret: Optional[bool] = None,
                 block_n: Optional[int] = None,
                 block_n_elem: Optional[int] = None,
                 precision: Optional[str] = None):
    """Single-pass P2M frontend step: the patch matmul happens exactly once.

    images (B,H,W,C) in [0,1]; w (k,k,C,Cout) signed quantized weights;
    v_th () the trainable threshold scale. Pipeline (DESIGN.md §5/§9):

        implicit-im2col kernel A (in-kernel patch gather + ONE packed-weight
            matmul: u + Hoyer partials)
          -> combine_hoyer_partials (theta, scalar)
          -> kernel B (u -> voltage -> switching draw + V_CONV partials)

    Returns ``(activations, aux)`` where activations is (B,H',W',Cout)
    binary and aux carries ``theta`` plus the ``v_conv_mean/min/max`` stats —
    every aux value comes out of the kernels' partial reductions, not a
    shadow pure-JAX conv. The patch matrix is never materialized in HBM and
    the relu-split phases share one MXU pass (``dot_count: 1``).

    ``chan`` is the optional (CHAN_ROWS, Cout) per-channel device-variation
    operand for kernel B (``repro.variation.chip.channel_operands`` — pixel
    gain/offset + calibration trim + channel MTJ corner); ``None`` runs the
    nominal chip (identity rows, bit-exact pass-through). ``chan`` is a
    traced operand (not in ``static_argnames``): a lifetime-aware caller
    feeds a different aged-chip operand every microbatch against ONE
    compilation of this function (DESIGN.md §8).

    ``block_n`` / ``block_n_elem`` default to the per-shape autotuner table
    (``kernels/autotune.py``; heuristic default when untuned — a pure
    function of the shape, so a jitted caller always traces the same tiles
    for one shape and the jit cache stays at one entry per shape).
    ``precision`` likewise: explicit ``"f32"``/``"int8"`` wins, otherwise
    the tuned per-shape choice. ``"int8"`` swaps kernel A for the quantized
    implicit-im2col kernel (DESIGN.md §14) — kernel B and the whole device
    chain are THE SAME kernel either way, so phase-B parity is structural.
    """
    n, k_eff, cout = _frontend_shape(images, kernel, stride, w)
    bn, bne = autotune.resolve(n, k_eff, cout, block_n, block_n_elem)
    prec = autotune.resolve_precision(n, k_eff, cout, precision)
    step = functools.partial(
        _p2m_frontend, kernel=kernel, stride=stride,
        pixel_params=pixel_params, mtj_params=mtj_params,
        interpret=platform.pallas_interpret(interpret),
        block_n=bn, block_n_elem=bne, precision=prec)
    return _on_each_device(step)(images, w, v_th, key, chan)


@functools.partial(jax.jit, static_argnames=("kernel", "stride",
                                             "pixel_params", "mtj_params",
                                             "interpret", "block_n",
                                             "precision", "on_device_rng"))
def _p2m_frontend_fused(images: jax.Array, w: jax.Array, v_th: jax.Array,
                        theta: jax.Array, key: jax.Array,
                        chan: Optional[jax.Array], *,
                        kernel: int, stride: int,
                        pixel_params: pixel_model.PixelCircuitParams,
                        mtj_params: mtj_model.MTJParams,
                        interpret: bool, block_n: int,
                        precision: str = "f32",
                        on_device_rng: bool = False):
    b, h, wd, cin = images.shape
    cout = w.shape[-1]
    ho, wo = conv_out_hw(h, stride), conv_out_hw(wd, stride)
    n = b * ho * wo
    wm = pack_phase_weights(w.reshape(kernel * kernel * cin, cout))
    v_th2 = v_th.reshape(1, 1).astype(jnp.float32)
    theta2 = theta.reshape(1, 1).astype(jnp.float32)
    chan_op = None if chan is None else chan.astype(jnp.float32)
    if on_device_rng:
        bits, rng_seed = None, rng_seed_from_key(key)
    else:
        bits, rng_seed = draw_bits(key, n, cout), None
    if precision == "int8":
        wq, dq = quantize_frontend_weights(wm)
        out, srow = p2m_fused_stream_q8_pallas(
            images, wq, dq, v_th2, theta2, bits, chan_op,
            kernel=kernel, stride=stride, pixel_params=pixel_params,
            mtj_params=mtj_params, block_n=block_n, rng_seed=rng_seed,
            interpret=interpret)
        theta_fresh, v_stats, rate_sums = combine_q8_stream_stats(
            srow, v_th.astype(jnp.float32), n, cout)
        aux = {"theta": theta_fresh,
               "theta_used": theta.reshape(()),
               "channel_rates": rate_sums / n,
               **v_stats}
        return out.reshape(b, ho, wo, cout), aux
    out, hoyer_partials, v_partials, rate_partials = p2m_fused_stream_pallas(
        images, wm, v_th2, theta2, bits, chan_op,
        kernel=kernel, stride=stride, pixel_params=pixel_params,
        mtj_params=mtj_params, block_n=block_n, rng_seed=rng_seed,
        interpret=interpret)
    aux = {"theta": combine_hoyer_partials(hoyer_partials,
                                           v_th.astype(jnp.float32)),
           "theta_used": theta.reshape(()),
           # the rate monitor comes out of the kernel's per-block partials;
           # the frontend api layer keeps it instead of re-reducing the map
           "channel_rates": jnp.sum(rate_partials, axis=0) / n,
           **combine_v_conv_partials(v_partials, n, cout)}
    return out.reshape(b, ho, wo, cout), aux


def p2m_frontend_fused(images: jax.Array, w: jax.Array, v_th: jax.Array,
                       theta: jax.Array, key: jax.Array, *,
                       kernel: int = 3, stride: int = 2,
                       chan: Optional[jax.Array] = None,
                       pixel_params: pixel_model.PixelCircuitParams =
                       pixel_model.DEFAULT_PIXEL,
                       mtj_params: mtj_model.MTJParams =
                       mtj_model.DEFAULT_MTJ,
                       interpret: Optional[bool] = None,
                       block_n: Optional[int] = None,
                       block_n_elem: Optional[int] = None,
                       precision: Optional[str] = None,
                       on_device_rng: bool = False):
    """Fused streaming frontend step: the whole sensor in ONE kernel pass.

    Same contract as ``p2m_frontend`` except the switching draw runs at the
    CARRIED threshold ``theta`` (a () or (1,1) array — typically the
    previous microbatch's EMA held by ``serving.VisionEngine``) instead of
    this batch's own global Hoyer reduction, which removes the only
    cross-block serialization point: u never round-trips through HBM and
    there is a single kernel dispatch per step.

    aux carries BOTH thresholds: ``theta`` is the FRESH value combined from
    this batch's in-kernel Hoyer partials (the caller's drift guard / EMA
    update input) and ``theta_used`` is the carried value the draws actually
    saw. With ``theta`` pinned to the exact pipeline's threshold the
    activations are bit-identical to ``p2m_frontend`` (regression-tested).
    ``block_n_elem`` is accepted for signature parity but unused (there is
    no kernel B on this path).

    ``precision`` (explicit or the tuned per-shape choice) selects the f32
    or int8 fused kernel — the int8 variant quantizes both packed-matmul
    operands and emits the identical aux from ONE packed stats row
    (DESIGN.md §14). ``on_device_rng=True`` (real TPUs, ``interpret=False``
    only) generates the draw words in-kernel via the per-core PRNG seeded
    from ``key`` instead of streaming ``draw_bits`` from HBM.
    """
    del block_n_elem
    n, k_eff, cout = _frontend_shape(images, kernel, stride, w)
    bn = autotune.resolve_fused(n, k_eff, cout, block_n)
    prec = autotune.resolve_precision(n, k_eff, cout, precision)
    step = functools.partial(
        _p2m_frontend_fused, kernel=kernel, stride=stride,
        pixel_params=pixel_params, mtj_params=mtj_params,
        interpret=platform.pallas_interpret(interpret), block_n=bn,
        precision=prec, on_device_rng=on_device_rng)
    return _on_each_device(step)(images, w, v_th,
                                 jnp.asarray(theta, jnp.float32), key, chan)


# --- fleet (chip-axis) frontend steps ----------------------------------------
#
# A fleet step runs G distinct chips in one dispatch: frames, rng keys and
# the per-chip kernel-B channel operands gain a leading chip axis and the
# single-chip kernels are vmapped over it — the batching rule turns the chip
# axis into an outer grid dimension of the SAME per-chip kernel, so a fleet
# step is bit-identical to G separate single-chip calls (regression-tested)
# and the tile table is consulted once at the per-chip (N, K, C) key
# (``autotune.resolve_fleet`` — the table cannot grow with fleet size).

@functools.partial(jax.jit, static_argnames=("kernel", "stride",
                                             "pixel_params", "mtj_params",
                                             "interpret", "block_n",
                                             "block_n_elem", "precision"))
def _p2m_frontend_fleet(images: jax.Array, w: jax.Array, v_th: jax.Array,
                        keys: jax.Array, chan: Optional[jax.Array], *,
                        kernel: int, stride: int,
                        pixel_params: pixel_model.PixelCircuitParams,
                        mtj_params: mtj_model.MTJParams,
                        interpret: bool, block_n: int, block_n_elem: int,
                        precision: str = "f32"):
    step = functools.partial(_p2m_frontend, kernel=kernel, stride=stride,
                             pixel_params=pixel_params, mtj_params=mtj_params,
                             interpret=interpret, block_n=block_n,
                             block_n_elem=block_n_elem, precision=precision)
    if chan is None:
        return jax.vmap(lambda im, k: step(im, w, v_th, k, None))(images,
                                                                  keys)
    return jax.vmap(lambda im, k, ch: step(im, w, v_th, k, ch))(images, keys,
                                                                chan)


def p2m_frontend_fleet(images: jax.Array, w: jax.Array, v_th: jax.Array,
                       keys: jax.Array, *, kernel: int = 3, stride: int = 2,
                       chan: Optional[jax.Array] = None,
                       pixel_params: pixel_model.PixelCircuitParams =
                       pixel_model.DEFAULT_PIXEL,
                       mtj_params: mtj_model.MTJParams = mtj_model.DEFAULT_MTJ,
                       interpret: Optional[bool] = None,
                       block_n: Optional[int] = None,
                       block_n_elem: Optional[int] = None,
                       precision: Optional[str] = None):
    """Exact two-kernel frontend over a leading chip axis.

    images (G,B,H,W,C); keys (G, key) one rng key per chip row; ``chan``
    (G, CHAN_ROWS, Cout) per-chip variation operands or None (a fleet of
    nominal chips). Weights are fleet-shared (the conv is chip-independent;
    only kernel B's channel operand is per-chip). Returns (acts, aux) with a
    leading G axis on every leaf.

    The vmap lives INSIDE a jitted inner (mirroring ``p2m_frontend``): a
    bare ``jax.vmap(...)`` call re-traces on every invocation — the inner
    jit inlines under vmap tracers — which costs ~10x wall per step in
    interpret mode. Here each (G, B) shape compiles once.
    """
    g = images.shape[0]
    n, k_eff, cout = _frontend_shape(images[0], kernel, stride, w)
    bn, bne = autotune.resolve_fleet(g, n, k_eff, cout, block_n, block_n_elem)
    prec = autotune.resolve_precision(n, k_eff, cout, precision)
    return _p2m_frontend_fleet(images, w, v_th, keys, chan,
                               kernel=kernel, stride=stride,
                               pixel_params=pixel_params,
                               mtj_params=mtj_params,
                               interpret=platform.pallas_interpret(interpret),
                               block_n=bn, block_n_elem=bne, precision=prec)


def p2m_frontend_fused_fleet(images: jax.Array, w: jax.Array,
                             v_th: jax.Array, theta: jax.Array,
                             keys: jax.Array, *, kernel: int = 3,
                             stride: int = 2,
                             chan: Optional[jax.Array] = None,
                             pixel_params: pixel_model.PixelCircuitParams =
                             pixel_model.DEFAULT_PIXEL,
                             mtj_params: mtj_model.MTJParams =
                             mtj_model.DEFAULT_MTJ,
                             interpret: Optional[bool] = None,
                             block_n: Optional[int] = None,
                             precision: Optional[str] = None):
    """Fused single-kernel streaming step over a leading chip axis.

    ``theta`` is (G,) — each chip row draws at ITS OWN carried threshold
    (a fleet of independent streams, each with its own theta EMA). Same
    fleet-axis contract as ``p2m_frontend_fleet`` otherwise, including the
    jitted-inner-vmap structure (one compilation per (G, B) shape), and the
    quantized kernels inherit unchanged — ``precision`` resolves at the
    per-chip shape key exactly like the tiles.
    """
    g = images.shape[0]
    n, k_eff, cout = _frontend_shape(images[0], kernel, stride, w)
    bn = autotune.resolve_fleet_fused(g, n, k_eff, cout, block_n)
    prec = autotune.resolve_precision(n, k_eff, cout, precision)
    return _p2m_frontend_fused_fleet(images, w, v_th,
                                     jnp.asarray(theta, jnp.float32), keys,
                                     chan, kernel=kernel, stride=stride,
                                     pixel_params=pixel_params,
                                     mtj_params=mtj_params,
                                     interpret=platform.pallas_interpret(
                                         interpret),
                                     block_n=bn, precision=prec)


@functools.partial(jax.jit, static_argnames=("kernel", "stride",
                                             "pixel_params", "mtj_params",
                                             "interpret", "block_n",
                                             "precision"))
def _p2m_frontend_fused_fleet(images: jax.Array, w: jax.Array,
                              v_th: jax.Array, theta: jax.Array,
                              keys: jax.Array, chan: Optional[jax.Array], *,
                              kernel: int, stride: int,
                              pixel_params: pixel_model.PixelCircuitParams,
                              mtj_params: mtj_model.MTJParams,
                              interpret: bool, block_n: int,
                              precision: str = "f32"):
    step = functools.partial(_p2m_frontend_fused, kernel=kernel,
                             stride=stride, pixel_params=pixel_params,
                             mtj_params=mtj_params, interpret=interpret,
                             block_n=block_n, precision=precision)
    if chan is None:
        return jax.vmap(
            lambda im, th, k: step(im, w, v_th, th, k, None))(images, theta,
                                                              keys)
    return jax.vmap(
        lambda im, th, k, ch: step(im, w, v_th, th, k, ch))(images, theta,
                                                            keys, chan)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_kv",
                                             "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, block_q: int = 128,
                    block_kv: int = 128,
                    interpret: Optional[bool] = None) -> jax.Array:
    """GQA-aware wrapper: (B,S,H,D) x (B,S,Hkv,D) -> (B,S,H,D)."""
    h, hkv = q.shape[2], k.shape[2]
    if hkv != h:
        rep = h // hkv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    d = q.shape[-1]
    scale = d ** -0.5
    dp = -d % 128
    if dp:
        # padded q/k lanes contribute 0 to scores; padded v lanes sliced off
        q = jnp.pad(q, ((0, 0), (0, 0), (0, 0), (0, dp)))
        k = jnp.pad(k, ((0, 0), (0, 0), (0, 0), (0, dp)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, dp)))
    out = flash_attention_pallas(q, k, v, causal=causal, block_q=block_q,
                                 block_kv=block_kv, interpret=interpret,
                                 scale=scale)
    return out[..., :d]
