"""Shared padding / blocking / conv-geometry helpers for the Pallas frontend.

One home for the little integer lemmas that used to be split across
``ops.py`` (``_pad_to``, ``_elem_block``) and are now also needed by the tile
autotuner (``kernels/autotune.py``): SAME-convolution geometry, lane/row
padding, and divisor-constrained block sizing. Everything here is pure
Python/jnp on static shapes — safe to call at trace time (the choices are
deterministic functions of the shape, so a jitted caller never sees two
different blockings for one shape).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp


def pad_to(x: jax.Array, axis: int, mult: int) -> jax.Array:
    """Zero-pad ``axis`` up to the next multiple of ``mult`` (no-op if aligned)."""
    rem = x.shape[axis] % mult
    if rem == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[axis] = (0, mult - rem)
    return jnp.pad(x, pad)


def conv_out_hw(h: int, stride: int) -> int:
    """SAME-padding output extent: ceil(h / stride)."""
    return -(-h // stride)


def same_pads(h: int, w: int, kernel: int, stride: int
              ) -> Tuple[Tuple[int, int], Tuple[int, int]]:
    """SAME-convolution padding amounts ((lo_h, hi_h), (lo_w, hi_w)).

    Matches ``jax.lax.conv_general_dilated(..., "SAME")`` exactly: output
    extent ceil(h/stride) with the extra element on the HIGH side for
    asymmetric strided cases. Odd kernels only (an even kernel has no
    SAME-consistent symmetric interpretation — callers reject it up front).
    """
    ho, wo = conv_out_hw(h, stride), conv_out_hw(w, stride)
    pad_h = max((ho - 1) * stride + kernel - h, 0)
    pad_w = max((wo - 1) * stride + kernel - w, 0)
    return ((pad_h // 2, pad_h - pad_h // 2),
            (pad_w // 2, pad_w - pad_w // 2))


def pad_same(images: jax.Array, kernel: int, stride: int) -> jax.Array:
    """NHWC SAME zero-padding (the only image copy the implicit-im2col
    pipeline makes — the patch matrix itself never exists in HBM)."""
    _, h, w, _ = images.shape
    (plo_h, phi_h), (plo_w, phi_w) = same_pads(h, w, kernel, stride)
    return jnp.pad(images, ((0, 0), (plo_h, phi_h), (plo_w, phi_w), (0, 0)))


def largest_divisor_at_most(n: int, cap: int) -> int:
    """The largest divisor of ``n`` that is <= ``cap`` (>= 1)."""
    cap = max(min(cap, n), 1)
    for d in range(cap, 0, -1):
        if n % d == 0:
            return d
    return 1


def row_block(ho: int, wo: int, block_n: int) -> int:
    """Output-row group for the implicit-im2col kernel A grid.

    Kernel A processes ``block_oh`` whole output rows (= ``block_oh * wo``
    patch rows) per grid step; ``block_oh`` must divide ``ho`` so the grid
    tiles exactly. Returns the largest divisor of ``ho`` whose patch-row
    count stays within the requested ``block_n`` target (>= 1 row).
    """
    return largest_divisor_at_most(ho, max(block_n // max(wo, 1), 1))


def a_block_geometry(b: int, ho: int, wo: int, block_n: int
                     ) -> Tuple[int, int]:
    """(frames per block ``bb``, output rows per block ``block_oh``) for the
    implicit-im2col kernel A.

    Blocks must hold whole output rows (``block_oh`` divides ``ho``) so each
    grid step's patch rows are contiguous in ``ops.im2col`` order; multiple
    frames per step (``bb > 1``, a divisor of ``b``) are only allowed when a
    step covers the full frame (``block_oh == ho``) for the same reason.
    The resulting patch-row block is ``bb * block_oh * wo <= max(block_n,
    wo)`` (at least one output row).
    """
    block_oh = row_block(ho, wo, block_n)
    bb = 1
    if block_oh == ho:
        bb = largest_divisor_at_most(b, max(block_n // (ho * wo), 1))
    return bb, block_oh


# --- VMEM budget of one grid step -------------------------------------------
#
# A TPU kernel's per-step buffers live in scoped VMEM, whose default limit is
# 16 MiB on a v5e; Mosaic refuses a kernel whose step needs more. Arrays are
# tiled (8 sublanes x 128 lanes) there, so every width below is padded to
# that tile. The frontend kernels keep their estimate within 3/4 of the
# limit. The estimate is an upper bound calibrated by compiling for a
# described v5e (tests/test_tpu_compile.py): at 32x32x3 frames, K=27, C=32
# the compiler's own scoped allocation is ~4.4 KiB per patch row for the
# fused kernel and ~4.3 KiB for kernel B, against 7.1 and 4.0 KiB here.
VMEM_BUDGET = 12 * 2 ** 20


def _tiled(n: int, tile: int) -> int:
    return -(-n // tile) * tile


def row_vmem_bytes(k_eff: int, c_out: int) -> int:
    """VMEM one patch row costs a grid step: double-buffered f32 output,
    uint16 draw words and f32 u, the K-wide patch row, and three f32
    epilogue temporaries on 2C lanes (the rest stays in vector registers)."""
    return (2 * (4 + 2 + 4) * _tiled(c_out, 128)
            + 4 * (_tiled(k_eff, 128) + 3 * _tiled(2 * c_out, 128)))


def elem_rows_cap(c_out: int) -> int:
    """Most rows an elementwise kernel-B step may hold in VMEM."""
    return max(VMEM_BUDGET // row_vmem_bytes(0, c_out), 8)


def implicit_block(b: int, h: int, w: int, cin: int, kernel: int,
                   stride: int, c_out: int, block_n: int) -> Tuple[int, int]:
    """``a_block_geometry`` for an implicit-im2col kernel, with the
    patch-row target capped by the VMEM budget: each step holds whole
    SAME-padded frames (channels on the lane axis) plus
    ``row_vmem_bytes`` per patch row."""
    ho, wo = conv_out_hw(h, stride), conv_out_hw(w, stride)
    (plo_h, phi_h), (plo_w, phi_w) = same_pads(h, w, kernel, stride)
    hp, wp = h + plo_h + phi_h, w + plo_w + phi_w
    frame = hp * _tiled(wp, 8) * _tiled(cin, 128) * 4
    row = row_vmem_bytes(kernel * kernel * cin, c_out)
    per_frame = frame + ho * wo * row
    if per_frame <= VMEM_BUDGET:
        cap = (VMEM_BUDGET // per_frame) * ho * wo
    else:
        cap = max((VMEM_BUDGET - frame) // row, wo)
    return a_block_geometry(b, ho, wo, min(block_n, cap))


def elem_block(n: int, block_n: int, block_n_elem: int) -> int:
    """Largest kernel-B row block <= block_n_elem that tiles n exactly.

    Kernel B is elementwise (no MXU tile), so it runs profitably with much
    larger blocks than the matmul kernel; n is already a multiple of block_n.
    """
    blk = min(block_n_elem, n)
    blk -= blk % block_n
    while blk > block_n and n % blk:
        blk -= block_n
    return max(blk, block_n)
