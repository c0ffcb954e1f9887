"""Causal flash attention as a Pallas TPU kernel.

Online-softmax attention with an explicit (q-block, kv-block) grid. Unlike
the pure-JAX chunked scan in models/blocks.py (whose HLO computes every
(i, j) block and masks), the kernel SKIPS fully-masked kv blocks via
``pl.when`` — on TPU this halves causal-attention FLOPs, which is exactly the
gap the §Perf log attributes to "causal waste" in the XLA path.

Grid: (batch*heads, n_q, n_kv), kv innermost so the f32 accumulator scratch
carries across kv steps in VMEM. Block shapes are (block_q, d) / (block_kv,
d) with d padded to 128 lanes by ops.py — MXU-aligned.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import platform

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            block_q, block_kv, causal, scale):
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # causal: skip kv blocks strictly in the future of this whole q block
    run = (not causal) or (kj * block_kv <= qi * block_q + block_q - 1)

    @pl.when(run)
    def _compute():
        q = q_ref[0]                          # (block_q, d)
        k = k_ref[0]                          # (block_kv, d)
        v = v_ref[0]
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
        if causal:
            qpos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 0)
            kpos = kj * block_kv + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_kv), 1)
            s = jnp.where(qpos >= kpos, s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        m_safe = jnp.where(m_new <= NEG_INF / 2, 0.0, m_new)
        p = jnp.exp(s - m_safe[:, None])
        if causal:
            p = jnp.where(qpos >= kpos, p, 0.0)
        alpha = jnp.where(m_prev <= NEG_INF / 2, 0.0,
                          jnp.exp(m_prev - m_safe))
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(kj == nk - 1)
    def _finish():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention_pallas(
    q: jax.Array, k: jax.Array, v: jax.Array, *,
    causal: bool = True, block_q: int = 128, block_kv: int = 128,
    interpret: Optional[bool] = None, scale: Optional[float] = None,
) -> jax.Array:
    """q, k, v: (B, S, H, D). Returns (B, S, H, D). No GQA here — callers
    expand kv heads (ops.py). ``scale`` overrides D^-0.5 (lane padding)."""
    b, s, h, d = q.shape
    block_q = min(block_q, s)
    block_kv = min(block_kv, s)
    assert s % block_q == 0 and s % block_kv == 0
    scale = d ** -0.5 if scale is None else scale

    # fold (b, h) into one grid axis; layout (BH, S, D)
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * h, s, d)

    kernel = functools.partial(_kernel, block_q=block_q, block_kv=block_kv,
                               causal=causal, scale=scale)
    out = pl.pallas_call(
        kernel,
        grid=(b * h, s // block_q, s // block_kv),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, block_kv, d), lambda bh, i, j: (bh, j, 0)),
            pl.BlockSpec((1, block_kv, d), lambda bh, i, j: (bh, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda bh, i, j: (bh, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
        ],
        interpret=platform.pallas_interpret(interpret),
    )(qf, kf, vf)
    return out.reshape(b, h, s, d).transpose(0, 2, 1, 3)
