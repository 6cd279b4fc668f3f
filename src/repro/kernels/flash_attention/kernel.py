"""Blockwise-causal flash attention as a Pallas TPU kernel.

Grid: (batch*heads, n_q_blocks, n_k_blocks) — the k axis is innermost, so on
TPU the same (bh, q) output block stays resident in VMEM while k blocks
stream through (sequential grid), carrying the online-softmax statistics
(m, l) in VMEM scratch.  BlockSpecs tile q/k/v/o as (BQ, D) / (BK, D) VMEM
tiles with D padded to a lane multiple (128).

Causal + sliding-window masking is applied per tile; fully-masked k tiles
still iterate (Pallas grids are dense) but skip the matmul via @pl.when —
the hillclimbed variant in ops.py shrinks the k-range per q block instead.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_scratch, l_scratch,
                 acc_scratch, *, scale: float, causal: bool, window: int,
                 block_q: int, block_k: int, n_k: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scratch[...] = jnp.full_like(m_scratch, NEG_INF)
        l_scratch[...] = jnp.zeros_like(l_scratch)
        acc_scratch[...] = jnp.zeros_like(acc_scratch)

    q_start = qi * block_q
    k_start = ki * block_k

    # tile-level skip: entirely above the causal diagonal / below the window
    def relevant():
        lo = q_start - (window - 1) if window else -1
        above = k_start > q_start + block_q - 1 if causal else False
        below = (k_start + block_k - 1) < lo if window else False
        return jnp.logical_not(jnp.logical_or(above, below))

    @pl.when(relevant())
    def _compute():
        q = q_ref[0].astype(jnp.float32)                # (BQ, D)
        k = k_ref[0].astype(jnp.float32)                # (BK, D)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                  (block_q, block_k), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                  (block_q, block_k), 1)
        mask = jnp.ones((block_q, block_k), jnp.bool_)
        if causal:
            mask &= qpos >= kpos
        if window:
            mask &= (qpos - kpos) < window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scratch[...]                          # (BQ, 1)
        l_prev = l_scratch[...]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc_scratch[...] = acc_scratch[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scratch[...] = m_new
        l_scratch[...] = l_new

    @pl.when(ki == n_k - 1)
    def _finalize():
        l = jnp.maximum(l_scratch[...], 1e-30)
        o_ref[0] = (acc_scratch[...] / l).astype(o_ref.dtype)


def flash_attention_kernel(q, k, v, *, causal: bool = True, window: int = 0,
                           block_q: int = 128, block_k: int = 128,
                           interpret: bool = False) -> jax.Array:
    """q: (BH, S, D), k/v: (BH, T, D) with D a lane multiple."""
    bh, s, d = q.shape
    t = k.shape[1]
    block_q = min(block_q, s)
    block_k = min(block_k, t)
    assert s % block_q == 0 and t % block_k == 0, (s, t, block_q, block_k)
    n_q, n_k = s // block_q, t // block_k
    scale = 1.0 / math.sqrt(d)

    kernel = functools.partial(
        _attn_kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, n_k=n_k)

    return pl.pallas_call(
        kernel,
        grid=(bh, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, q_, k_: (b, q_, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, q_, k_: (b, k_, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, q_, k_: (b, k_, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, q_, k_: (b, q_, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max m
            pltpu.VMEM((block_q, 1), jnp.float32),   # running sum l
            pltpu.VMEM((block_q, d), jnp.float32),   # output accumulator
        ],
        name="flash_attention",
        interpret=interpret,
    )(q, k, v)
