"""Pallas TPU flash attention (causal + GQA-repeated + sliding window).

Grid (B, H, nq, nk): outer three parallel, innermost arbitrary — the (m, l,
acc) online-softmax state lives in VMEM scratch and is carried across the nk
iterations for each q block. Block shapes are MXU-aligned (bq × hd, bkv × hd,
multiples of 128 on the lane dim); K/V stream HBM→VMEM one block per step.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            block_q: int, block_kv: int, nk: int, window, scale, seq_t: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0]                                   # [bq, hd]
    k = k_ref[0, 0]                                   # [bkv, hd]
    v = v_ref[0, 0]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 0)
    k_pos = ki * block_kv + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_kv), 1)
    mask = q_pos >= k_pos
    if window is not None:
        mask &= (q_pos - k_pos) < window
    mask &= k_pos < seq_t
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]                               # [bq, 1]
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    m_scr[...] = m_new
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _finalize():
        o_ref[0, 0] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                       ).astype(o_ref.dtype)


def flash_attention(q, k, v, *, window: Optional[int] = None,
                    block_q: int = 128, block_kv: int = 128,
                    interpret: Optional[bool] = None):
    """q,k,v: [B, S(T), H, hd] (KV already repeated to H heads). Causal."""
    B, S, H, hd = q.shape
    T = k.shape[1]
    block_q = min(block_q, S)
    block_kv = min(block_kv, T)
    Sp = -(-S // block_q) * block_q
    Tp = -(-T // block_kv) * block_kv
    qp = jnp.pad(q, ((0, 0), (0, Sp - S), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, Tp - T), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, Tp - T), (0, 0), (0, 0)))
    # layout [B, H, S, hd] so blocks are contiguous (lane dim = hd)
    qt = jnp.moveaxis(qp, 2, 1)
    kt = jnp.moveaxis(kp, 2, 1)
    vt = jnp.moveaxis(vp, 2, 1)
    nq, nk = Sp // block_q, Tp // block_kv
    grid = (B, H, nq, nk)
    scale = 1.0 / math.sqrt(hd)

    out = pl.pallas_call(
        functools.partial(_kernel, block_q=block_q, block_kv=block_kv, nk=nk,
                          window=window, scale=scale, seq_t=T),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd), lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_kv, hd), lambda b, h, qi, ki: (b, h, ki, 0)),
            pl.BlockSpec((1, 1, block_kv, hd), lambda b, h, qi, ki: (b, h, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd), lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sp, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, hd), jnp.float32),
        ],
        interpret=interpret_mode(interpret),
    )(qt, kt, vt)
    return jnp.moveaxis(out, 1, 2)[:, :S]
