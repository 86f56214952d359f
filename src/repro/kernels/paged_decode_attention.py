"""Pallas TPU flash-decode over a PAGED KV cache (block-table gather).

One query token per sequence attends to K/V scattered across fixed-size
pages of a shared pool (serving/kvpool.py). Same (B, nw) grid and VMEM
online-softmax scratch as ``kernels/decode_attention``, but the KV BlockSpec
index maps through the *scalar-prefetched block table*: grid step (b, wi)
DMAs pool page ``bt[b, wi]`` instead of slice ``wi`` of a dense per-slot
cache — the gather costs nothing extra because the pages-to-VMEM DMA was
happening anyway; only the page index changes. ``cache_len`` also arrives
via scalar prefetch for on-core validity masks, and to skip dead pages: the
index maps clamp the page column to the row's last live page, so steps past
it repeat the block index and the pipeline issues no copy, and the body
computes nothing there (a dead page only ever added exact zeros).

Full (non-windowed) attention only: the serving engine gates paged mode to
archs whose KV is position-causal, hence page-shareable.
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


def _kernel(clen_ref, bt_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
            acc_scr, *, page_size: int, nw: int, G: int, scale: float):
    b = pl.program_id(0)
    wi = pl.program_id(1)
    _, ps, K, hd = k_ref.shape                       # one page [ps, K, hd]

    @pl.when(wi == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    clen = clen_ref[b]

    @pl.when(wi * page_size <= clen)                 # a live position
    def _accumulate():
        q = q_ref[0]                                 # [K*G, hd] (heads-major)
        k = k_ref[0]
        v = v_ref[0]
        qg = q.reshape(K, G, hd)
        # scores [K, G, ps]
        s = jax.lax.dot_general(qg, k, (((2,), (2,)), ((0,), (1,))),
                                preferred_element_type=jnp.float32) * scale

        pos = (wi * page_size
               + jax.lax.broadcasted_iota(jnp.int32, (1, ps), 1)[0])
        valid = pos < clen + 1                       # new token written
        s = jnp.where(valid[None, None, :], s, NEG_INF)

        m_prev = m_scr[...]                          # [K, G]
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new[..., None])            # [K, G, ps]
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=-1)
        m_scr[...] = m_new
        acc_scr[...] = acc_scr[...] * corr[..., None] + \
            jax.lax.dot_general(p.astype(v.dtype), v,
                                (((2,), (0,)), ((0,), (1,))),
                                preferred_element_type=jnp.float32)

    @pl.when(wi == nw - 1)
    def _finalize():
        out = acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)[..., None]
        o_ref[0] = out.reshape(K * G, hd).astype(o_ref.dtype)


def paged_decode_attention(q, k_pool, v_pool, block_tables, cache_len, *,
                           q_per_kv: int, interpret: Optional[bool] = None):
    """q [B,1,H,hd]; pools [P, page_size, K, hd]; block_tables [B, nw] int32;
    cache_len scalar or [B] int32 (the new token's K/V must already be
    written at position ``cache_len`` through the block table)."""
    P, ps, K, hd = k_pool.shape
    B = q.shape[0]
    H = q.shape[2]
    G = q_per_kv
    nw = block_tables.shape[1]
    clen = jnp.asarray(cache_len, jnp.int32)
    if clen.ndim == 0:
        clen = jnp.broadcast_to(clen, (B,))
    bt = jnp.asarray(block_tables, jnp.int32)
    qf = q.reshape(B, H, hd)

    def page(b, wi, clen_ref, bt_ref):
        # the row's last live page holds position clen; later columns
        # repeat it, so their DMA is skipped
        last = jnp.minimum(clen_ref[b] // ps, nw - 1)
        return (bt_ref[b, jnp.minimum(wi, last)], 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                       # cache_len, block table
        grid=(B, nw),
        in_specs=[
            pl.BlockSpec((1, H, hd),
                         lambda b, wi, clen_ref, bt_ref: (b, 0, 0)),
            # the paged gather: page index comes from the prefetched table
            pl.BlockSpec((1, ps, K, hd), page),
            pl.BlockSpec((1, ps, K, hd), page),
        ],
        out_specs=pl.BlockSpec((1, H, hd),
                               lambda b, wi, clen_ref, bt_ref: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((K, G), jnp.float32),
            pltpu.VMEM((K, G), jnp.float32),
            pltpu.VMEM((K, G, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, page_size=ps, nw=nw, G=G,
                          scale=1.0 / math.sqrt(hd)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, hd), q.dtype),
        interpret=interpret_mode(interpret),
    )(clen, bt, qf, k_pool, v_pool)
    return out[:, None]
