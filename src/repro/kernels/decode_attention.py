"""Pallas TPU flash-decode: one query token vs a (ring) KV cache.

Grid (B, nw): the window axis is innermost/arbitrary with (m, l, acc) VMEM
scratch carried across KV blocks. ``cache_len`` arrives via scalar prefetch
(PrefetchScalarGridSpec) so validity masks are computed on-core without a
host round-trip. GQA is native: no KV repetition — q is [K, G, hd] and each
KV block is [bw, K, hd].
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


def _kernel(clen_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            block_w: int, nw: int, W: int, window, scale, G: int):
    b = pl.program_id(0)
    wi = pl.program_id(1)

    @pl.when(wi == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0]                                     # [K*G, hd] (heads-major)
    k = k_ref[0]                                     # [bw, K, hd]
    v = v_ref[0]
    bw, K, hd = k.shape
    qg = q.reshape(K, G, hd)
    # scores [K, G, bw]
    s = jax.lax.dot_general(qg, k, (((2,), (2,)), ((0,), (1,))),
                            preferred_element_type=jnp.float32) * scale

    clen = clen_ref[b]
    pos = wi * block_w + jax.lax.broadcasted_iota(jnp.int32, (1, bw), 1)[0]
    n_valid = jnp.minimum(clen + 1, W)
    valid = pos < n_valid
    if window is not None:
        age = (clen % W) - pos
        age = jnp.where(age < 0, age + W, age)
        valid &= age < jnp.minimum(window, n_valid + 1)
    s = jnp.where(valid[None, None, :], s, NEG_INF)

    m_prev = m_scr[...]                              # [K, G]
    m_cur = jnp.max(s, axis=-1)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new[..., None])                # [K, G, bw]
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=-1)
    m_scr[...] = m_new
    # acc [K, G, hd] += p @ v  (batched over K)
    acc_scr[...] = acc_scr[...] * corr[..., None] + jax.lax.dot_general(
        p.astype(v.dtype), v, (((2,), (0,)), ((0,), (1,))),
        preferred_element_type=jnp.float32)

    @pl.when(wi == nw - 1)
    def _finalize():
        out = acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)[..., None]
        o_ref[0] = out.reshape(K * G, hd).astype(o_ref.dtype)


def decode_attention(q, k_cache, v_cache, cache_len, *, q_per_kv: int,
                     window: Optional[int] = None, block_w: int = 256,
                     interpret: Optional[bool] = None):
    """q [B,1,H,hd]; caches [B,W,K,hd]; cache_len scalar or [B] int32."""
    B, W, K, hd = k_cache.shape
    H = q.shape[2]
    G = q_per_kv
    block_w = min(block_w, W)
    Wp = -(-W // block_w) * block_w
    if Wp == W:
        # capacity already block-aligned (the serving engine rounds it up):
        # no per-step copy of the whole cache
        kp, vp = k_cache, v_cache
    else:
        kp = jnp.pad(k_cache, ((0, 0), (0, Wp - W), (0, 0), (0, 0)))
        vp = jnp.pad(v_cache, ((0, 0), (0, Wp - W), (0, 0), (0, 0)))
    nw = Wp // block_w
    clen = jnp.asarray(cache_len, jnp.int32)
    if clen.ndim == 0:
        clen = jnp.broadcast_to(clen, (B,))
    qf = q.reshape(B, H, hd)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, nw),
        in_specs=[
            pl.BlockSpec((1, H, hd), lambda b, wi, clen_ref: (b, 0, 0)),
            pl.BlockSpec((1, block_w, K, hd), lambda b, wi, clen_ref: (b, wi, 0, 0)),
            pl.BlockSpec((1, block_w, K, hd), lambda b, wi, clen_ref: (b, wi, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, H, hd), lambda b, wi, clen_ref: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((K, G), jnp.float32),
            pltpu.VMEM((K, G), jnp.float32),
            pltpu.VMEM((K, G, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, block_w=block_w, nw=nw, W=W, window=window,
                          scale=1.0 / math.sqrt(hd), G=G),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, hd), q.dtype),
        interpret=interpret_mode(interpret),
    )(clen, qf, kp, vp)
    return out[:, None]
