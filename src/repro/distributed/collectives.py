"""shard_map collectives: sequence-parallel flash-decode with LSE combine.

The baseline decode path (models/attention.decode_attention under pjit) lets
SPMD partition the softmax over the sequence-sharded KV cache. This module is
the EXPLICIT version — each device computes flash-decode partials (m, l, o)
over its local KV shard and combines with a single fused ``psum`` — used by
the §Perf hillclimb to control the collective schedule precisely (one
all-reduce of [B,H,hd+2] instead of separate max/sum/value reductions).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

NEG_INF = -1e30


def _local_flash_decode(q, k_shard, v_shard, valid):
    """q [B,K,G,hd]; k/v [B,Wl,K,hd]; valid [B,Wl] -> (m,l,o) partials."""
    s = jnp.einsum("bkgh,bwkh->bkgw", q, k_shard,
                   preferred_element_type=jnp.float32) / math.sqrt(q.shape[-1])
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    m = jnp.max(s, axis=-1)                                   # [B,K,G]
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bkgw,bwkh->bkgh", p.astype(v_shard.dtype), v_shard,
                   preferred_element_type=jnp.float32)
    return m, l, o


def make_seqpar_decode_attention(mesh: Mesh, *, batch_axes=("data",),
                                 seq_axis: str = "model"):
    """Returns decode_attn_fn(q, k_cache, v_cache, cache_len, *, q_per_kv,
    window) with cache sequence-sharded over ``seq_axis``."""

    def decode_attn(q, k_cache, v_cache, cache_len, *, q_per_kv: int,
                    window: Optional[int] = None):
        B, W, K, hd = k_cache.shape
        H = q.shape[2]
        n_shards = mesh.shape[seq_axis]
        Wl = W // n_shards
        b = batch_axes if len(batch_axes) > 1 else (batch_axes[0] if batch_axes else None)

        def body(q_l, k_l, v_l, clen):
            # local seq range of this shard
            r = jax.lax.axis_index(seq_axis)
            pos = r * Wl + jnp.arange(Wl)
            clen_b = jnp.asarray(clen)
            if clen_b.ndim == 0:
                clen_b = clen_b[None]
            n_valid = jnp.minimum(clen_b + 1, W)
            valid = pos[None, :] < n_valid[:, None]
            if window is not None:
                age = (clen_b % W)[:, None] - pos[None, :]
                age = jnp.where(age < 0, age + W, age)
                valid &= age < jnp.minimum(window, n_valid + 1)[:, None]
            qg = q_l.reshape(q_l.shape[0], K, q_per_kv, hd)
            m, l, o = _local_flash_decode(qg, k_l, v_l, valid)
            # one fused LSE combine: psum of (exp-shifted l, o) after global max
            m_g = jax.lax.pmax(m, seq_axis)
            corr = jnp.exp(m - m_g)
            l_g = jax.lax.psum(l * corr, seq_axis)
            o_g = jax.lax.psum(o * corr[..., None], seq_axis)
            out = o_g / jnp.maximum(l_g, 1e-30)[..., None]
            return out.reshape(q_l.shape[0], 1, H, hd).astype(q_l.dtype)

        clen_spec = P() if jnp.asarray(cache_len).ndim == 0 else P(b)
        return jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(b, None, None, None),          # q [B,1→B,H,hd] flat
                      P(b, seq_axis, None, None),       # k cache
                      P(b, seq_axis, None, None),       # v cache
                      clen_spec),
            out_specs=P(b, None, None, None),
            check_vma=False,
        )(q.reshape(q.shape[0], H, hd)[:, None], k_cache, v_cache, cache_len)

    return decode_attn
