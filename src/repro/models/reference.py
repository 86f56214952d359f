"""Plain float32 reference forward pass for dense decoder configurations.

Written apart from the serving model (``models/transformer.py``): a plain
scan over layers, softmax attention over an explicit causal mask,
every matmul in float32 at HIGHEST precision, no cache, no kernel, no
sharding. It is the yardstick that the served bf16 path (Pallas kernels on
a TPU) is held to at the logits level.

Covered: decoder stacks of full-attention blocks with RMSNorm, rotary
positions, optional QKV bias, GQA, a gated or plain MLP, and tied or
untied embeddings (qwen2.5, mistral-nemo, granite, chatglm). Anything else
raises.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.configs import base as cfgbase

_HI = jax.lax.Precision.HIGHEST


def check_supported(cfg):
    """Raise ValueError unless ``cfg`` is a configuration this reference
    implements."""
    why = []
    if set(cfg.pattern) != {cfgbase.ATTN}:
        why.append(f"pattern {cfg.pattern}")
    if cfg.norm_type != "rmsnorm":
        why.append(f"norm {cfg.norm_type}")
    if cfg.pos_emb != "rope":
        why.append(f"positions {cfg.pos_emb}")
    if cfg.qk_norm or cfg.logit_softcap or cfg.sliding_window or cfg.mlp_bias:
        why.append("qk-norm / softcap / window / mlp bias")
    if cfg.modality != "text" or cfg.family == "hybrid" or \
            cfg.name.startswith("gemma"):
        why.append(f"modality {cfg.modality} / family {cfg.family}")
    if why:
        raise ValueError(f"no float32 reference for {cfg.name}: "
                         + ", ".join(why))


def _rms(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, pos, theta, pct):
    """Rotate the leading ``pct`` of the head dim in two halves."""
    rot = int(x.shape[-1] * pct)
    rot -= rot % 2
    half = rot // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None] * freqs[None, :]                       # [S, half]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:rot]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., rot:]], axis=-1)


def _mm(eq, a, b):
    return jnp.einsum(eq, a, b, precision=_HI)


def reference_logits(params, tokens, cfg):
    """tokens [B, S] int32 (positions 0..S-1) -> logits [B, S, V] float32
    over the padded vocabulary. Weights are cast to float32 one layer at a
    time, so the reference fits on a chip beside the bf16 weights."""
    check_supported(cfg)
    B, S = tokens.shape
    H, K, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    G = H // K
    pos = jnp.arange(S, dtype=jnp.float32)
    causal = jnp.tril(jnp.ones((S, S), bool))

    def f32(tree):
        return jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), tree)

    def layer(x, p):
        p = f32(p)
        a = p["attn"]
        h = _rms(x, a["norm"]["scale"], cfg.norm_eps)
        q = _mm("bsd,dhk->bshk", h, a["wq"])
        k = _mm("bsd,dhk->bshk", h, a["wk"])
        v = _mm("bsd,dhk->bshk", h, a["wv"])
        if cfg.attn_bias:
            q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
        q = _rope(q, pos, cfg.rope_theta, cfg.rotary_pct)
        k = _rope(k, pos, cfg.rope_theta, cfg.rotary_pct)
        qg = q.reshape(B, S, K, G, hd)
        s = _mm("bskgd,btkd->bkgst", qg, k) / math.sqrt(hd)
        s = jnp.where(causal, s, -jnp.inf)
        o = _mm("bkgst,btkd->bskgd", jax.nn.softmax(s, axis=-1), v)
        x = x + _mm("bshk,hkd->bsd", o.reshape(B, S, H, hd), a["wo"])
        m = p["mlp"]
        h = _rms(x, p["norm2"]["scale"], cfg.norm_eps)
        up = _mm("bsd,df->bsf", h, m["wi"])
        act = jax.nn.gelu(up) if cfg.act == "gelu" else jax.nn.silu(up)
        if cfg.gated_mlp:
            act = act * _mm("bsd,df->bsf", h, m["wg"])
        return x + _mm("bsf,fd->bsd", act, m["wo"]), None

    x = jnp.asarray(params["embed"]["table"][tokens], jnp.float32)
    x, _ = jax.lax.scan(layer, x, params["scan"]["sub0"])
    x = _rms(x, jnp.asarray(params["final_norm"]["scale"], jnp.float32),
             cfg.norm_eps)
    if cfg.tie_embeddings:
        return _mm("bsd,vd->bsv", x, f32(params["embed"]["table"]))
    return _mm("bsd,dv->bsv", x, f32(params["unembed"]["table"]))
