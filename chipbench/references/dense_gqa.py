"""Plain reference for dense decoder models with grouped-query attention.

Covers the Qwen2 and Mistral architectures: RMSNorm, rotary positions
over the whole head, optional QKV bias, GQA, a SiLU-gated MLP, and tied or
untied embeddings. It reads the configuration file's published keys and
imports nothing of the system under test.

The forward pass runs one sequence in float32 with every matmul at HIGHEST
precision, one layer at a time (a scan) and attention in blocks of query
rows, so that a 16k-token sequence fits on one chip beside the served
weights. ``precision="fp8"`` is the control: the same pass with every
matmul operand rounded to float8 (e4m3) under a scale per row of the
activations and per output channel of the weights, the arithmetic a
later change to 8-bit floats would bring.

The weights are the benchmark's own (``init``), made on the device from
the seed in the layout the served model takes, so that the same arrays
serve both.
"""
from __future__ import annotations

import functools
import math
from typing import Dict

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST
_F8 = jnp.float8_e4m3fn
_F8_MAX = 448.0
Q_BLOCK = 512          # query rows per attention block


def dims(spec: Dict) -> Dict[str, int]:
    """The sizes the reference needs, from the configuration file."""
    D = spec["hidden_size"]
    H = spec["num_attention_heads"]
    pad = spec["layout"]["vocab_pad_multiple"]
    return {"L": spec["num_hidden_layers"], "D": D, "H": H,
            "K": spec["num_key_value_heads"],
            "hd": spec.get("head_dim") or D // H,
            "F": spec["intermediate_size"], "V": spec["vocab_size"],
            "Vp": -(-spec["vocab_size"] // pad) * pad}


def layout(spec: Dict) -> Dict:
    """Each weight's shape and how it is drawn: ("normal", std) or
    ("norm",) for RMSNorm scales, nested as the served model keeps them
    (layers stacked on a leading axis)."""
    d = dims(spec)
    L, D, H, K, hd, F, Vp = (d[k] for k in ("L", "D", "H", "K", "hd", "F",
                                            "Vp"))

    def w(shape, fan_in):
        return (shape, ("normal", 1.0 / math.sqrt(fan_in)))

    attn = {"norm": {"scale": ((L, D), ("norm",))},
            "wq": w((L, D, H, hd), D), "wk": w((L, D, K, hd), D),
            "wv": w((L, D, K, hd), D), "wo": w((L, H, hd, D), H * hd)}
    if spec["attention_bias"]:
        attn.update({"bq": ((L, H, hd), ("normal", 0.02)),
                     "bk": ((L, K, hd), ("normal", 0.02)),
                     "bv": ((L, K, hd), ("normal", 0.02))})
    out = {"embed": {"table": ((Vp, D), ("normal", 0.02))},
           "scan": {"sub0": {
               "attn": attn,
               "norm2": {"scale": ((L, D), ("norm",))},
               "mlp": {"wi": w((L, D, F), D), "wg": w((L, D, F), D),
                       "wo": w((L, F, D), F)}}},
           "final_norm": {"scale": ((D,), ("norm",))}}
    if not spec["tie_word_embeddings"]:
        out["unembed"] = {"table": w((D, Vp), D)}
    return out


def _is_leaf(x):
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[1], tuple)


@functools.lru_cache(maxsize=None)
def _init_program(leaves, treedef, dtype, sharding):
    def make(key):
        keys = jax.random.split(key, len(leaves))
        vals = []
        for (shape, how), k in zip(leaves, keys):
            z = jax.random.normal(k, shape, jnp.float32)
            if how[0] == "norm":     # RMSNorm scales near 1, not exactly 1
                v = 1.0 + 0.1 * z
            else:
                v = z * how[1]
            vals.append(v.astype(dtype))
        return jax.tree.unflatten(treedef, vals)
    if isinstance(sharding, tuple):              # one per leaf
        sharding = jax.tree.unflatten(treedef, sharding)
    return jax.jit(make, out_shardings=sharding)


def init(spec: Dict, seed: int, sharding=None):
    """The weights for ``seed``, made on the device in one jitted call in
    the configuration's dtype (each leaf's float32 draw fuses into its
    cast, so no float32 copy of a weight is ever held). ``sharding`` is
    one sharding for every leaf, or a tree of them in the weights'
    layout."""
    leaves, treedef = jax.tree.flatten(layout(spec), is_leaf=_is_leaf)
    if sharding is not None and not isinstance(sharding,
                                               jax.sharding.Sharding):
        sharding = tuple(treedef.flatten_up_to(sharding))
    seed %= 2 ** 64
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                             seed >> 32)
    return _init_program(tuple(leaves), treedef, spec["torch_dtype"],
                         sharding)(key)


def _q8(x, axis):
    """Round ``x`` to float8 e4m3 under a scale per slice along ``axis``
    (the contraction axis), and back to float32."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / _F8_MAX, 1.0)
    return (x / scale).astype(_F8).astype(jnp.float32) * scale


def _mm(eq, a, b, fp8, a_axis, b_axis):
    if fp8:
        a, b = _q8(a, a_axis), _q8(b, b_axis)
    return jnp.einsum(eq, a, b, precision=_HI)


def _rms(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def forward(params, tokens, rows, spec: Dict, precision: str = "float32"):
    """Logits [len(rows), V] at positions ``rows`` of one sequence
    ``tokens`` [S] (S a multiple of ``Q_BLOCK``; attention is causal, so
    right padding leaves earlier positions alone), over the real vocabulary.
    """
    fp8 = {"float32": False, "fp8": True}[precision]
    d = dims(spec)
    H, K, hd = d["H"], d["K"], d["hd"]
    G = H // K
    S = tokens.shape[0]
    eps, theta = spec["rms_norm_eps"], spec["rope_theta"]
    pos = jnp.arange(S, dtype=jnp.float32)
    mm = functools.partial(_mm, fp8=fp8)

    def f32(tree):
        return jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), tree)

    def layer(x, p):
        p = f32(p)
        a = p["attn"]
        h = _rms(x, a["norm"]["scale"], eps)
        q = mm("sd,dhk->shk", h, a["wq"], a_axis=1, b_axis=0)
        k = mm("sd,dhk->shk", h, a["wk"], a_axis=1, b_axis=0)
        v = mm("sd,dhk->shk", h, a["wv"], a_axis=1, b_axis=0)
        if spec["attention_bias"]:
            q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
        q = _rope(q, pos, theta).reshape(S, K, G, hd)
        k = _rope(k, pos, theta)

        def block(_, i):
            qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK, 0)
            s = mm("qkgd,tkd->kgqt", qb, k, a_axis=3, b_axis=2) \
                / math.sqrt(hd)
            qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
            s = jnp.where(qpos[:, None] >= jnp.arange(S)[None, :], s,
                          -jnp.inf)
            o = mm("kgqt,tkd->qkgd", jax.nn.softmax(s, axis=-1), v,
                   a_axis=3, b_axis=0)
            return None, o.reshape(Q_BLOCK, H, hd)

        _, o = jax.lax.scan(block, None, jnp.arange(S // Q_BLOCK))
        o = o.reshape(S, H, hd)
        x = x + mm("shk,hkd->sd", o, a["wo"], a_axis=(1, 2), b_axis=(0, 1))
        m = p["mlp"]
        h = _rms(x, p["norm2"]["scale"], eps)
        up = mm("sd,df->sf", h, m["wi"], a_axis=1, b_axis=0)
        act = jax.nn.silu(up) * mm("sd,df->sf", h, m["wg"], a_axis=1,
                                   b_axis=0)
        return x + mm("sf,fd->sd", act, m["wo"], a_axis=1, b_axis=0), None

    x = jnp.asarray(params["embed"]["table"][tokens], jnp.float32)
    x, _ = jax.lax.scan(layer, x, params["scan"]["sub0"])
    x = _rms(x[rows], jnp.asarray(params["final_norm"]["scale"],
                                  jnp.float32), eps)
    if spec["tie_word_embeddings"]:
        out = mm("sd,vd->sv", x, f32(params["embed"]["table"]), a_axis=1,
                 b_axis=1)
    else:
        out = mm("sd,dv->sv", x, f32(params["unembed"]["table"]), a_axis=1,
                 b_axis=0)
    return out[:, :d["V"]]
