"""Griffin / RecurrentGemma recurrent block: conv1d + RG-LRU temporal mixing.

RG-LRU recurrence (diagonal, real):
    r_t = sigmoid(w_r * x_t + b_r)          (recurrence gate)
    i_t = sigmoid(w_i * x_t + b_i)          (input gate)
    a_t = exp(c * softplus(Lambda) * (-r_t))  in log space, c = 8
    h_t = a_t * h_{t-1} + sqrt(1 - a_t^2) * (i_t * x_t)

TPU adaptation: train/prefill uses ``jax.lax.associative_scan`` over the
linear recurrence (parallel depth log S) — the Pallas kernel
(`kernels/rglru_scan.py`) implements the time-blocked sequential variant for
deployment. Gates are diagonal (per-channel), matching the block-diagonal
spirit of the published model at equal parameter count.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import kernels
from repro.distributed.sharding import constrain, per_shard
from repro.kernels import rglru_scan as _krg
from repro.models.layers import ParamDef, norm_defs

C_RGLRU = 8.0


def rglru_defs(cfg):
    D = cfg.d_model
    R = cfg.rglru_dim or D
    W = cfg.conv1d_width
    return {
        "norm": norm_defs(cfg),
        "wx": ParamDef((D, R), ("embed", "rnn"), init="scaled"),
        "wy": ParamDef((D, R), ("embed", "rnn"), init="scaled"),   # gate branch
        "conv_w": ParamDef((W, R), ("conv", "rnn"), init="scaled"),
        "conv_b": ParamDef((R,), ("rnn",), init="zeros"),
        "w_rgate": ParamDef((R,), ("rnn",), init="normal"),
        "b_rgate": ParamDef((R,), ("rnn",), init="zeros"),
        "w_igate": ParamDef((R,), ("rnn",), init="normal"),
        "b_igate": ParamDef((R,), ("rnn",), init="zeros"),
        "a_param": ParamDef((R,), ("rnn",), init="normal"),        # Lambda
        # wo contracts over R: own logical axis so serve replicates it
        # (bit-exact — see distributed/sharding.py) while train keeps TP
        "wo": ParamDef((R, D), ("rnn_in", "embed"), init="scaled"),
    }


def causal_conv1d(x, w, b, state=None, length=None):
    """Depthwise causal conv. x [B,S,R], w [W,R]; state [B,W-1,R] or None.

    ``length`` (traced scalar, optional): number of valid leading positions
    when ``x`` is right-padded (bucketed prefill) — the returned state then
    holds the inputs at positions [length-W+1, length) rather than the padded
    tail. Returns (y [B,S,R], new_state [B,W-1,R]).
    """
    W = w.shape[0]
    if state is None:
        state = jnp.zeros((x.shape[0], W - 1, x.shape[2]), x.dtype)
    xs = jnp.concatenate([state, x], axis=1)          # [B, S+W-1, R]
    y = sum(xs[:, i:i + x.shape[1]] * w[i] for i in range(W)) + b
    if W <= 1:
        new_state = state
    elif length is None:
        new_state = xs[:, -(W - 1):]
    else:
        # xs index j holds the input at position j - (W-1); the state for a
        # sequence ending at `length` is positions [length-W+1, length).
        new_state = jax.lax.dynamic_slice_in_dim(xs, length, W - 1, axis=1)
    return y.astype(x.dtype), new_state


def _gates(p, x):
    """log a_t [.., R] (f32) and gated input beta*i*x (f32)."""
    xf = x.astype(jnp.float32)
    r = jax.nn.sigmoid(xf * p["w_rgate"].astype(jnp.float32) + p["b_rgate"].astype(jnp.float32))
    i = jax.nn.sigmoid(xf * p["w_igate"].astype(jnp.float32) + p["b_igate"].astype(jnp.float32))
    log_a = -C_RGLRU * jax.nn.softplus(p["a_param"].astype(jnp.float32)) * r
    a = jnp.exp(log_a)
    beta = jnp.sqrt(jnp.maximum(1.0 - a * a, 1e-12))
    return a, beta * i * xf


def rglru_scan(p, x, h0=None, mask=None, all_states: bool = False):
    """Linear recurrence over [B,S,R] via associative scan. Returns (y, h_S),
    or (y, hh [B,S,R] f32 — the state after EVERY position) when
    ``all_states`` — the speculative verify step keeps all of them so the
    accept step can rewind to any accepted prefix with a gather.

    ``mask`` [B,S] bool: padded positions become identity steps (a=1, input=0)
    so the final state equals the state after the last *valid* position.
    """
    a, bx = _gates(p, x)                       # [B,S,R] f32
    if mask is not None:
        a = jnp.where(mask[..., None], a, 1.0)
        bx = jnp.where(mask[..., None], bx, 0.0)
    if h0 is not None:
        bx = bx.at[:, 0].add(a[:, 0] * h0.astype(jnp.float32))

    def op(l, r):
        (al, bl), (ar, br) = l, r
        return al * ar, ar * bl + br

    aa, hh = jax.lax.associative_scan(op, (a, bx), axis=1)
    return hh.astype(x.dtype), (hh if all_states else hh[:, -1])


def rglru_step(p, x, h):
    """Single decode step. x [B,1,R], h [B,R] f32 -> (y [B,1,R], h')."""
    a, bx = _gates(p, x[:, 0])
    h_new = a * h.astype(jnp.float32) + bx
    return h_new[:, None].astype(x.dtype), h_new


def apply_recurrent_mixer(p, x, cfg, *, cache=None, mode="full", length=None,
                          mask=None):
    """Full Griffin temporal-mixing branch (pre-norm handled by caller).

    x [B,S,D] -> (y [B,S,D], new_cache) with cache {"h": [B,R] f32,
    "conv": [B,W-1,R]}. ``length``/``mask`` mark the valid prefix when the
    prompt is right-padded to a prefill bucket (identity steps — a=1,
    input=0 — past the valid prefix). ``mode="verify"`` returns a staged
    record instead of a cache: per-position states the speculative accept
    step rewinds with a gather (``verify_commit``) — batched across rows,
    no replay forward.
    """
    u = jnp.einsum("bsd,dr->bsr", x, p["wx"])
    gate = jax.nn.gelu(jnp.einsum("bsd,dr->bsr", x, p["wy"]))
    # extend / verify (prefill or draft continuation) resume conv +
    # recurrence state from the cache instead of zeros
    prev_conv = cache["conv"] if mode in ("extend", "verify") else None
    h0 = cache["h"] if mode in ("extend", "verify") else None
    if mode == "decode":
        c, conv_state = causal_conv1d(u, p["conv_w"], p["conv_b"], cache["conv"])
        y, h = rglru_step(p, c, cache["h"])
    elif mode == "verify":
        # batched speculative verify: per-row draft chunks at per-row valid
        # lengths (``mask``). Nothing is committed here — the staged record
        # holds the state after EVERY draft position plus the raw conv input
        # stream, and ``verify_commit`` gathers the state at each row's
        # accepted length once the accept step has chosen it (the batched
        # replacement for the old per-slot snapshot+replay rollback).
        c, _ = causal_conv1d(u, p["conv_w"], p["conv_b"], prev_conv)
        xs = jnp.concatenate([prev_conv, u], axis=1)      # [B, S+W-1, R]
        y, hh = rglru_scan(p, c, h0=h0, mask=mask, all_states=True)
        yg = constrain(y * gate, "batch", None, "rnn_act")
        out = jnp.einsum("bsr,rd->bsd", yg, p["wo"])
        return out, {"hh": hh, "xs": xs, "h0": cache["h"]}
    elif kernels.on_tpu():
        c, conv_state = causal_conv1d(u, p["conv_w"], p["conv_b"], prev_conv,
                                      length=length)
        a, bx = _gates(p, c)
        if mask is not None:
            a = jnp.where(mask[..., None], a, 1.0)
            bx = jnp.where(mask[..., None], bx, 0.0)
        if h0 is not None:
            bx = bx.at[:, 0].add(a[:, 0] * h0.astype(jnp.float32))
        # the recurrence runs in float32, as the XLA scan does
        axes = ("batch", None, "rnn")
        y, h = per_shard(_krg.rglru_scan, (a, bx), (axes, axes),
                         (axes, ("batch", "rnn")))
        y = y.astype(c.dtype)
    else:
        c, conv_state = causal_conv1d(u, p["conv_w"], p["conv_b"], prev_conv,
                                      length=length)
        y, h = rglru_scan(p, c, h0=h0, mask=mask)
    # "rnn_act": serve gathers the R-sharded mixed output here so the wo
    # contraction is never split across devices (train/decode: no-op)
    yg = constrain(y * gate, "batch", None, "rnn_act")
    out = jnp.einsum("bsr,rd->bsd", yg, p["wo"])
    return out, {"h": h, "conv": conv_state}


def verify_commit(staged, ns, valid):
    """Rewind one RG-LRU layer's verify record to each row's accepted length.

    staged: ``{"hh" [B,S,R] f32, "xs" [B,S+W-1,R], "h0" [B,R]}`` from
    ``apply_recurrent_mixer(mode="verify")``; ns [B] = accepted inputs per
    row (1..S); valid [B] = rows that took part in this verify step (the
    rest keep their pre-verify state untouched). Returns the committed
    ``{"h", "conv"}`` cache — state exactly after the first ``ns`` inputs,
    with no replay forward.
    """
    hh, xs, h0 = staged["hh"], staged["xs"], staged["h0"]
    B, S, R = hh.shape
    W1 = xs.shape[1] - S                                   # conv width - 1
    idx = jnp.clip(ns - 1, 0, S - 1)
    h = jnp.take_along_axis(hh, idx[:, None, None], axis=1)[:, 0]
    h = jnp.where(valid[:, None], h, h0)
    # conv window after n inputs = stream positions [n-W+1, n) = xs[n:n+W-1]
    n_eff = jnp.where(valid, jnp.clip(ns, 0, S), 0)        # 0 -> old window
    conv = jax.vmap(
        lambda row, n: jax.lax.dynamic_slice_in_dim(row, n, W1, axis=0)
    )(xs, n_eff)
    return {"h": h, "conv": conv}
