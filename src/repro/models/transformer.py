"""Block assembly + scan-over-layers stack + train/prefill/decode entry points.

The layer stack is ``cfg.pattern`` repeated ``cfg.num_scan_groups`` times (a
single ``lax.scan`` over stacked params — O(1) HLO size in depth) plus an
explicit tail for patterns that don't divide ``num_layers`` (recurrentgemma:
38 = 12×(R,R,A) + (R,R)).

Caches mirror the param structure: ``{"scan": {"sub<i>": stacked}, "tail<j>":
...}`` plus a scalar ``cache_len`` carried by the caller.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro import kernels
from repro.configs import base as cfgbase
from repro.distributed.sharding import constrain, per_shard
from repro.kernels import decode_attention as _kda
from repro.kernels import flash_attention as _kfa
from repro.kernels import paged_decode_attention as _kpda
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import rglru as rglru_mod
from repro.models import xlstm as xlstm_mod
from repro.models.layers import (apply_mlp, apply_norm, embed_defs,
                                 embed_tokens, init_params, logical_axes,
                                 mlp_defs, norm_defs, param_specs,
                                 sinusoidal_pos, stack_defs, unembed)

# ---------------------------------------------------------------------------
# Per-block param defs
# ---------------------------------------------------------------------------


def block_defs(kind: str, cfg):
    if kind in (cfgbase.ATTN, cfgbase.LOCAL_ATTN):
        return {"attn": attn.attn_defs(cfg), "norm2": norm_defs(cfg), "mlp": mlp_defs(cfg)}
    if kind == cfgbase.ATTN_MOE:
        return {"attn": attn.attn_defs(cfg), "norm2": norm_defs(cfg), "moe": moe_mod.moe_defs(cfg)}
    if kind == cfgbase.RECURRENT:
        return {"rec": rglru_mod.rglru_defs(cfg), "norm2": norm_defs(cfg), "mlp": mlp_defs(cfg)}
    if kind == cfgbase.MLSTM:
        return {"mlstm": xlstm_mod.mlstm_defs(cfg)}
    if kind == cfgbase.SLSTM:
        return {"slstm": xlstm_mod.slstm_defs(cfg)}
    raise ValueError(kind)


def model_defs(cfg):
    defs: Dict[str, Any] = dict(embed_defs(cfg))
    scan = {}
    for i, kind in enumerate(cfg.pattern):
        scan[f"sub{i}"] = stack_defs(block_defs(kind, cfg), cfg.num_scan_groups)
    defs["scan"] = scan
    for j, kind in enumerate(cfg.tail_kinds):
        defs[f"tail{j}"] = block_defs(kind, cfg)
    defs["final_norm"] = norm_defs(cfg)
    return defs


# ---------------------------------------------------------------------------
# Cache defs (ShapeDtypeStructs — allocated by the serving engine / dry-run)
# ---------------------------------------------------------------------------


def block_cache_spec(kind: str, cfg, batch: int, capacity: int):
    K, hd = cfg.num_kv_heads, cfg.head_dim
    R = cfg.rglru_dim or cfg.d_model
    F2 = int(cfg.mlstm_proj_factor * cfg.d_model)
    W = cfg.conv1d_width
    cdt = jnp.dtype(cfg.dtype)
    if kind in (cfgbase.ATTN, cfgbase.ATTN_MOE):
        cap = capacity if cfg.sliding_window is None else min(capacity, cfg.sliding_window)
        return {"k": jax.ShapeDtypeStruct((batch, cap, K, hd), cdt),
                "v": jax.ShapeDtypeStruct((batch, cap, K, hd), cdt)}
    if kind == cfgbase.LOCAL_ATTN:
        cap = min(capacity, cfg.local_window or capacity)
        return {"k": jax.ShapeDtypeStruct((batch, cap, K, hd), cdt),
                "v": jax.ShapeDtypeStruct((batch, cap, K, hd), cdt)}
    if kind == cfgbase.RECURRENT:
        return {"h": jax.ShapeDtypeStruct((batch, R), jnp.float32),
                "conv": jax.ShapeDtypeStruct((batch, W - 1, R), cdt)}
    if kind == cfgbase.MLSTM:
        H = cfg.num_heads
        mhd = F2 // H
        return {"state": (jax.ShapeDtypeStruct((batch, H, mhd, mhd), jnp.float32),
                          jax.ShapeDtypeStruct((batch, H, mhd), jnp.float32),
                          jax.ShapeDtypeStruct((batch, H), jnp.float32)),
                "conv": jax.ShapeDtypeStruct((batch, W - 1, F2), cdt)}
    if kind == cfgbase.SLSTM:
        D = cfg.d_model
        st = jax.ShapeDtypeStruct((batch, D), jnp.float32)
        return {"state": (st, st, st, st)}
    raise ValueError(kind)


def _stack_spec(spec, n):
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct((n,) + s.shape, s.dtype), spec)


def cache_spec(cfg, batch: int, capacity: int):
    c: Dict[str, Any] = {"scan": {}}
    for i, kind in enumerate(cfg.pattern):
        c["scan"][f"sub{i}"] = _stack_spec(
            block_cache_spec(kind, cfg, batch, capacity), cfg.num_scan_groups)
    for j, kind in enumerate(cfg.tail_kinds):
        c[f"tail{j}"] = block_cache_spec(kind, cfg, batch, capacity)
    return c


def init_cache(cfg, batch: int, capacity: int):
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), cache_spec(cfg, batch, capacity))


# ---------------------------------------------------------------------------
# Block application
# ---------------------------------------------------------------------------


# logical axes of a kernel's [B, S, H, hd] query / output: q heads share
# the KV heads' axis so each shard keeps whole GQA groups
_Q_AXES = ("batch", None, "kv_heads", None)


def _len_axes(cache_len):
    """Logical axes of a scalar or per-row [B] cache length."""
    return ("batch",) if jnp.ndim(cache_len) else ()


def _attn_mixer(p, x, cfg, *, kind, positions, mode, cache, cache_len,
                decode_attn_fn, prefill_len=None, block_tables=None):
    """Attention temporal mixer (pre-norm residual handled by caller).

    On a TPU the hot spots run as compiled Pallas kernels (repro.kernels);
    elsewhere the XLA references run.
    ``prefill_len`` (traced scalar) marks the valid prompt prefix when the
    input is right-padded to a prefill bucket — the cache write then keeps
    the last real positions, not the padded tail.

    ``block_tables`` switches the cache layout to paged: cache leaves are a
    shared page pool [P, page_size, K, hd] and reads/writes route through the
    per-sequence block table (full attention only — the serving engine gates
    paged mode to non-windowed archs). ``mode == "extend"`` continues a
    partially-filled cache: a chunk at positions [cache_len, cache_len+S)
    attends to the cached prefix plus itself (chunked prefill / shared-prefix
    suffix prefill).
    """
    window = cfg.sliding_window if kind != cfgbase.LOCAL_ATTN else cfg.local_window
    q, k, v = attn.qkv_proj(p, x, cfg, positions)
    if mode == "decode" and block_tables is not None:
        ps = cache["k"].shape[1]
        kc, vc = attn.paged_cache_update(cache["k"], cache["v"], k, v,
                                         block_tables, cache_len, ps)
        if kernels.on_tpu():
            # pages replicated: block tables index the whole pool. The
            # serve layout puts pages over "data", so on a mesh with
            # data > 1 every device all-gathers the pool here, per layer
            # and step (sharding pages needs per-shard page ids)
            pool = (None, None, "kv_heads", None)
            o = per_shard(
                functools.partial(_kpda.paged_decode_attention,
                                  q_per_kv=cfg.q_per_kv),
                (q, kc, vc, block_tables, cache_len),
                (_Q_AXES, pool, pool, ("batch", None),
                 _len_axes(cache_len)), _Q_AXES)
        else:
            o = attn.paged_decode_attention_ref(q, kc, vc, block_tables,
                                                cache_len,
                                                q_per_kv=cfg.q_per_kv)
        new_cache = {"k": kc, "v": vc}
    elif mode == "decode":
        kc, vc = attn.cache_update(cache["k"], cache["v"], k, v, cache_len)
        if kernels.on_tpu():
            rows = ("batch", None, "kv_heads", None)
            o = per_shard(
                functools.partial(_kda.decode_attention,
                                  q_per_kv=cfg.q_per_kv, window=window,
                                  block_w=cfg.decode_block_w),
                (q, kc, vc, cache_len),
                (_Q_AXES, rows, rows, _len_axes(cache_len)), _Q_AXES)
        else:
            o = decode_attn_fn(q, kc, vc, cache_len, q_per_kv=cfg.q_per_kv,
                               window=window)
        new_cache = {"k": kc, "v": vc}
    elif mode == "verify":
        # speculative verify: S draft tokens per row at PER-ROW positions
        # [cache_len[b], cache_len[b]+S). Linear full-attention caches write
        # ahead: writes of the padded draft tail are dropped, and
        # rejected-draft K/V needs no rollback because later reads mask by
        # cache position and K/V at accepted positions is causally
        # independent of rejected tokens. Ring (windowed) caches can't write
        # ahead — a ring write destroys the overwritten position — so they
        # attend against a position-ordered view + the draft chunk and stage
        # the chunk K/V for ``verify_commit`` to ring-splice at each row's
        # accepted length.
        S = k.shape[1]
        clens = jnp.asarray(cache_len, jnp.int32).reshape(-1)
        lens = (prefill_len if prefill_len is not None else jnp.int32(S))
        valid = jnp.arange(S, dtype=jnp.int32)[None, :] < \
            jnp.reshape(lens, (-1, 1))
        valid = jnp.broadcast_to(valid, (k.shape[0], S))
        if window is not None:
            kv = jnp.concatenate([attn.ring_verify_view(cache["k"], clens),
                                  k.astype(cache["k"].dtype)], axis=1)
            vv = jnp.concatenate([attn.ring_verify_view(cache["v"], clens),
                                  v.astype(cache["v"].dtype)], axis=1)
            o = attn.spec_attention_ring(q, kv, vv, clens,
                                         q_per_kv=cfg.q_per_kv, window=window)
            new_cache = {"k": cache["k"], "v": cache["v"],
                         "k_new": k, "v_new": v}
        elif block_tables is not None:
            ps = cache["k"].shape[1]
            kc, vc = attn.paged_spec_cache_update(
                cache["k"], cache["v"], k, v, block_tables, clens, valid, ps)
            o = attn.spec_attention(q, attn.paged_view(kc, block_tables),
                                    attn.paged_view(vc, block_tables), clens,
                                    q_per_kv=cfg.q_per_kv)
            new_cache = {"k": kc, "v": vc}
        else:
            kc, vc = attn.spec_cache_update(cache["k"], cache["v"], k, v,
                                            clens, valid)
            o = attn.spec_attention(q, kc, vc, clens, q_per_kv=cfg.q_per_kv)
            new_cache = {"k": kc, "v": vc}
    elif mode == "extend":
        # chunk positions [start, start+S); first `prefill_len` rows valid
        S = k.shape[1]
        start = cache_len
        length = prefill_len if prefill_len is not None else jnp.int32(S)
        qpos = start + jnp.arange(S, dtype=jnp.int32)
        if block_tables is not None:
            if k.shape[0] != 1:
                raise NotImplementedError(
                    "paged extend writes one sequence per call (the engine "
                    f"prefills slot by slot); got batch {k.shape[0]}")
            ps = cache["k"].shape[1]
            kc = attn.paged_chunk_write(cache["k"], k, block_tables[0],
                                        start, ps)
            vc = attn.paged_chunk_write(cache["v"], v, block_tables[0],
                                        start, ps)
            kv = attn.paged_view(kc, block_tables)
            vv = attn.paged_view(vc, block_tables)
            o = attn.flash_attention(q, attn.repeat_kv(kv, cfg.q_per_kv),
                                     attn.repeat_kv(vv, cfg.q_per_kv),
                                     q_positions=qpos)
        else:
            cap = cache["k"].shape[1]
            if cap >= S and window is None:
                # linear cache: splice the chunk in place, attend to the whole
                # row (stale rows past the chunk are causal-masked exactly)
                kc = jax.lax.dynamic_update_slice(
                    cache["k"], k.astype(cache["k"].dtype), (0, start, 0, 0))
                vc = jax.lax.dynamic_update_slice(
                    cache["v"], v.astype(cache["v"].dtype), (0, start, 0, 0))
                o = attn.flash_attention(q, attn.repeat_kv(kc, cfg.q_per_kv),
                                         attn.repeat_kv(vc, cfg.q_per_kv),
                                         q_positions=qpos)
            else:
                # ring (windowed) cache: attend to a position-ordered view of
                # the last `cap` positions + the chunk, then ring-splice
                kseq = jnp.concatenate(
                    [attn.ring_order(cache["k"], start), k.astype(cache["k"].dtype)], axis=1)
                vseq = jnp.concatenate(
                    [attn.ring_order(cache["v"], start), v.astype(cache["v"].dtype)], axis=1)
                o = attn.flash_attention(
                    q, attn.repeat_kv(kseq, cfg.q_per_kv),
                    attn.repeat_kv(vseq, cfg.q_per_kv), window=window,
                    q_positions=cap + jnp.arange(S, dtype=jnp.int32),
                    k_start=jnp.maximum(cap - start, 0))
                kc = attn.ring_extend_write(cache["k"], k, start, length)
                vc = attn.ring_extend_write(cache["v"], v, start, length)
        new_cache = {"k": kc, "v": vc}
    else:
        kr = attn.repeat_kv(k, cfg.q_per_kv)
        vr = attn.repeat_kv(v, cfg.q_per_kv)
        if kernels.on_tpu():
            o = per_shard(functools.partial(_kfa.flash_attention,
                                            window=window),
                          (q, kr, vr), (_Q_AXES,) * 3, _Q_AXES)
        else:
            o = attn.flash_attention(q, kr, vr, window=window,
                                     q_positions=positions[0])
        if mode == "prefill":
            cap = cache["k"].shape[1]
            S = k.shape[1]
            if cap >= S:
                # right-padding is harmless here: padded rows land at
                # positions >= prefill_len, which decode masks by cache_len
                kc = jax.lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype), (0, 0, 0, 0))
                vc = jax.lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype), (0, 0, 0, 0))
            elif prefill_len is None:
                # windowed cache: keep the last `cap` positions, ring-aligned
                k_tail, v_tail = k[:, S - cap:], v[:, S - cap:]
                roll = (S - cap) % cap
                kc = jnp.roll(k_tail, shift=roll, axis=1).astype(cache["k"].dtype)
                vc = jnp.roll(v_tail, shift=roll, axis=1).astype(cache["v"].dtype)
            else:
                # windowed cache under padding: keep positions
                # [prefill_len - cap, prefill_len), ring-aligned at p % cap
                def ring_write(knew, tgt):
                    padded = jnp.concatenate(
                        [jnp.zeros_like(knew[:, :cap]), knew], axis=1)
                    tail = jax.lax.dynamic_slice_in_dim(padded, prefill_len,
                                                        cap, axis=1)
                    return jnp.roll(tail, shift=prefill_len % cap,
                                    axis=1).astype(tgt.dtype)
                kc, vc = ring_write(k, cache["k"]), ring_write(v, cache["v"])
            new_cache = {"k": kc, "v": vc}
        else:
            new_cache = cache
    return attn.out_proj(p, o), new_cache


def apply_block(kind, p, x, cfg, *, positions, mode, cache, cache_len,
                decode_attn_fn, prefill_len=None, prefill_mask=None,
                block_tables=None):
    """One residual block. Returns (x', new_cache, aux_loss).

    In ``mode="verify"`` the returned "cache" of stateful blocks (recurrent /
    mLSTM / sLSTM / ring attention) is a *staged* record — per-position
    states plus the pre-verify state — that ``verify_commit`` resolves to a
    real cache once the accept step has picked each row's accepted length.
    Full-attention blocks commit in place (position-masked write-ahead).
    """
    aux = jnp.zeros((), jnp.float32)
    rec_mode = mode if mode in ("decode", "extend", "verify") else "full"
    rec_len = prefill_len if mode in ("prefill", "extend", "verify") else None
    rec_mask = prefill_mask if mode in ("prefill", "extend", "verify") else None
    if kind in (cfgbase.ATTN, cfgbase.ATTN_MOE, cfgbase.LOCAL_ATTN):
        h = apply_norm(p["attn"]["norm"], x, cfg)
        o, new_cache = _attn_mixer(p["attn"], h, cfg, kind=kind, positions=positions,
                                   mode=mode, cache=cache, cache_len=cache_len,
                                   decode_attn_fn=decode_attn_fn,
                                   prefill_len=rec_len,
                                   block_tables=block_tables)
        x = x + o
        h2 = apply_norm(p["norm2"], x, cfg)
        if kind == cfgbase.ATTN_MOE:
            y, aux = moe_mod.apply_moe(p["moe"], h2, cfg)
        else:
            y = apply_mlp(p["mlp"], h2, cfg)
        return x + y, new_cache, aux
    if kind == cfgbase.RECURRENT:
        h = apply_norm(p["rec"]["norm"], x, cfg)
        o, new_cache = rglru_mod.apply_recurrent_mixer(
            p["rec"], h, cfg, cache=cache, mode=rec_mode,
            length=rec_len, mask=rec_mask)
        x = x + o
        h2 = apply_norm(p["norm2"], x, cfg)
        return x + apply_mlp(p["mlp"], h2, cfg), new_cache, aux
    if kind == cfgbase.MLSTM:
        h = apply_norm(p["mlstm"]["norm"], x, cfg)
        o, new_cache = xlstm_mod.apply_mlstm(
            p["mlstm"], h, cfg, cache=cache, mode=rec_mode,
            length=rec_len, mask=rec_mask)
        return x + o, new_cache, aux
    if kind == cfgbase.SLSTM:
        h = apply_norm(p["slstm"]["norm"], x, cfg)
        o, new_cache = xlstm_mod.apply_slstm(
            p["slstm"], h, cfg, cache=cache, mode=rec_mode,
            length=rec_len, mask=rec_mask)
        return x + o, new_cache, aux
    raise ValueError(kind)


# ---------------------------------------------------------------------------
# The stack
# ---------------------------------------------------------------------------


@jax.custom_vjp
def _diff_barrier(x):
    """``optimization_barrier`` with a gradient rule (none exists upstream):
    the cotangent is barrier'd too, so the backward layers loop keeps the
    same LICM protection as the forward one."""
    return jax.lax.optimization_barrier(x)


def _diff_barrier_fwd(x):
    return jax.lax.optimization_barrier(x), None


def _diff_barrier_bwd(_, g):
    return (jax.lax.optimization_barrier(g),)


_diff_barrier.defvjp(_diff_barrier_fwd, _diff_barrier_bwd)


def _superblock(params_g, cache_g, x, cfg, *, positions, mode, cache_len,
                decode_attn_fn, prefill_len=None, prefill_mask=None,
                block_tables=None):
    """Apply one period of the pattern. Returns (x, new_cache_g, aux)."""
    aux = jnp.zeros((), jnp.float32)
    new_cache = {}
    x = constrain(x, "batch", None, None)
    # Barrier: stops XLA LICM from hoisting per-layer converts of the saved
    # residual stack out of the (backward) layers loop — that hoist would
    # materialize an f32 copy of the whole [L, B, S, D] stack (MaxText does
    # the same around scanned blocks).
    x = _diff_barrier(x)
    for i, kind in enumerate(cfg.pattern):
        sub_cache = cache_g.get(f"sub{i}") if cache_g else None
        x, nc, a = apply_block(kind, params_g[f"sub{i}"], x, cfg,
                               positions=positions, mode=mode, cache=sub_cache,
                               cache_len=cache_len, decode_attn_fn=decode_attn_fn,
                               prefill_len=prefill_len, prefill_mask=prefill_mask,
                               block_tables=block_tables)
        new_cache[f"sub{i}"] = nc
        aux = aux + a
    return x, new_cache, aux


def apply_stack(params, x, cfg, *, positions, mode, cache=None, cache_len=None,
                decode_attn_fn=None, prefill_len=None, prefill_mask=None,
                block_tables=None):
    """Run all layers. Returns (x, new_cache, aux_loss_sum)."""
    decode_attn_fn = decode_attn_fn or attn.decode_attention
    use_cache = cache is not None
    scan_cache = cache["scan"] if use_cache else None

    def body(carry, xs):
        x, aux = carry
        params_g, cache_g = xs
        x, new_cache_g, a = _superblock(params_g, cache_g, x, cfg,
                                        positions=positions, mode=mode,
                                        cache_len=cache_len,
                                        decode_attn_fn=decode_attn_fn,
                                        prefill_len=prefill_len,
                                        prefill_mask=prefill_mask,
                                        block_tables=block_tables)
        return (x, aux + a), new_cache_g

    if cfg.remat_policy != "none" and mode == "train":
        policy = (jax.checkpoint_policies.nothing_saveable
                  if cfg.remat_policy == "full"
                  else jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
        body = jax.checkpoint(body, policy=policy)

    aux0 = jnp.zeros((), jnp.float32)
    if cfg.scan_layers and cfg.num_scan_groups > 1:
        if use_cache:
            (x, aux), new_scan_cache = jax.lax.scan(body, (x, aux0),
                                                    (params["scan"], scan_cache))
        else:
            def body_nocache(carry, params_g):
                return body(carry, (params_g, None))[0], None
            (x, aux), _ = jax.lax.scan(body_nocache, (x, aux0), params["scan"])
            new_scan_cache = None
    else:
        aux = aux0
        slices = []
        for g in range(cfg.num_scan_groups):
            params_g = jax.tree.map(lambda v: v[g], params["scan"])
            cache_g = jax.tree.map(lambda v: v[g], scan_cache) if use_cache else None
            (x, aux), nc = body((x, aux), (params_g, cache_g))
            slices.append(nc)
        if not use_cache:
            new_scan_cache = None
        elif slices:
            new_scan_cache = jax.tree.map(lambda *xs: jnp.stack(xs), *slices)
        else:
            # num_layers < len(pattern): every layer is a tail layer and the
            # scan cache is zero-size — pass it through unchanged
            new_scan_cache = scan_cache

    new_cache = {"scan": new_scan_cache} if use_cache else None
    for j, kind in enumerate(cfg.tail_kinds):
        tail_cache = cache.get(f"tail{j}") if use_cache else None
        x, nc, a = apply_block(kind, params[f"tail{j}"], x, cfg,
                               positions=positions, mode=mode, cache=tail_cache,
                               cache_len=cache_len, decode_attn_fn=decode_attn_fn,
                               prefill_len=prefill_len, prefill_mask=prefill_mask,
                               block_tables=block_tables)
        aux = aux + a
        if use_cache:
            new_cache[f"tail{j}"] = nc
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# Entry points
# ---------------------------------------------------------------------------


def _inputs_to_x(params, batch, cfg):
    """Resolve tokens vs precomputed frame embeddings (modality stub)."""
    if cfg.modality == "audio_frames":
        x = batch["frames"].astype(jnp.dtype(cfg.dtype))
    else:
        x = embed_tokens(params, batch["tokens"], cfg)
    if cfg.pos_emb == "sinusoidal":
        x = x + sinusoidal_pos(batch["positions"], cfg.d_model, x.dtype)
    return x


def forward_logits(params, batch, cfg, *, mode="train", cache=None, cache_len=None,
                   decode_attn_fn=None, prefill_len=None, block_tables=None,
                   with_logits=True):
    """``with_logits`` selects how much of the unembed matmul runs:

    * False    — skip final-norm + unembed, return None logits (intermediate
                 prefill chunks only need the cache side effects, and the
                 unembed is the dominant matmul at real vocab sizes).
    * "last"   — unembed only the position ``prefill_len - 1`` (or the final
                 position), returning [B, 1, V]: all a prompt's final chunk
                 needs to seed sampling. Scalar ``prefill_len`` only.
    * "all" / True — unembed every position, [B, S, V]: the speculative
                 verify step scores all draft positions from one forward.

    ``prefill_len`` may be a traced scalar (uniform valid prefix — bucketed
    prefill / extend) or a [B] vector (per-row valid counts — verify mode).
    """
    x = _inputs_to_x(params, batch, cfg)
    prefill_mask = None
    if prefill_len is not None:
        S = x.shape[1]
        plen = jnp.reshape(jnp.asarray(prefill_len, jnp.int32), (-1, 1))
        prefill_mask = jnp.broadcast_to(
            jnp.arange(S, dtype=jnp.int32)[None, :] < plen,
            (x.shape[0], S))
    x, new_cache, aux = apply_stack(params, x, cfg, positions=batch["positions"],
                                    mode=mode, cache=cache, cache_len=cache_len,
                                    decode_attn_fn=decode_attn_fn,
                                    prefill_len=prefill_len,
                                    prefill_mask=prefill_mask,
                                    block_tables=block_tables)
    if not with_logits:
        return None, new_cache, aux
    x = apply_norm(params["final_norm"], x, cfg)
    if with_logits == "last":
        last = (prefill_len - 1 if prefill_len is not None
                else x.shape[1] - 1)
        x = jax.lax.dynamic_slice_in_dim(x, last, 1, axis=1)
    logits = unembed(params, x, cfg)
    return logits, new_cache, aux


def train_loss(params, batch, cfg, *, decode_attn_fn=None):
    """Causal LM loss. batch: tokens/frames [B,S], labels [B,S], positions."""
    logits, _, aux = forward_logits(params, batch, cfg, mode="train")
    labels = batch["labels"]
    V = cfg.padded_vocab
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    mask = (labels >= 0) & (labels < cfg.vocab_size)
    nll = jnp.where(mask, lse - ll, 0.0)
    nll = constrain(nll, "batch", None)
    loss = jnp.sum(nll) / jnp.maximum(jnp.sum(mask), 1)
    return loss + aux, {"nll": loss, "aux": aux}


def prefill(params, batch, cfg, cache, *, length=None, decode_attn_fn=None,
            with_logits=True):
    """Fill the cache from a prompt. Returns (logits [B,S,V], cache').

    ``length`` (traced scalar, optional): valid prompt length when tokens are
    right-padded to a bucket — recurrent state, conv state, and windowed KV
    caches then match an unpadded prefill of the first ``length`` tokens.
    ``with_logits="last"`` unembeds only position ``length - 1`` ([B,1,V]) —
    all the serving engine needs to seed sampling, skipping the other
    bucket-1 rows of the dominant matmul.
    """
    logits, new_cache, _ = forward_logits(params, batch, cfg, mode="prefill",
                                          cache=cache, cache_len=jnp.zeros((), jnp.int32),
                                          prefill_len=length,
                                          decode_attn_fn=decode_attn_fn,
                                          with_logits=with_logits)
    return logits, new_cache


def decode_step(params, batch, cfg, cache, cache_len, *, decode_attn_fn=None,
                block_tables=None):
    """One decode step. batch tokens [B,1]; returns (logits [B,1,V], cache').

    ``block_tables`` [B, P] int32 switches attention caches to the paged
    layout (cache leaves are page pools; see serving/kvpool.py).
    """
    logits, new_cache, _ = forward_logits(params, batch, cfg, mode="decode",
                                          cache=cache, cache_len=cache_len,
                                          decode_attn_fn=decode_attn_fn,
                                          block_tables=block_tables)
    return logits, new_cache


def extend(params, batch, cfg, cache, cache_len, *, length=None,
           decode_attn_fn=None, block_tables=None, with_logits=True):
    """Prefill continuation: a chunk of S tokens at positions
    [cache_len, cache_len+S) against an already partially-filled cache.

    Attention layers attend to the cached prefix + the chunk; recurrent /
    conv / xLSTM layers resume from their cached state. ``length`` (traced
    scalar) marks the valid chunk prefix when the chunk is right-padded to a
    bucket. Powers chunked prefill past the largest bucket and shared-prefix
    suffix prefill in paged mode. Returns (logits [B,S,V], cache');
    ``with_logits=False`` returns (None, cache') and skips the unembed —
    only a prompt's final chunk needs logits.
    """
    logits, new_cache, _ = forward_logits(params, batch, cfg, mode="extend",
                                          cache=cache, cache_len=cache_len,
                                          prefill_len=length,
                                          decode_attn_fn=decode_attn_fn,
                                          block_tables=block_tables,
                                          with_logits=with_logits)
    return logits, new_cache


def verify(params, batch, cfg, cache, cache_lens, *, lens=None,
           decode_attn_fn=None, block_tables=None):
    """Speculative-decode verify: score S draft tokens per row in ONE forward.

    batch tokens [B, S] are ``[last, d_1 .. d_k, pad...]`` per row at per-row
    positions ``[cache_lens[b], cache_lens[b]+S)``; ``lens`` [B] counts the
    valid inputs (k+1) — padded-tail cache writes are dropped and padded
    logits are garbage the acceptance step never reads. Returns
    (logits [B,S,V], staged): ``logits[:, i]`` is the target distribution
    for the token following input i (sampler.accept_batched consumes it).

    For pure linear full-attention caches ``staged`` IS the new cache
    (write-ahead, position-masked). Stateful blocks (recurrent / conv /
    mLSTM / sLSTM, ring KV) stage per-position states instead — pass
    ``staged`` plus the accept step's per-row emitted counts to
    ``verify_commit`` to resolve the final cache. Works for every arch.
    """
    logits, new_cache, _ = forward_logits(params, batch, cfg, mode="verify",
                                          cache=cache, cache_len=cache_lens,
                                          prefill_len=lens,
                                          decode_attn_fn=decode_attn_fn,
                                          block_tables=block_tables,
                                          with_logits="all")
    return logits, new_cache


def _commit_block(kind, cfg, staged, clens, ns, valid):
    """Resolve one block's verify record to a committed cache (see
    apply_block's verify contract)."""
    window = (cfg.sliding_window if kind != cfgbase.LOCAL_ATTN
              else cfg.local_window)
    if kind in (cfgbase.ATTN, cfgbase.ATTN_MOE) and window is None:
        return staged                       # write-ahead already committed
    if kind in (cfgbase.ATTN, cfgbase.ATTN_MOE, cfgbase.LOCAL_ATTN):
        return attn.ring_verify_commit(staged, clens, ns, valid)
    if kind == cfgbase.RECURRENT:
        return rglru_mod.verify_commit(staged, ns, valid)
    if kind == cfgbase.MLSTM:
        return xlstm_mod.mlstm_verify_commit(staged, ns, valid)
    if kind == cfgbase.SLSTM:
        return xlstm_mod.slstm_verify_commit(staged, ns, valid)
    raise ValueError(kind)


def verify_commit(cfg, staged, cache_lens, ns, lens):
    """Resolve a ``verify`` call's staged record to the committed cache.

    ns [B]: tokens emitted per row by ``sampler.accept_batched`` (= accepted
    drafts + 1 correction/bonus = inputs actually consumed); lens [B]: the
    verify call's valid-input counts — rows with ``lens == 0`` sat the step
    out and keep their pre-verify state bit-exactly. The whole rewind is
    gathers and ring splices — no second forward — which is what lets
    stateful archs share the engine's ONE-jit'd-verify-per-step fast path.
    """
    clens = jnp.asarray(cache_lens, jnp.int32).reshape(-1)
    ns = jnp.asarray(ns, jnp.int32).reshape(-1)
    valid = jnp.asarray(lens, jnp.int32).reshape(-1) > 0
    new_cache = {"scan": {}}
    if cfg.num_scan_groups == 0:
        # num_layers < len(pattern): apply_stack passed the zero-size scan
        # cache through unchanged — no staged records to resolve
        new_cache["scan"] = staged["scan"]
    else:
        for i, kind in enumerate(cfg.pattern):
            fn = functools.partial(_commit_block, kind, cfg,
                                   clens=clens, ns=ns, valid=valid)
            new_cache["scan"][f"sub{i}"] = jax.vmap(
                lambda s, fn=fn: fn(s))(staged["scan"][f"sub{i}"])
    for j, kind in enumerate(cfg.tail_kinds):
        new_cache[f"tail{j}"] = _commit_block(kind, cfg, staged[f"tail{j}"],
                                              clens, ns, valid)
    return new_cache
