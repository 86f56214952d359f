"""xLSTM blocks: mLSTM (matrix memory, chunkwise-parallel) and sLSTM (scalar).

TPU adaptation (DESIGN.md §3): the GPU reference implements mLSTM as a fused
step-recurrent CUDA kernel; here we use the *chunkwise-parallel* formulation —
intra-chunk attention-like einsums (MXU-friendly) + an inter-chunk state scan
— mathematically equivalent under the standard max-stabilizer. The naive
sequential recurrence lives in ``kernels/ref.py`` as the oracle; tests check
chunkwise == sequential. sLSTM's state nonlinearity is inherently sequential
(per the xLSTM paper), so it stays a ``lax.scan``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import ParamDef, norm_defs
from repro.models.rglru import causal_conv1d


# ---------------------------------------------------------------------------
# Param defs
# ---------------------------------------------------------------------------


def mlstm_defs(cfg):
    D = cfg.d_model
    F2 = int(cfg.mlstm_proj_factor * D)
    H = cfg.num_heads
    W = cfg.conv1d_width
    return {
        "norm": norm_defs(cfg),
        "w_up": ParamDef((D, F2), ("embed", "inner"), init="scaled"),
        "w_gate": ParamDef((D, F2), ("embed", "inner"), init="scaled"),
        "conv_w": ParamDef((W, F2), ("conv", "inner"), init="scaled"),
        "conv_b": ParamDef((F2,), ("inner",), init="zeros"),
        "wq": ParamDef((F2, F2), ("inner", "inner_out"), init="scaled"),
        "wk": ParamDef((F2, F2), ("inner", "inner_out"), init="scaled"),
        "wv": ParamDef((F2, F2), ("inner", "inner_out"), init="scaled"),
        "w_ig": ParamDef((F2, H), ("inner", None), init="scaled"),
        "b_ig": ParamDef((H,), (None,), init="zeros"),
        "w_fg": ParamDef((F2, H), ("inner", None), init="scaled"),
        "b_fg": ParamDef((H,), (None,), init="ones"),   # bias toward remembering
        "out_norm": ParamDef((F2,), ("inner",), init="ones"),
        "w_down": ParamDef((F2, D), ("inner", "embed"), init="scaled"),
    }


def slstm_defs(cfg):
    D = cfg.d_model
    H = cfg.slstm_heads
    hd = D // H
    return {
        "norm": norm_defs(cfg),
        # "slstm_inner" is replicated (§Perf): the sequential scan would
        # otherwise psum [B, D] across `model` EVERY timestep (32768 steps!)
        # because heads (hd=256) straddle 16 model shards of 64 channels.
        "w_gates": ParamDef((D, 4, D), ("embed", None, "slstm_inner"), init="scaled"),
        "r_gates": ParamDef((H, 4, hd, hd), (None, None, None, None), init="scaled"),
        "b_gates": ParamDef((4, D), (None, "slstm_inner"), init="zeros"),
        "out_norm": ParamDef((D,), ("slstm_inner",), init="ones"),
        "wo": ParamDef((D, D), ("slstm_inner", "embed"), init="scaled"),
    }


# ---------------------------------------------------------------------------
# mLSTM chunkwise-parallel cell
# ---------------------------------------------------------------------------


def mlstm_chunkwise(q, k, v, ig, fg, state=None, *, chunk: int = 256):
    """Stabilized chunkwise mLSTM.

    q,k,v: [B, S, H, hd]; ig,fg: [B, S, H] (pre-activations, log-space).
    state: (C [B,H,hd,hd], n [B,H,hd], m [B,H]) f32 or None.
    Returns (h [B,S,H,hd], state').
    """
    B, S, H, hd = q.shape
    L = min(chunk, S)
    Sp = -(-S // L) * L
    if Sp != S:
        # pad so padded positions contribute nothing: i-gate -> -inf (zero
        # write weight), f-gate -> +large (zero extra decay)
        pad = ((0, 0), (0, Sp - S), (0, 0), (0, 0))
        q, k, v = (jnp.pad(a, pad) for a in (q, k, v))
        ig = jnp.pad(ig, ((0, 0), (0, Sp - S), (0, 0)), constant_values=-1e30)
        fg = jnp.pad(fg, ((0, 0), (0, Sp - S), (0, 0)), constant_values=30.0)
    S_out = S
    S = Sp
    nc = S // L
    scale = hd ** -0.5

    def to_chunks(x):
        return jnp.moveaxis(x.reshape(B, nc, L, *x.shape[2:]), 1, 0)

    qc, kc, vc = to_chunks(q * scale), to_chunks(k), to_chunks(v)
    igc, fgc = to_chunks(ig.astype(jnp.float32)), to_chunks(fg.astype(jnp.float32))

    if state is None:
        C0 = jnp.zeros((B, H, hd, hd), jnp.float32)
        n0 = jnp.zeros((B, H, hd), jnp.float32)
        m0 = jnp.full((B, H), -1e30, jnp.float32)
    else:
        C0, n0, m0 = state

    def chunk_step(carry, xs):
        C, n, m = carry
        qj, kj, vj, ij, fj = xs                     # [B,L,H,*]
        logf = jax.nn.log_sigmoid(fj)               # [B,L,H]
        F = jnp.cumsum(logf, axis=1)                # decay chunk-start..j inclusive
        FL = F[:, -1]                               # [B,H]
        # intra-chunk pair weights: D_ji = F_j - F_i + i_i   (i <= j)
        #   (decay from i+1..j) = F_j - F_i
        logD = F[:, :, None, :] - F[:, None, :, :] + ij[:, None, :, :]  # [B,L(j),L(i),H]
        tri = jnp.tril(jnp.ones((L, L), bool))
        logD = jnp.where(tri[None, :, :, None], logD, -1e30)
        m_intra = jnp.max(logD, axis=2)             # [B,L,H]
        m_inter = F + m[:, None, :]                 # [B,L,H]
        mj = jnp.maximum(m_inter, m_intra)
        d = jnp.exp(logD - mj[:, :, None, :])       # [B,L,L,H]
        inter = jnp.exp(m_inter - mj)               # [B,L,H]

        s = jnp.einsum("blhd,bmhd->blmh", qj, kj,
                       preferred_element_type=jnp.float32)      # [B,L(j),L(i),H]
        w = s * d
        h_intra = jnp.einsum("blmh,bmhd->blhd", w.astype(vj.dtype), vj,
                             preferred_element_type=jnp.float32)
        h_inter = jnp.einsum("blhd,bhde->blhe", qj.astype(jnp.float32), C)
        h_num = h_inter * inter[..., None] + h_intra
        n_intra = jnp.einsum("blmh,bmhd->blhd", d, kj.astype(jnp.float32))
        n_j = n[:, None] * inter[..., None] + n_intra                       # [B,L,H,hd]
        denom = jnp.maximum(
            jnp.abs(jnp.einsum("blhd,blhd->blh", qj.astype(jnp.float32), n_j)),
            jnp.exp(-mj))
        h = h_num / denom[..., None]

        # ---- state to end of chunk -----------------------------------------
        m_next = jnp.maximum(FL + m, jnp.max(FL[:, None] - F + ij, axis=1))
        sc = jnp.exp(FL[:, None] - F + ij - m_next[:, None])    # [B,L,H]
        C_next = (C * jnp.exp(FL + m - m_next)[..., None, None]
                  + jnp.einsum("blh,blhd,blhe->bhde", sc,
                               kj.astype(jnp.float32), vj.astype(jnp.float32)))
        n_next = (n * jnp.exp(FL + m - m_next)[..., None]
                  + jnp.einsum("blh,blhd->bhd", sc, kj.astype(jnp.float32)))
        return (C_next, n_next, m_next), h.astype(q.dtype)

    (C, n, m), hs = jax.lax.scan(chunk_step, (C0, n0, m0), (qc, kc, vc, igc, fgc))
    h = jnp.moveaxis(hs, 0, 1).reshape(B, S, H, hd)[:, :S_out]
    return h, (C, n, m)


def mlstm_step(q, k, v, ig, fg, state):
    """Single decode step. q,k,v [B,1,H,hd]; ig,fg [B,1,H]."""
    C, n, m = state
    q1, k1, v1 = (x[:, 0].astype(jnp.float32) for x in (q, k, v))
    scale = q.shape[-1] ** -0.5
    logf = jax.nn.log_sigmoid(fg[:, 0].astype(jnp.float32))
    i1 = ig[:, 0].astype(jnp.float32)
    m_new = jnp.maximum(logf + m, i1)
    fp = jnp.exp(logf + m - m_new)
    ip = jnp.exp(i1 - m_new)
    C_new = C * fp[..., None, None] + ip[..., None, None] * (k1[..., :, None] * v1[..., None, :])
    n_new = n * fp[..., None] + ip[..., None] * k1
    qs = q1 * scale
    h_num = jnp.einsum("bhd,bhde->bhe", qs, C_new)
    denom = jnp.maximum(jnp.abs(jnp.sum(qs * n_new, axis=-1)), jnp.exp(-m_new))
    h = (h_num / denom[..., None])[:, None]
    return h.astype(q.dtype), (C_new, n_new, m_new)


# ---------------------------------------------------------------------------
# Block assembly
# ---------------------------------------------------------------------------


def _heads(x, H):
    B, S, F2 = x.shape
    return x.reshape(B, S, H, F2 // H)


def _group_norm_heads(x, scale, eps=1e-6):
    """Per-head RMS norm with a flat [F2] learned scale (xLSTM multi-head norm)."""
    B, S, H, hd = x.shape
    xf = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = (xf * jax.lax.rsqrt(ms + eps)).reshape(B, S, H * hd)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def apply_mlstm(p, x, cfg, *, cache=None, mode="full", length=None, mask=None):
    """x [B,S,D] -> (y, new_cache). cache: {"state": (C,n,m), "conv": [B,W-1,F2]}.

    ``length``/``mask`` mark the valid prefix under right-padded (bucketed)
    prefill: padded positions get i-gate -> -inf / f-gate -> +large (the same
    trick the chunkwise cell uses for its internal padding), so they neither
    write to nor decay the (C, n, m) state. ``mode="verify"`` returns a
    staged record of per-position states instead of a cache; the
    speculative accept step rewinds the matrix memory to each row's
    accepted length with a gather (``mlstm_verify_commit``) — batched, no
    replay forward.
    """
    H = cfg.num_heads
    u = jnp.einsum("bsd,df->bsf", x, p["w_up"])
    z = jnp.einsum("bsd,df->bsf", x, p["w_gate"])
    conv_state = cache["conv"] if mode in ("decode", "extend", "verify") else None
    # verify: per-row lengths live in ``mask``; the committed conv window is
    # gathered from the staged input stream, not the scalar-length slice
    c, new_conv = causal_conv1d(u, p["conv_w"], p["conv_b"], conv_state,
                                length=None if mode == "verify" else length)
    c = jax.nn.silu(c)
    q = _heads(jnp.einsum("bsf,fg->bsg", c, p["wq"]), H)
    k = _heads(jnp.einsum("bsf,fg->bsg", c, p["wk"]), H)
    v = _heads(jnp.einsum("bsf,fg->bsg", u, p["wv"]), H)
    ig = jnp.einsum("bsf,fh->bsh", u, p["w_ig"]) + p["b_ig"]
    fg = jnp.einsum("bsf,fh->bsh", u, p["w_fg"]) + p["b_fg"]
    if mask is not None and mode != "decode":
        ig = jnp.where(mask[..., None], ig, -1e30)
        fg = jnp.where(mask[..., None], fg, 30.0)
    if mode == "verify":
        # batched speculative verify: step the exact decode recurrence over
        # the (tiny) draft chunk, stacking the (C, n, m) state after every
        # position so ``mlstm_verify_commit`` can rewind to any accepted
        # length with a gather — no per-slot snapshot+replay. Masked rows
        # (i-gate -1e30 / f-gate +30) step as identities, so padded draft
        # tails neither write to nor decay the matrix memory.
        def step(st, xs_t):
            qt, kt, vt, it, ft = xs_t
            h_t, st2 = mlstm_step(qt[:, None], kt[:, None], vt[:, None],
                                  it[:, None], ft[:, None], st)
            return st2, (h_t[:, 0], st2)
        xs_t = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, ig, fg))
        _, (hs, states) = jax.lax.scan(step, cache["state"], xs_t)
        h = jnp.moveaxis(hs, 0, 1)
        h = _group_norm_heads(h, p["out_norm"])
        y = jnp.einsum("bsf,fd->bsd", h * jax.nn.silu(z), p["w_down"])
        staged = {"states": tuple(jnp.moveaxis(s, 0, 1) for s in states),
                  "state0": cache["state"],
                  "xs": jnp.concatenate([cache["conv"], u], axis=1)}
        return y, staged
    if mode == "decode":
        h, state = mlstm_step(q, k, v, ig, fg, cache["state"])
    elif mode == "extend":
        # chunked-prefill continuation: resume (C, n, m) from the cache (the
        # Pallas chunk kernel has no initial-state input, so extend always
        # takes the XLA chunkwise path)
        h, state = mlstm_chunkwise(q, k, v, ig, fg, cache["state"],
                                   chunk=cfg.mlstm_chunk)
    else:
        # XLA on every platform: its chunk matmuls already run on the MXU,
        # and it yields the state the cache needs alongside h
        h, state = mlstm_chunkwise(q, k, v, ig, fg, chunk=cfg.mlstm_chunk)
    h = _group_norm_heads(h, p["out_norm"])
    y = jnp.einsum("bsf,fd->bsd", h * jax.nn.silu(z), p["w_down"])
    return y, {"state": state, "conv": new_conv}


def slstm_scan(p, x, cfg, state=None, mask=None, all_states: bool = False):
    """Sequential sLSTM over [B,S,D]. state: (c,n,h,m) each [B,D] f32.

    ``mask`` [B,S] bool: padded timesteps carry the state through unchanged.
    ``all_states``: additionally return the state after every position
    (each [B,S,D] f32) — the verify step's accept-rewind record.
    """
    B, S, D = x.shape
    H = cfg.slstm_heads
    hd = D // H
    gates_x = jnp.einsum("bsd,dge->bsge", x, p["w_gates"]) + p["b_gates"]  # [B,S,4,D]
    if state is None:
        zeros = jnp.zeros((B, D), jnp.float32)
        state = (zeros, zeros, zeros, jnp.full((B, D), -1e30, jnp.float32))
    if mask is None:
        mask = jnp.ones((B, S), bool)

    def step(carry, xs):
        gx, mt = xs                                  # [B,4,D], [B]
        c, n, h, m = carry
        hh = h.reshape(B, H, hd)
        rec = jnp.einsum("bhd,hgde->bhge", hh.astype(x.dtype), p["r_gates"])
        g = gx.astype(jnp.float32) + rec.transpose(0, 2, 1, 3).reshape(B, 4, D).astype(jnp.float32)
        gi, gf, gz, go = g[:, 0], g[:, 1], g[:, 2], g[:, 3]
        m_new = jnp.maximum(gf + m, gi)
        fp = jnp.exp(gf + m - m_new)
        ip = jnp.exp(gi - m_new)
        c_new = fp * c + ip * jnp.tanh(gz)
        n_new = fp * n + ip
        h_new = jax.nn.sigmoid(go) * c_new / jnp.maximum(n_new, 1e-6)
        keep = mt[:, None]
        new = tuple(jnp.where(keep, a, b)
                    for a, b in zip((c_new, n_new, h_new, m_new), (c, n, h, m)))
        y = h_new.astype(x.dtype)
        return new, ((y, new) if all_states else y)

    gates_t = jnp.moveaxis(gates_x, 1, 0)           # [S,B,4,D]
    mask_t = jnp.moveaxis(mask, 1, 0)               # [S,B]
    new_state, ys = jax.lax.scan(step, state, (gates_t, mask_t))
    if all_states:
        hs, states = ys
        return jnp.moveaxis(hs, 0, 1), tuple(jnp.moveaxis(s, 0, 1)
                                             for s in states)
    return jnp.moveaxis(ys, 0, 1), new_state


def apply_slstm(p, x, cfg, *, cache=None, mode="full", length=None, mask=None):
    state = cache["state"] if mode in ("decode", "extend", "verify") else None
    h, new_state = slstm_scan(p, x, cfg, state,
                              mask=mask if mode != "decode" else None,
                              all_states=mode == "verify")
    hf = h.astype(jnp.float32)
    ms = jnp.mean(jnp.square(hf), axis=-1, keepdims=True)
    h = ((hf * jax.lax.rsqrt(ms + 1e-6)) * p["out_norm"].astype(jnp.float32)).astype(x.dtype)
    y = jnp.einsum("bsd,de->bse", h, p["wo"])
    if mode == "verify":
        # new_state is the per-position state stack; commit gathers at the
        # accepted length (slstm_verify_commit)
        return y, {"states": new_state, "state0": cache["state"]}
    return y, {"state": new_state}


# ---------------------------------------------------------------------------
# Speculative-verify commit: rewind the staged per-position states to each
# row's accepted length (batched; replaces per-slot snapshot+replay)
# ---------------------------------------------------------------------------


def _gather_states(states, state0, ns, valid):
    """Pick state after input ``ns[b]`` per row from per-position stacks
    (each [B, S, ...]); invalid rows keep their pre-verify state."""
    S = jax.tree.leaves(states)[0].shape[1]
    idx = jnp.clip(ns - 1, 0, S - 1)

    def pick(stack, old):
        ix = idx.reshape((-1,) + (1,) * (stack.ndim - 1))
        sel = jnp.take_along_axis(stack, ix, axis=1)[:, 0]
        v = valid.reshape((-1,) + (1,) * (old.ndim - 1))
        return jnp.where(v, sel, old)

    return jax.tree.map(pick, states, state0)


def mlstm_verify_commit(staged, ns, valid):
    """staged: {"states": (C,n,m) each [B,S,...], "state0", "xs"} from
    ``apply_mlstm(mode="verify")``. Returns the committed
    {"state", "conv"} cache at each row's accepted length."""
    state = _gather_states(staged["states"], staged["state0"], ns, valid)
    xs = staged["xs"]                                       # [B, S+W-1, F2]
    S = staged["states"][2].shape[1]
    W1 = xs.shape[1] - S
    n_eff = jnp.where(valid, jnp.clip(ns, 0, S), 0)
    conv = jax.vmap(
        lambda row, n: jax.lax.dynamic_slice_in_dim(row, n, W1, axis=0)
    )(xs, n_eff)
    return {"state": state, "conv": conv}


def slstm_verify_commit(staged, ns, valid):
    """staged: {"states": (c,n,h,m) each [B,S,D], "state0"} from
    ``apply_slstm(mode="verify")``."""
    return {"state": _gather_states(staged["states"], staged["state0"],
                                    ns, valid)}
