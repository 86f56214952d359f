"""Token samplers: greedy / temperature / top-k, pure jax.

``sample`` keeps the original host-friendly API (python-scalar temperature,
branching at trace time). ``sample_batched`` is the serving fast path: all
parameters are traced per-row vectors, so one jit'd callable serves any mix
of greedy and stochastic slots without recompiling — it runs inside the
engine's on-device decode loop. ``accept_batched`` is the speculative-decode
verify step: batched greedy exact-match / rejection-sampling acceptance of
drafted tokens (serving/spec.py proposes them, models verify mode scores
them), distribution-correct for stochastic slots.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import kernels
from repro.distributed.sharding import per_shard
from repro.kernels import spec_scan

NEG = -1e30


def sample(logits, key, *, temperature: float = 0.0, top_k: int = 0,
           vocab_limit: int = 0):
    """logits [B, V] -> token ids [B]."""
    if vocab_limit:
        mask = jnp.arange(logits.shape[-1]) < vocab_limit
        logits = jnp.where(mask, logits, NEG)
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / temperature
    if top_k:
        kth = jnp.sort(logits, axis=-1)[..., -top_k][..., None]
        logits = jnp.where(logits >= kth, logits, NEG)
    return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)


def _top_k_filter(scaled, k):
    """Keep the top-k entries of the trailing axis per row, -inf the rest.

    scaled [..., V]; k broadcastable int32 against the leading axes, with
    ``k <= 0`` meaning no filter for that row and ``k >= V`` degenerating to
    no filter as well (the k-th largest is then the global minimum, so every
    entry passes — see test_serving_fastpath's top-k edge tests).
    """
    V = scaled.shape[-1]
    srt = jnp.sort(scaled, axis=-1)                      # ascending
    idx = jnp.clip(V - k, 0, V - 1)                      # k-th largest
    idx = jnp.broadcast_to(idx[..., None], scaled.shape[:-1] + (1,))
    kth = jnp.take_along_axis(srt, idx, axis=-1)
    keep = (k <= 0)[..., None] | (scaled >= kth)
    return jnp.where(keep, scaled, NEG)


def sample_batched(logits, key, *, temperature, top_k=None, vocab_limit: int = 0):
    """Per-row sampling with traced parameters. logits [B, V] -> ids [B].

    key:         a single PRNG key shared by the batch, or per-row keys
                 [B, 2] — then row b samples with its own key (the serving
                 engine's per-request RNG chains: a request's draws depend
                 only on its own key and token index, never on batch
                 composition — see SamplingParams.seed).
    temperature: [B] f32 (<= 0 means greedy for that row), or None for a
                 statically greedy batch — no RNG / sort ops are traced at
                 all, which matters inside the engine's per-token decode loop.
    top_k:       [B] int32 or None (<= 0 means no top-k filter for that row;
                 k >= vocab also means no filter — never a negative index).
    vocab_limit: static int — ids >= vocab_limit are never produced, and the
                 top-k filter composes (masked ids stay at -inf below any kth
                 threshold, so they are neither kept nor sampled).
    """
    B, V = logits.shape
    logits = logits.astype(jnp.float32)
    if vocab_limit:
        vmask = jnp.arange(V) < vocab_limit
        logits = jnp.where(vmask[None, :], logits, NEG)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    if temperature is None:
        return greedy
    temperature = jnp.asarray(temperature, jnp.float32).reshape(B)

    scaled = logits / jnp.maximum(temperature, 1e-6)[:, None]
    if top_k is not None:
        scaled = _top_k_filter(scaled, jnp.asarray(top_k, jnp.int32).reshape(B))
    if key.ndim == 2:                       # per-row (per-request) keys
        stochastic = jax.vmap(
            lambda k, s: jax.random.categorical(k, s))(key, scaled)
        stochastic = stochastic.astype(jnp.int32)
    else:
        stochastic = jax.random.categorical(key, scaled, axis=-1).astype(jnp.int32)
    return jnp.where(temperature > 0.0, stochastic, greedy)


def accept_batched(logits, inputs, draft_lens, key, *, temperature,
                   top_k=None, vocab_limit: int = 0):
    """Batched draft acceptance for drafter-free speculative decoding.

    logits [B, S, V]:  verify-forward logits; ``logits[:, i]`` is the target
                       distribution for the token FOLLOWING input i.
    inputs [B, S]:     the verify-step inputs ``[last, d_1 .. d_k, pad...]``
                       per row, so ``inputs[:, i+1]`` is the draft token that
                       ``logits[:, i]`` is judged against.
    draft_lens [B]:    k per row (0 <= k <= S-1). k == 0 degenerates to a
                       plain decode step: one token sampled from logits[:, 0].
    temperature/top_k/vocab_limit: as in ``sample_batched`` (None temperature
                       = statically greedy batch, no RNG traced).

    Greedy rows accept a draft iff it matches the argmax, so greedy
    speculative output is bit-identical to non-speculative decode. Stochastic
    rows use rejection sampling against the deterministic drafter (q = point
    mass on d): accept d with prob p(d); on reject, sample from the
    renormalized residual (p with d removed). Either way each emitted token
    is marginally distributed exactly as non-speculative sampling — the
    standard speculative-sampling correctness argument specialised to a
    deterministic draft distribution.

    Returns (out_tokens [B, S], out_lens [B]): ``out_tokens[b, :m]`` are the
    accepted drafts, ``out_tokens[b, m]`` the correction (on reject) or bonus
    (full accept) token; ``out_lens = m + 1`` tokens are emitted per row.
    On a TPU the accept-length reduction runs as the fused Pallas scan
    (kernels/spec_scan.py).
    """
    B, S, V = logits.shape
    logits = logits.astype(jnp.float32)
    if vocab_limit:
        vmask = jnp.arange(V) < vocab_limit
        logits = jnp.where(vmask[None, None, :], logits, NEG)
    col = jnp.arange(S, dtype=jnp.int32)[None, :]
    draft_lens = jnp.asarray(draft_lens, jnp.int32).reshape(B)
    # draft token judged at column i (junk at the last column — never read,
    # draft_lens <= S-1 keeps every judged column in range)
    d_next = jnp.concatenate([inputs[:, 1:], inputs[:, :1]], axis=1)
    greedy_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    if temperature is None:
        accept = greedy_tok == d_next
        t_key = c_key = None
    else:
        temperature = jnp.asarray(temperature, jnp.float32).reshape(B)
        scaled = logits / jnp.maximum(temperature, 1e-6)[:, None, None]
        if top_k is not None:
            k = jnp.asarray(top_k, jnp.int32).reshape(B)
            scaled = _top_k_filter(scaled, k[:, None])
        probs = jax.nn.softmax(scaled, axis=-1)
        p_draft = jnp.take_along_axis(probs, d_next[..., None], axis=-1)[..., 0]
        t_key, c_key = jax.random.split(key)
        u = jax.random.uniform(t_key, (B, S))
        accept = jnp.where(temperature[:, None] > 0.0, u < p_draft,
                           greedy_tok == d_next)

    if kernels.on_tpu():
        m = per_shard(spec_scan.accept_len, (accept, draft_lens),
                      (("batch", None), ("batch",)), ("batch",))
    else:
        m = spec_scan.accept_len_ref(accept, draft_lens)

    # correction / bonus token from the target distribution at position m
    l_m = jnp.take_along_axis(logits, m[:, None, None], axis=1)[:, 0]  # [B,V]
    rejected_d = jnp.take_along_axis(d_next, m[:, None], axis=1)[:, 0]
    greedy_m = jnp.argmax(l_m, axis=-1).astype(jnp.int32)
    if temperature is None:
        # greedy reject already implies argmax != d; greedy full-accept takes
        # the free bonus argmax — no residual mass to re-normalize
        t_star = greedy_m
    else:
        scaled_m = l_m / jnp.maximum(temperature, 1e-6)[:, None]
        if top_k is not None:
            scaled_m = _top_k_filter(scaled_m, k)
        # residual for a point-mass drafter: p with the rejected token
        # removed, renormalized (only when a draft was actually rejected)
        drop = (m < draft_lens)[:, None] & \
            (jnp.arange(V)[None, :] == rejected_d[:, None])
        scaled_m = jnp.where(drop, NEG, scaled_m)
        stoch = jax.random.categorical(c_key, scaled_m, axis=-1).astype(jnp.int32)
        t_star = jnp.where(temperature > 0.0, stoch, greedy_m)

    out = jnp.where(col < m[:, None], d_next, 0)
    out = jnp.where(col == m[:, None], t_star[:, None], out)
    return out, m + 1
