"""Pallas TPU fused accept-length scan for speculative decoding.

The verify step of drafter-free speculative decode (serving/spec.py,
sampler.accept_batched) reduces per-row accept flags to the length of the
accepted draft prefix: ``m[b] = #leading True in accept[b, :draft_lens[b]]``.
XLA lowers the naive formulation as a where + min-reduce pair with an int32
temp per element; this kernel fuses flag masking and the reduction into one
VMEM pass so the (tiny but per-engine-step) scan never round-trips through
HBM. One grid step — B × spec_len is far below a single VMEM tile.

``interpret`` defaults to the platform: compiled on a TPU, the Pallas
interpreter elsewhere.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_mode


def _kernel(acc_ref, len_ref, m_ref, *, S: int):
    acc = acc_ref[...]                               # [B, S] int32 (1 = accept)
    lens = len_ref[...]                              # [B, 1] int32
    col = jax.lax.broadcasted_iota(jnp.int32, acc.shape, 1)
    # first rejected draft position (S when the whole draft is accepted)
    bad = (acc == 0) & (col < lens)
    first_bad = jnp.min(jnp.where(bad, col, S), axis=1, keepdims=True)
    m_ref[...] = jnp.minimum(first_bad, lens)


def accept_len(accept, draft_lens, *, interpret: Optional[bool] = None):
    """accept [B, S] bool, draft_lens [B] int32 -> accepted prefix length [B].

    Column i of ``accept`` is the accept flag of draft token i; columns at or
    past ``draft_lens[b]`` are padding and ignored.
    """
    B, S = accept.shape
    acc = accept.astype(jnp.int32)
    lens = draft_lens.astype(jnp.int32).reshape(B, 1)
    m = pl.pallas_call(
        functools.partial(_kernel, S=S),
        out_shape=jax.ShapeDtypeStruct((B, 1), jnp.int32),
        interpret=interpret_mode(interpret),
    )(acc, lens)
    return m[:, 0]


def accept_len_ref(accept, draft_lens):
    """Pure-XLA reference (also the CPU serving path in sampler.py)."""
    S = accept.shape[1]
    col = jnp.arange(S, dtype=jnp.int32)[None, :]
    bad = (~accept) & (col < draft_lens[:, None])
    first_bad = jnp.min(jnp.where(bad, col, S), axis=1)
    return jnp.minimum(first_bad, draft_lens)
