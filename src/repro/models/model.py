"""Thin façade over the functional model: init / specs / axes / entry points."""
from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.models import transformer as tfm
from repro.models.layers import init_params, logical_axes, param_specs


@functools.lru_cache(maxsize=None)
def _init_program(cfg, out_tree=None, out_leaves=()):
    """Parameter init as one program: each leaf's float32 draw fuses into
    its cast to the param dtype, so init holds no temporaries beside the
    weights (op by op, the largest leaf's float32 draw and its random bits
    sit beside them) and compiles once per configuration and placement.
    The placement comes flattened (``out_tree``, ``out_leaves``) so it can
    key the cache."""
    defs = tfm.model_defs(cfg)
    shardings = None if out_tree is None else out_tree.unflatten(out_leaves)
    return jax.jit(lambda key: init_params(defs, key, cfg.param_dtype),
                   out_shardings=shardings)


class Model:
    """Stateless model handle for one ModelConfig."""

    def __init__(self, cfg):
        self.cfg = cfg
        self.defs = tfm.model_defs(cfg)

    # ---- params ------------------------------------------------------------
    def init(self, key, shardings=None) -> Dict[str, Any]:
        """Random params from ``key``. ``shardings`` (one sharding, or a
        pytree of them matching the params) places them as they are made,
        so no device ever holds weights it does not keep."""
        if shardings is None:
            return _init_program(self.cfg)(key)
        leaves, tree = jax.tree.flatten(shardings)
        return _init_program(self.cfg, tree, tuple(leaves))(key)

    def param_specs(self):
        return param_specs(self.defs, self.cfg.param_dtype)

    def param_axes(self):
        return logical_axes(self.defs)

    def param_count(self) -> int:
        return sum(int(jnp.size(jnp.zeros(s.shape, jnp.int8)) * 0 + 1) *
                   int(functools.reduce(lambda a, b: a * b, s.shape, 1))
                   for s in jax.tree.leaves(self.param_specs()))

    # ---- caches ------------------------------------------------------------
    def cache_spec(self, batch: int, capacity: int):
        return tfm.cache_spec(self.cfg, batch, capacity)

    def init_cache(self, batch: int, capacity: int):
        return tfm.init_cache(self.cfg, batch, capacity)

    # ---- entry points --------------------------------------------------------
    def loss(self, params, batch):
        return tfm.train_loss(params, batch, self.cfg)

    def prefill(self, params, batch, cache, **kw):
        return tfm.prefill(params, batch, self.cfg, cache, **kw)

    def decode_step(self, params, batch, cache, cache_len, **kw):
        return tfm.decode_step(params, batch, self.cfg, cache, cache_len, **kw)

    def extend(self, params, batch, cache, cache_len, **kw):
        """Prefill continuation against a partially-filled cache (chunked
        prefill / shared-prefix suffix prefill / speculative replay after a
        partial draft accept). See transformer.extend."""
        return tfm.extend(params, batch, self.cfg, cache, cache_len, **kw)

    def verify(self, params, batch, cache, cache_lens, **kw):
        """Speculative-decode verify: score all draft positions in one
        forward, per-row cache lengths. See transformer.verify."""
        return tfm.verify(params, batch, self.cfg, cache, cache_lens, **kw)

    def verify_commit(self, staged, cache_lens, ns, lens):
        """Resolve a verify call's staged record to the committed cache at
        each row's accepted length (batched accept-rewind for stateful
        blocks; identity for linear full attention). See
        transformer.verify_commit."""
        return tfm.verify_commit(self.cfg, staged, cache_lens, ns, lens)

    # ---- input construction ------------------------------------------------
    def make_batch(self, tokens_or_frames, *, labels=None, positions=None, start=0):
        cfg = self.cfg
        key = "frames" if cfg.modality == "audio_frames" else "tokens"
        arr = tokens_or_frames
        B, S = arr.shape[0], arr.shape[1]
        if positions is None:
            positions = jnp.broadcast_to(start + jnp.arange(S)[None, :], (B, S))
        batch = {key: arr, "positions": positions}
        if labels is not None:
            batch["labels"] = labels
        return batch
