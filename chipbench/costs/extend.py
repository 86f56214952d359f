"""Least work of one admission's prefill: its suffix through the model.

A suffix of ``s`` tokens at positions ``p .. p + s - 1`` (``p`` prompt
tokens served from the prefix cache) needs the linear layers for ``s``
tokens, causal attention over the real context, logits for the one
sampled position, and at least: every weight read once, the cached K/V of
the context read once, and the suffix's K/V written once.
"""
from __future__ import annotations

from typing import Dict

from costs import model


def cost(d: Dict[str, int], tied: bool, start: int, stop: int
         ) -> Dict[str, int]:
    s = stop - start
    flops = model.tokens_flops(d, s, model.causal_contexts(start, stop), 1)
    kv = model.kv_bytes_per_position(d)
    bytes_ = model.weight_bytes(d, tied) + kv * start + kv * s
    return {"flops": flops, "bytes": bytes_}
