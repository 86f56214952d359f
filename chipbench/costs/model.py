"""Operations a dense GQA decoder needs per token, and its bytes.

Counts the work the algorithm requires, not what an implementation does:
a multiply-add is two operations, attention is causal over the real
context (a token at position t attends to t + 1 keys), K and V are at
KV-head width, and logits are computed only where a token is sampled.
Bytes are bfloat16 (2 per element), as the configurations serve.
"""
from __future__ import annotations

from typing import Dict

BYTES = 2


def linear_params(d: Dict[str, int]) -> int:
    """Weights each token multiplies through in the layers (no embedding,
    no head): the reference's own count where its ``dims`` gives one, else
    that of a dense GQA stack."""
    if "linear_params" in d:
        return d["linear_params"]
    D, H, K, hd, F = d["D"], d["H"], d["K"], d["hd"], d["F"]
    return d["L"] * (D * H * hd + 2 * D * K * hd + H * hd * D + 3 * D * F)


def weight_bytes(d: Dict[str, int], tied: bool) -> int:
    """Bytes of every weight a forward pass reads: layers, the head, and
    the embedding rows (counted as the head when tied)."""
    head = d["D"] * d["Vp"]
    return BYTES * (linear_params(d) + head * (1 if tied else 2))


def kv_bytes_per_position(d: Dict[str, int]) -> int:
    """K and V of one position, all layers."""
    return BYTES * 2 * d["L"] * d["K"] * d["hd"]


def attention_flops(d: Dict[str, int], contexts: int) -> int:
    """Scores and weighted values, summed over tokens whose contexts
    (keys attended) add up to ``contexts``."""
    return 4 * d["L"] * d["H"] * d["hd"] * contexts


def logits_flops(d: Dict[str, int], sampled: int) -> int:
    return 2 * d["D"] * d["V"] * sampled


def causal_contexts(start: int, stop: int) -> int:
    """Sum of the contexts of the tokens at positions start..stop-1."""
    return (stop * (stop + 1) - start * (start + 1)) // 2


def tokens_flops(d: Dict[str, int], n_tokens: int, contexts: int,
                 sampled: int) -> int:
    """Required operations for ``n_tokens`` tokens through the model."""
    return (2 * linear_params(d) * n_tokens + attention_flops(d, contexts)
            + logits_flops(d, sampled))
