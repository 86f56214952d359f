"""Least work of the paged decode attention kernel for one decode step of
one sequence: its query against the K/V of its real context.

A step that attends over ``c`` positions reads the K and V of those ``c``
positions in every layer, reads its query and writes its output (H heads),
and computes scores and weighted values over them.
"""
from __future__ import annotations

from typing import Dict

from costs import model


def cost(d: Dict[str, int], contexts: int, steps: int) -> Dict[str, int]:
    """Over ``steps`` sequence-steps whose contexts add up to
    ``contexts``."""
    qo = model.BYTES * 2 * d["L"] * d["H"] * d["hd"] * steps
    return {"flops": model.attention_flops(d, contexts),
            "bytes": model.kv_bytes_per_position(d) * contexts + qo}
