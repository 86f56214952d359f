"""Decides ``correct``: the served tokens against the float32 reference.

Once the window has closed and the server is gone, a sample of the turns
the window finished, drawn from the seed and holding the longest of them,
goes through the plain reference: one forward pass over each turn's
prompt and served tokens. A greedy token is the served model's argmax, so
at each served position the reference's best logit minus the served
token's reference logit is zero, or a small gap where bf16 rounding broke
a near-tie. The widest such gap over the sample is compared with the
cell's limit. Turns due in the window that never finished are compared
with 0.

``control_gap`` is the same reading for the reference itself computed in
8-bit floats (the control, run by ``control.py`` and the tests, never by
the benchmark's runs): at each position of the same sequences, the gap of
the token the low-precision pass puts first.
"""
from __future__ import annotations

import functools
import json
import random
from typing import Dict, List

import numpy as np

MIN_SEQ = 1024


def sample(records, seed: int, tokens: int) -> List:
    """Finished turns due in the window: the longest, then others drawn
    from the seed until they hold ``tokens`` served tokens."""
    done = [r for r in records if r.measured and r.ok and len(r.output)]
    if not done:
        return []
    done.sort(key=lambda r: (len(r.prompt) + len(r.output), r.due))
    picked, rest = [done[-1]], done[:-1]
    random.Random(seed).shuffle(rest)
    while rest and sum(len(r.output) for r in picked) < tokens:
        picked.append(rest.pop())
    return picked


@functools.lru_cache(maxsize=None)
def _program(ref_path: str, spec_json: str, precision: str):
    import jax
    import jax.numpy as jnp
    import manifest
    ref = manifest.module(__import__("pathlib").Path(ref_path))
    spec = json.loads(spec_json)

    def gaps(params, tokens, rows, served, n):
        """Per row: the reference's best logit minus that of the served
        token (or, for the control, of its own first choice)."""
        with jax.default_matmul_precision("highest"):
            best = ref.forward(params, tokens, rows, spec, "float32")
            top = best.max(-1)
            if precision == "float32":
                pick = served
            else:
                pick = jnp.argmax(ref.forward(params, tokens, rows, spec,
                                              precision), -1)
        gap = top - jnp.take_along_axis(best, pick[:, None], -1)[:, 0]
        return jnp.where(jnp.arange(rows.shape[0]) < n, gap, 0.0)
    return jax.jit(gaps)


def gap_readings(picked, params, spec: Dict, ref_path, precision: str,
                 rows_len: int) -> np.ndarray:
    """Each picked turn's widest gap, in ``precision``."""
    import jax.numpy as jnp
    fn = _program(str(ref_path), json.dumps(spec, sort_keys=True), precision)
    out = []
    for r in picked:
        seq = np.concatenate([r.prompt, r.output[:-1]]).astype(np.int32)
        S = max(MIN_SEQ, 1 << (len(seq) - 1).bit_length())
        tokens = np.zeros(S, np.int32)
        tokens[:len(seq)] = seq
        n = len(r.output)
        # output token i was sampled from the logits at position P - 1 + i
        rows = np.full(rows_len, len(r.prompt) - 1 + n - 1, np.int32)
        rows[:n] = len(r.prompt) - 1 + np.arange(n)
        served = np.zeros(rows_len, np.int32)
        served[:n] = r.output
        g = fn(params, jnp.asarray(tokens), jnp.asarray(rows),
               jnp.asarray(served), jnp.int32(n))
        out.append(float(g.max()))
    return np.asarray(out)


def compare(records, params, spec: Dict, ref_path, cell: Dict,
            seed: int) -> Dict[str, Dict]:
    """The numbers ``correct`` rests on, each with its limit (a value of
    None, nothing to compare, fails), and how many turns and served
    tokens the check read."""
    chk = cell["check"]
    picked = sample(records, seed, chk["served_tokens"])
    rows_len = max(r.max_new for r in records)
    gaps = gap_readings(picked, params, spec, ref_path, "float32", rows_len)
    unfinished = sum(1 for r in records if r.measured and not r.ok)
    return {
        "unfinished_turns": {"value": unfinished, "limit": 0},
        "max_logit_gap": {"value": float(gaps.max()) if len(gaps) else None,
                          "limit": chk["max_logit_gap"]},
    }, {"turns": len(picked),
        "served_tokens": int(sum(len(r.output) for r in picked))}


def holds(compared: Dict[str, Dict]) -> bool:
    return all(c["value"] is not None and c["value"] <= c["limit"]
               for c in compared.values())
