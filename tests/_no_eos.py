"""Random weights whose greedy choices are always printable text.

Tests that count generated tokens or cut stop strings from the output must
not depend on whether seeded random weights happen to emit EOS early, or
ids that decode to nothing (JAX's default PRNG implementation decides which
weights a seed gives). ``without_eos`` edits the output embedding: every
id the tokenizer cannot decode — EOS, the other specials, unused ids — gets
a logit of exactly 0, while two byte tokens get logits ``l`` and ``-l``.
Some decodable id then always scores >= 0 and a tie goes to the lower id,
so greedy argmax never returns EOS or an undecodable id.
"""
from repro.serving.tokenizer import ByteTokenizer

_A, _B = 1, 2          # byte ids 0x01 / 0x02: never in the test prompts


def without_eos(params, cfg):
    tok = ByteTokenizer(cfg.vocab_size)
    first_unused = 256 + len(tok.merges)
    if cfg.tie_embeddings:
        t = params["embed"]["table"]
        t = t.at[first_unused:].set(0).at[_B].set(-t[_A])
        return {**params, "embed": {**params["embed"], "table": t}}
    t = params["unembed"]["table"]
    t = t.at[:, first_unused:].set(0).at[:, _B].set(-t[:, _A])
    return {**params, "unembed": {**params["unembed"], "table": t}}
