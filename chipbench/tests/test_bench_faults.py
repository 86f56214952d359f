"""A whole run on the CPU at toy sizes, with the chip check skipped: sound,
it comes out correct; with the timed path broken underneath, it does
not. The limit here is the toy model's, not a cell's."""
import jax
import pytest

import _tiny
import run

LIMIT = 0.02    # toy model: sound runs read under 0.006 (bf16 vs float32),
                # the 8-bit control above 0.05


def _alter_tokens(server):
    """A token altered where it is produced: the first token of every
    decode chunk comes out one id higher."""
    progs = server.engine.progs
    orig = progs.decode_chunk

    def broken(*args):
        cache, tok, emit, clens, rem, done = orig(*args)
        vocab = server.engine.cfg.vocab_size - 3
        return cache, tok.at[0].set((tok[0] + 1) % vocab), emit, clens, \
            rem, done
    progs.decode_chunk = broken


def _state_unchanged(server):
    """A decode step that returns its state unchanged: the chunk's K/V
    writes are dropped, every step reads the cache as it was."""
    progs = server.engine.progs
    fn = jax.jit(lambda *a: (a[1],) + progs._decode_chunk_fn(*a)[1:])
    progs._decode_chunk_jit = fn


def _run(tmp_path, fault=None, kind="agent", load=None):
    wl = _tiny.workload(kind, max_logit_gap=LIMIT, load=load)
    return run.run_cell(wl, seed=2 ** 31 + 5, seconds=2.0, trace=False,
                        require_tpu=False, fault=fault, out_dir=tmp_path)


@pytest.mark.parametrize("kind", ["agent", "open"])
def test_sound_run_is_correct(tmp_path, kind):
    res = _run(tmp_path, kind=kind)
    assert res["correct"], res["compared"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "compared"
    assert set(res["metrics"]) == {"ttft_p95_s", "tpot_p95_ms",
                                   "output_tok_s", "setup_s"}


def test_closed_loop_runs_conversations_back_to_back(tmp_path):
    res = _run(tmp_path, load="closed")
    assert res["correct"], res["compared"]
    assert res["attempted"] > 0 and res["failed"] == 0


@pytest.mark.parametrize("fault", [_alter_tokens, _state_unchanged])
def test_broken_timed_path_is_not_correct(tmp_path, fault):
    res = _run(tmp_path, fault)
    assert not res["correct"]
    assert res["compared"]["max_logit_gap"]["value"] > LIMIT
