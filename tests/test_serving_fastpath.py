"""Serving fast path: bucketed prefill, chunked decode, on-device sampling."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs.registry import ARCHS
from repro.models import Model
from repro.serving.engine import EngineConfig, ServingEngine, _auto_buckets
from repro.serving.sampler import sample_batched

from tests._no_eos import without_eos


@pytest.fixture(scope="module")
def cfg():
    return ARCHS["qwen2.5-3b"].reduced(dtype="float32", param_dtype="float32",
                                       vocab_size=512)


@pytest.fixture(scope="module")
def engine(cfg):
    params = without_eos(Model(cfg).init(jax.random.PRNGKey(0)), cfg)
    return ServingEngine(cfg, num_slots=3, capacity=96, params=params)


# ---------------------------------------------------------------------------
# bucketed prefill
# ---------------------------------------------------------------------------


def test_auto_buckets_cover_capacity():
    assert _auto_buckets(96) == (32, 64, 96)
    assert _auto_buckets(512) == (32, 64, 128, 256, 512)
    assert _auto_buckets(16) == (16,)


def test_mixed_lengths_share_one_compiled_bucket(engine):
    """Prompts of different lengths in one bucket -> one prefill compile."""
    before = engine.stats()["prefill_compiles"]
    # 5, 12, and 25 chars -> 6..26 tokens, all within the 32-token bucket
    for p in ("short", "medium p " * 2, "quite a bit longer yet, ok"):
        engine.generate(p, max_new_tokens=4)
    after = engine.stats()["prefill_compiles"]
    assert after - before <= 1
    assert after <= len(engine.buckets)


def test_compile_count_bounded_by_buckets(engine):
    """Many distinct prompt lengths never exceed one compile per bucket."""
    for n in (3, 9, 17, 33, 41, 57, 70):
        engine.generate("x" * n, max_new_tokens=2)
    assert engine.stats()["prefill_compiles"] <= len(engine.buckets)


def test_capacity_rounded_up_to_block_w(cfg):
    eng = ServingEngine(cfg, num_slots=2, capacity=100,
                        engine_cfg=EngineConfig(block_w=64))
    assert eng.capacity == 128
    assert eng.cfg.decode_block_w == 64
    # capacity below block_w stays as requested (kernel clamps the block)
    eng2 = ServingEngine(cfg, num_slots=2, capacity=96, params=eng.params)
    assert eng2.capacity == 96


# ---------------------------------------------------------------------------
# continuous batching: admission / eviction / equivalence
# ---------------------------------------------------------------------------


def test_admission_fifo_and_eviction_under_full_queue(engine):
    """More requests than slots: FIFO admission, slots recycled, all finish."""
    reqs = [engine.submit(f"queued request number {i}", max_new_tokens=6)
            for i in range(8)]
    engine.run_until_drained()
    assert all(r.output_tokens == 6 for r in reqs)
    admit_order = [r.admit_index for r in reqs]
    assert admit_order == sorted(admit_order), admit_order
    assert all(s.request is None for s in engine.slots)


def test_chunked_equals_single_token_greedy(cfg, engine):
    """New chunked path == old one-token-per-step path, greedy decode."""
    legacy = ServingEngine(cfg, num_slots=3, capacity=96, params=engine.params,
                           engine_cfg=EngineConfig(prefill_buckets=(),
                                                   decode_chunk=1))
    prompts = ["alpha", "a rather longer prompt for the second slot here",
               "mid-size prompt text"]
    fast_out = [engine.generate(p, max_new_tokens=8) for p in prompts]
    legacy_out = [legacy.generate(p, max_new_tokens=8) for p in prompts]
    assert fast_out == legacy_out
    # and chunk=1 through the same bucketed path also agrees
    chunk1 = ServingEngine(cfg, num_slots=3, capacity=96, params=engine.params,
                           engine_cfg=EngineConfig(decode_chunk=1))
    assert [chunk1.generate(p, max_new_tokens=8) for p in prompts] == fast_out


@pytest.mark.parametrize("arch", ["recurrentgemma-9b", "xlstm-350m"])
def test_bucketed_prefill_exact_for_stateful_archs(arch):
    """Right-padded (bucketed) prefill must be bit-identical to exact-length
    prefill for recurrent / conv / mLSTM / sLSTM / windowed-attention state —
    the valid-prefix masks in models/{rglru,xlstm,transformer}.py."""
    acfg = ARCHS[arch].reduced(dtype="float32", param_dtype="float32",
                               vocab_size=512)
    fast = ServingEngine(acfg, num_slots=2, capacity=64)
    exact = ServingEngine(acfg, num_slots=2, capacity=64, params=fast.params,
                          engine_cfg=EngineConfig(prefill_buckets=(),
                                                  decode_chunk=1))
    prompts = ["tiny", "a prompt long enough to cross the conv window edge"]
    assert [fast.generate(p, max_new_tokens=6) for p in prompts] == \
           [exact.generate(p, max_new_tokens=6) for p in prompts]


def test_decode_chunk_must_be_positive(cfg):
    with pytest.raises(ValueError):
        ServingEngine(cfg, num_slots=1, capacity=64,
                      engine_cfg=EngineConfig(decode_chunk=0))


def test_per_request_temperature_honored(cfg, engine):
    """Same seed + same sampling params -> identical text; decode is no
    longer hard-wired greedy (seed engine ignored Request.temperature)."""
    e1 = ServingEngine(cfg, num_slots=2, capacity=96, params=engine.params,
                       seed=11)
    e2 = ServingEngine(cfg, num_slots=2, capacity=96, params=engine.params,
                       seed=11)
    s1 = e1.generate("sample this", max_new_tokens=8, temperature=1.3, top_k=20)
    s2 = e2.generate("sample this", max_new_tokens=8, temperature=1.3, top_k=20)
    assert s1 == s2
    r1 = e1.submit("mixed batch greedy", max_new_tokens=8)
    r2 = e1.submit("mixed batch sampled", max_new_tokens=8, temperature=1.3)
    e1.run_until_drained()
    assert r1.output_tokens == 8 and r2.output_tokens == 8
    # the greedy request must match a pure-greedy engine's output
    assert engine.generate("mixed batch greedy",
                           max_new_tokens=8) == r1.output_text


def test_host_syncs_at_most_one_per_chunk(engine):
    s0 = engine.stats()
    engine.generate("count my syncs please", max_new_tokens=12)
    s1 = engine.stats()
    assert s1["host_syncs"] - s0["host_syncs"] <= \
        s1["decode_chunks"] - s0["decode_chunks"]
    assert s1["host_syncs_per_token"] <= 1.0 / min(
        engine.engine_cfg.decode_chunk, 12) + 0.51


# ---------------------------------------------------------------------------
# chunked prefill: prompts past the largest bucket extend chunk by chunk
# ---------------------------------------------------------------------------


def test_chunked_prefill_matches_single_shot(cfg, engine):
    """Buckets smaller than the prompt no longer fall back to exact-length
    compiles: the prompt prefills in bucket-sized chunks (model.extend) and
    the outputs match the single-bucket engine bit for bit."""
    small = ServingEngine(cfg, num_slots=3, capacity=96, params=engine.params,
                          engine_cfg=EngineConfig(prefill_buckets=(32,)))
    prompts = ["tiny",
               "a prompt that is comfortably longer than one thirty-two "
               "token bucket and so must be chunked across extends"]
    outs = [small.generate(p, max_new_tokens=6) for p in prompts]
    assert outs == [engine.generate(p, max_new_tokens=6) for p in prompts]
    s = small.stats()
    assert s["extend_chunks"] >= 1
    # compile count stays bounded: one prefill bucket + extend chunk shapes
    assert s["prefill_compiles"] <= 1
    assert s["extend_compiles"] <= len(small.buckets) + 1


@pytest.mark.parametrize("arch", ["recurrentgemma-9b", "xlstm-350m",
                                  "mixtral-8x22b"])
def test_chunked_prefill_exact_for_stateful_archs(arch):
    """Extend must resume recurrent / conv / xLSTM state and ring-spliced
    windowed KV exactly — chunked == single-shot for every cache family."""
    acfg = ARCHS[arch].reduced(dtype="float32", param_dtype="float32",
                               vocab_size=512)
    single = ServingEngine(acfg, num_slots=2, capacity=96)
    chunked = ServingEngine(acfg, num_slots=2, capacity=96,
                            params=single.params,
                            engine_cfg=EngineConfig(prefill_buckets=(32,)))
    prompts = ["short one",
               "a much longer prompt crossing the recurrent conv window and "
               "the local attention window and the bucket boundary at once"]
    assert [chunked.generate(p, max_new_tokens=6) for p in prompts] == \
           [single.generate(p, max_new_tokens=6) for p in prompts]
    assert chunked.stats()["extend_chunks"] >= 1


# ---------------------------------------------------------------------------
# prompt accounting satellites: truncation counter + padding waste
# ---------------------------------------------------------------------------


def test_truncation_recorded_not_silent(cfg):
    eng = ServingEngine(cfg, num_slots=1, capacity=64)
    window = eng.capacity - 8 - 1
    long_prompt = "x" * 200                    # > window tokens, must truncate
    req = eng.submit(long_prompt, max_new_tokens=8)
    eng.run_until_drained()
    assert req.prompt_tokens == window
    assert req.truncated_tokens > 0
    s = eng.stats()
    assert s["truncated_requests"] == 1
    assert s["truncated_tokens"] == req.truncated_tokens
    # short prompts don't count
    req2 = eng.submit("hi", max_new_tokens=4)
    eng.run_until_drained()
    assert req2.truncated_tokens == 0
    assert eng.stats()["truncated_requests"] == 1


def test_padding_waste_reported(cfg, engine):
    eng = ServingEngine(cfg, num_slots=1, capacity=96, params=engine.params)
    req = eng.submit("abcde", max_new_tokens=4)    # 6 tokens -> 32 bucket
    eng.run_until_drained()
    s = eng.stats()
    assert s["prompt_tokens"] == req.prompt_tokens
    assert s["prefill_pad_tokens"] == 32 - req.prompt_tokens
    assert 0.0 < s["prefill_pad_frac"] < 1.0


# ---------------------------------------------------------------------------
# admission guard (satellite): max_new_tokens vs capacity
# ---------------------------------------------------------------------------


def test_submit_rejects_unsatisfiable_budget(engine):
    with pytest.raises(ValueError):
        engine.submit("p", max_new_tokens=engine.capacity - 1)
    with pytest.raises(ValueError):
        engine.submit("p", max_new_tokens=engine.capacity + 5)
    with pytest.raises(ValueError):
        engine.submit("p", max_new_tokens=0)
    # boundary: capacity - 2 leaves a 1-token prompt window and must work
    req = engine.submit("q", max_new_tokens=engine.capacity - 2)
    engine.run_until_drained()
    assert req.prompt_tokens == 1 and req.output_tokens >= 1


# ---------------------------------------------------------------------------
# on-device batched sampler
# ---------------------------------------------------------------------------


def test_sample_batched_per_row_params():
    import jax
    logits = jnp.asarray([[0.0, 1.0, 5.0, 2.0, -1.0],
                          [5.0, 1.0, 0.0, 2.0, -1.0],
                          [0.0, 1.0, 2.0, 9.0, -1.0]])
    key = jax.random.PRNGKey(0)
    # all-greedy rows == argmax; None temperature means statically greedy
    out = sample_batched(logits, key, temperature=jnp.zeros(3))
    assert out.tolist() == [2, 0, 3]
    assert sample_batched(logits, None, temperature=None).tolist() == [2, 0, 3]
    # vocab_limit masks the tail ids
    out = sample_batched(logits, key, temperature=jnp.zeros(3), vocab_limit=3)
    assert out.tolist() == [2, 0, 2]
    # mixed greedy/stochastic rows: greedy rows stay argmax, sampled rows
    # with top_k=1 are forced to the argmax too (degenerate top-k)
    temps = jnp.asarray([0.0, 2.0, 2.0])
    ks = jnp.asarray([0, 1, 1], jnp.int32)
    out = sample_batched(logits, key, temperature=temps, top_k=ks)
    assert out.tolist() == [2, 0, 3]
    # high-temperature sampling stays inside the vocab limit
    for s in range(5):
        out = sample_batched(logits, jax.random.PRNGKey(s),
                             temperature=jnp.full((3,), 50.0), vocab_limit=4)
        assert int(out.max()) < 4


def test_sample_batched_topk_ge_vocab_is_no_filter():
    """top_k >= V must degenerate to an unfiltered sample (the k-th largest
    is then the global minimum; the V - k index is clipped, never negative),
    bit-identical to top_k=0 under the same key."""
    import jax
    key = jax.random.PRNGKey(7)
    logits = jax.random.normal(key, (4, 5))
    temps = jnp.full((4,), 1.3)
    ref = sample_batched(logits, key, temperature=temps,
                         top_k=jnp.zeros(4, jnp.int32))
    for k in (5, 6, 100):
        out = sample_batched(logits, key, temperature=temps,
                             top_k=jnp.full((4,), k, jnp.int32))
        assert out.tolist() == ref.tolist(), k
    # mixed rows: only the filtered row may differ from no-filter
    mixed = sample_batched(logits, key, temperature=temps,
                           top_k=jnp.asarray([1, 9, 0, 5], jnp.int32))
    assert mixed[1:].tolist() == ref[1:].tolist()
    assert int(mixed[0]) == int(jnp.argmax(logits[0]))      # top-1 == argmax


def test_sample_batched_topk_composes_with_vocab_limit():
    """vocab_limit masks ids to -inf BEFORE top-k: a top_k spanning the
    whole limited vocab equals vocab-limit-only sampling, and masked ids are
    never produced even when top_k counts past them."""
    import jax
    key = jax.random.PRNGKey(11)
    logits = jax.random.normal(key, (3, 8))
    temps = jnp.full((3,), 2.0)
    ref = sample_batched(logits, key, temperature=temps, vocab_limit=3,
                         top_k=jnp.zeros(3, jnp.int32))
    for k in (3, 7, 8, 50):                # k >= effective vocab -> no filter
        out = sample_batched(logits, key, temperature=temps, vocab_limit=3,
                             top_k=jnp.full((3,), k, jnp.int32))
        assert out.tolist() == ref.tolist(), k
    for s in range(6):                     # masked ids never sampled
        out = sample_batched(logits, jax.random.PRNGKey(s),
                             temperature=jnp.full((3,), 50.0), vocab_limit=3,
                             top_k=jnp.full((3,), 6, jnp.int32))
        assert int(out.max()) < 3
