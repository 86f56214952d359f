"""Session-oriented serving API (repro.serving.server): handle/stream/cancel
semantics, multi-turn session state reuse, per-request RNG reproducibility,
stop sequences, priority classes, and the deprecation shim — with the
acceptance invariant that greedy outputs through ``LLMServer`` are
bit-identical to the pre-redesign ``ServingEngine.generate`` in dense,
paged, and snapshot cache modes."""
import pytest

from repro.configs.registry import ARCHS
from repro.serving.engine import ServingEngine
from repro.serving.scheduler import SamplingParams, Scheduler
from repro.serving.server import EngineConfig, LLMServer

from tests._hypothesis_compat import given, settings, st
from tests._no_eos import without_eos


def _cfg(arch):
    return ARCHS[arch].reduced(dtype="float32", param_dtype="float32",
                               vocab_size=512)


@pytest.fixture(scope="module")
def qwen():
    return _cfg("qwen2.5-3b")


@pytest.fixture(scope="module")
def qwen_params(qwen):
    from repro.models import Model
    import jax
    return Model(qwen).init(jax.random.PRNGKey(0))


# ---------------------------------------------------------------------------
# acceptance: LLMServer == pre-redesign ServingEngine.generate, greedy,
# across all three cache modes — and concurrent handles co-batch
# ---------------------------------------------------------------------------

MODES = [("qwen2.5-3b", "dense"), ("qwen2.5-3b", "paged"),
         ("recurrentgemma-9b", "paged")]          # paged resolves: pages/snaps

PROMPTS = ["alpha prompt for slot one",
           "a rather longer second prompt that crosses a bucket",
           "third prompt"]


@pytest.mark.parametrize("arch,mode", MODES)
def test_server_greedy_bit_identical_to_engine(arch, mode):
    cfg = _cfg(arch)
    ecfg = EngineConfig(cache_mode=mode, page_size=16)
    eng = ServingEngine(cfg, num_slots=3, capacity=128, engine_cfg=ecfg)
    with pytest.warns(DeprecationWarning):
        ref = [eng.generate(p, max_new_tokens=8) for p in PROMPTS]
    srv = LLMServer(cfg, num_slots=3, capacity=128, params=eng.params,
                    engine_cfg=ecfg)
    handles = [srv.submit(p, SamplingParams(max_new_tokens=8))
               for p in PROMPTS]                  # all queued before any runs
    srv.run_until_idle()
    assert [h.result() for h in handles] == ref, (arch, mode)
    # the three concurrent handles actually shared engine steps
    assert srv.stats()["active_slots_per_step"] > 1.0


# ---------------------------------------------------------------------------
# sessions: multi-turn reuse at non-block-aligned boundaries, bit-identical
# ---------------------------------------------------------------------------

SYS = "System: cooperating agents share this conversation verbatim. "
TURNS = ["[planner] Plan the next step of the task. ",
         "[actor] Act: call the search tool now. ",
         "[evaluator] Evaluate the tool output please. "]


@pytest.mark.parametrize("arch", ["qwen2.5-3b", "recurrentgemma-9b"])
def test_session_multi_turn_reuse_bit_identical(arch):
    """Turn N+1 restores turn N's end-of-generation state (partial tail
    page / tail snapshot — NON-block-aligned) and prefills only the new
    message; greedy outputs must equal a fresh engine fed the exact same
    token stream."""
    cfg = _cfg(arch)
    ps = 16
    srv = LLMServer(cfg, num_slots=2, capacity=192,
                    engine_cfg=EngineConfig(cache_mode="paged", page_size=ps))
    fresh = Scheduler(cfg, num_slots=2, capacity=192, params=srv.params)
    sess = srv.open_session()
    sp = SamplingParams(max_new_tokens=9)
    prompt = SYS
    hits, tails = [], []
    for turn in TURNS:
        prompt = sess.text + turn if sess.text else prompt + turn
        tails.append(srv.engine._sessions[sess.sid].tail_len)
        h = sess.submit(prompt, sp)
        out = h.result()
        r = fresh.enqueue(prompt, sp, token_ids=h.request._ids)
        fresh.run_until_drained()
        assert r.output_text == out, (arch, turn)
        hits.append(h.request.prefix_hit_tokens)
    st = srv.stats()
    assert st["session_turns"] == 3
    assert st["turn_prefix_hits"] >= 2           # every later turn reused
    # each later turn restored EXACTLY the previous end-of-generation
    # boundary (prompt + generated) — non-block-aligned, which a radix hit
    # alone cannot reach
    assert hits[1:] == tails[1:], (hits, tails)
    assert any(t % ps for t in tails[1:]), tails
    sess.close()


def test_session_dense_mode_token_exact_no_reuse(qwen, qwen_params):
    """Dense cache mode has nothing to retain, but session turns must still
    continue the exact token stream (prompt + generated), matching a fresh
    engine fed the same ids — with zero prefix hits."""
    srv = LLMServer(qwen, num_slots=2, capacity=192, params=qwen_params)
    fresh = Scheduler(qwen, num_slots=2, capacity=192, params=qwen_params)
    sess = srv.open_session()
    sp = SamplingParams(max_new_tokens=7)
    prompt = SYS + TURNS[0]
    for turn in TURNS[1:]:
        out = sess.submit(prompt, sp).result()
        prompt = sess.text + turn
    h = sess.submit(prompt, sp)
    out = h.result()
    assert len(h.request._ids) > len(srv.engine.tokenizer.encode(TURNS[-1]))
    r = fresh.enqueue(prompt, sp, token_ids=h.request._ids)
    fresh.run_until_drained()
    assert r.output_text == out
    assert h.request.prefix_hit_tokens == 0
    sess.close()


def test_session_history_rewrite_falls_back(qwen, qwen_params):
    """A turn that does NOT extend the session's conversation resets the
    retained tail and still serves correctly."""
    srv = LLMServer(qwen, num_slots=2, capacity=128, params=qwen_params,
                    engine_cfg=EngineConfig(cache_mode="paged"))
    sess = srv.open_session()
    sp = SamplingParams(max_new_tokens=6)
    sess.submit(SYS + TURNS[0], sp).result()
    rewritten = "totally different conversation history. " + TURNS[1]
    out = sess.submit(rewritten, sp).result()
    eng = Scheduler(qwen, num_slots=2, capacity=128, params=qwen_params)
    ref = eng.enqueue(rewritten, sp)
    eng.run_until_drained()
    assert out == ref.output_text
    sess.close()
    # everything the session retained was released on reset/close
    eng2 = srv.engine
    owned = eng2.radix.check_invariants()
    free = set(eng2.kvpool._free)
    assert len(owned) + len(free) == eng2.kvpool.num_pages - eng2.kvpool.reserved


def test_session_single_turn_in_flight(qwen, qwen_params):
    srv = LLMServer(qwen, num_slots=2, capacity=96, params=qwen_params)
    sess = srv.open_session()
    sess.submit("first turn", SamplingParams(max_new_tokens=4))
    with pytest.raises(RuntimeError):
        sess.submit("second turn before the first drained",
                    SamplingParams(max_new_tokens=4))
    srv.run_until_idle()
    sess.close()


# ---------------------------------------------------------------------------
# streaming
# ---------------------------------------------------------------------------


def test_stream_utf8_holdback_boundaries():
    """A multi-byte UTF-8 character split across chunk syncs must be held
    back until complete: at every boundary the holdback allows, the split
    decode equals the full decode (so the concatenated stream equals
    ``result()`` byte-for-byte)."""
    from repro.serving.server import _utf8_holdback
    from repro.serving.tokenizer import ByteTokenizer
    tok = ByteTokenizer(512)
    streams = [
        list("café!".encode()),                  # 2-byte char
        list("a€ b".encode()),                   # 3-byte char
        list("x\U0001f600y".encode()),                # 4-byte char
        [ord("a"), 0xC3],                             # ends mid-sequence
        [ord("a"), 0xE2, 0x82],                       # ends mid-3-byte
        [0x80, 0x80, ord("b")],                       # stray continuations
        [0xC0, 0x80, ord("c")],                       # invalid lead
        [260, 0xC3, 0xA9, 261],                       # merges around a char
    ]
    for ids in streams:
        full = tok.decode(ids)
        for k in range(len(ids) + 1):
            hb = _utf8_holdback(ids[:k])
            cut = k - hb
            assert tok.decode(ids[:cut]) + tok.decode(ids[cut:]) == full, \
                (ids, k, hb)
        # the holdback never withholds a complete stream
        assert _utf8_holdback(ids) <= 3


def test_jaxllm_concurrent_same_role_falls_back(qwen, qwen_params):
    """Two concurrent workflows sharing one role prompt must both serve:
    the second submit finds the role's session busy and degrades to a
    sessionless (still co-batched) request instead of raising."""
    from repro.core.llm import JaxLLM
    srv = LLMServer(qwen, num_slots=2, capacity=96, params=qwen_params)
    llm = JaxLLM(srv, max_new_tokens=5)
    h1 = llm.submit("shared planner prompt", "workflow one context")
    h2 = llm.submit("shared planner prompt", "workflow two context")
    srv.run_until_idle()
    h1.result(), h2.result()
    assert h1.request.output_tokens == h2.request.output_tokens == 5
    assert srv.stats()["sessions_opened"] == 1


@pytest.mark.parametrize("arch,want_drafts", [
    ("qwen2.5-3b", True),           # copy prompts reliably draft on qwen
    ("recurrentgemma-9b", False),   # stateful tail-snapshot path; untrained
])                                  # weights may not reach the copy regime
def test_session_with_spec_decode_matches_fresh(arch, want_drafts):
    """Sessions + speculative decoding: the tail state restored by turn N+1
    must reflect exactly the kept tokens even when verify commits drafts,
    staying bit-identical to a fresh engine on the same stream."""
    cfg = _cfg(arch)
    srv = LLMServer(cfg, num_slots=2, capacity=256,
                    engine_cfg=EngineConfig(cache_mode="paged", page_size=16,
                                            spec_len=6))
    fresh = Scheduler(cfg, num_slots=2, capacity=256, params=srv.params)
    sess = srv.open_session()
    sp = SamplingParams(max_new_tokens=32)
    prompt = SYS + "Tool result: ERROR 429 rate limit exceeded at gateway. " * 2
    for turn in TURNS[:2]:
        prompt = (sess.text or prompt) + turn
        h = sess.submit(prompt, sp)
        out = h.result()
        r = fresh.enqueue(prompt, sp, token_ids=h.request._ids)
        fresh.run_until_drained()
        assert r.output_text == out, (arch, turn)
    if want_drafts:
        assert srv.stats()["draft_tokens"] > 0   # speculation actually ran
    sess.close()


def test_spec_eos_truncation_skips_tail_snapshot():
    """Regression: a spec accept truncated at EOS leaves the device state
    ahead of the kept tokens (verify_commit rewound to the full accepted
    length) — the session tail snapshot for that turn must be SKIPPED, not
    captured from the over-advanced state."""
    cfg = _cfg("recurrentgemma-9b")
    srv = LLMServer(cfg, num_slots=1, capacity=128,
                    engine_cfg=EngineConfig(cache_mode="paged", page_size=16,
                                            spec_len=5, decode_chunk=2))
    eng = srv.engine
    sess = srv.open_session()
    h = sess.submit(SYS + TURNS[0], SamplingParams(max_new_tokens=40))
    srv.step()                                   # admit + one decode chunk
    slot = eng.slots[0]
    assert slot.request is h.request
    eos = eng.tokenizer.eos_id
    # simulate a verify outcome whose 4 emitted tokens contain EOS at
    # position 1: the host keeps 2 tokens, the device state processed 4
    eng._commit_spec(0, [1, 2, 3], [7, eos, 9, 10], 4, 0.0)
    assert h.request.finished                    # EOS ended the request
    assert h.request.output_ids[-1] == eos
    st = eng._sessions[sess.sid]
    assert st.tail_snap == -1                    # capture skipped
    assert st.all_tokens == h.request._ids + h.request.output_ids
    # the conversation still continues correctly off the radix/trie path
    h2 = sess.submit(st.text + TURNS[1], SamplingParams(max_new_tokens=6))
    fresh = Scheduler(cfg, num_slots=1, capacity=128, params=srv.params)
    out = h2.result()
    r = fresh.enqueue("", SamplingParams(max_new_tokens=6),
                      token_ids=h2.request._ids)
    fresh.run_until_drained()
    assert r.output_text == out
    sess.close()


def test_stream_increments_concatenate_to_result(qwen, qwen_params):
    srv = LLMServer(qwen, num_slots=2, capacity=96, params=qwen_params,
                    engine_cfg=EngineConfig(decode_chunk=2))
    h = srv.submit("stream me some text please",
                   SamplingParams(max_new_tokens=12))
    pieces = list(h.stream())
    assert len(pieces) >= 2                       # incremental, not one blob
    assert "".join(pieces) == h.result() == h.text
    assert h.status().value == "completed"
    assert srv.stats()["stream_chunks"] >= len(pieces)


# ---------------------------------------------------------------------------
# cancellation: queued / mid-flight, slot + page accounting, leak property
# ---------------------------------------------------------------------------


def test_cancel_queued_and_midflight(qwen, qwen_params):
    srv = LLMServer(qwen, num_slots=1, capacity=128, params=qwen_params,
                    engine_cfg=EngineConfig(cache_mode="paged",
                                            decode_chunk=2))
    a = srv.submit("request a " * 3, SamplingParams(max_new_tokens=24))
    b = srv.submit("request b " * 3, SamplingParams(max_new_tokens=24))
    srv.step()                                    # admit a, decode one chunk
    assert a.status().value == "running" and b.status().value == "queued"
    assert srv.cancel(b) and b.status().value == "cancelled"
    partial = a.text
    assert srv.cancel(a) and a.status().value == "cancelled"
    assert a.result() == a.text and a.text.startswith(partial)
    assert a.request.output_tokens > 0            # partial output kept
    assert not srv.cancel(a)                      # idempotent: already done
    c = srv.submit("request c", SamplingParams(max_new_tokens=4))
    c.result()                                    # freed slot is reusable
    eng = srv.engine
    st = eng.stats()
    assert st["cancelled_requests"] == 2
    assert all(s.request is None for s in eng.slots)
    owned = eng.radix.check_invariants()
    free = set(eng.kvpool._free)
    assert not (owned & free)
    assert len(owned) + len(free) == eng.kvpool.num_pages - eng.kvpool.reserved


def test_cancel_snapshot_mode_accounting():
    """Mid-flight cancel on a stateful arch releases the pin and keeps the
    session's retained tail for a retried turn."""
    cfg = _cfg("recurrentgemma-9b")
    srv = LLMServer(cfg, num_slots=1, capacity=128,
                    engine_cfg=EngineConfig(cache_mode="paged",
                                            decode_chunk=2))
    sess = srv.open_session()
    sess.submit(SYS + TURNS[0], SamplingParams(max_new_tokens=8)).result()
    tail_before = srv.engine._sessions[sess.sid].tail_snap
    assert tail_before >= 0
    h = sess.submit(sess.text + TURNS[1], SamplingParams(max_new_tokens=24))
    srv.step()
    assert srv.cancel(h)
    # the retained tail survived the cancelled turn — retry reuses it
    assert srv.engine._sessions[sess.sid].tail_snap == tail_before
    h2 = sess.submit(sess.text + TURNS[1], SamplingParams(max_new_tokens=8))
    out = h2.result()
    assert out and h2.request.prefix_hit_tokens > 0
    sess.close()
    eng = srv.engine
    owned = eng.radix.check_invariants(snapshots=True)
    free = set(eng.snaps._free)
    assert not (owned & free)
    assert len(owned) + len(free) == eng.snaps.num_snaps


_CANCEL_SRV = None


def _cancel_server():
    global _CANCEL_SRV
    if _CANCEL_SRV is None:
        # tiny pool (eviction pressure) + spec (rejection pressure) + tiny
        # chunks (many cancel windows) — the PR-3 page-leak test's twin,
        # now under random cancel + session-tail pressure
        _CANCEL_SRV = LLMServer(
            _cfg("qwen2.5-3b"), num_slots=2, capacity=64,
            engine_cfg=EngineConfig(cache_mode="paged", page_size=8,
                                    num_pages=18, spec_len=4,
                                    decode_chunk=4))
    return _CANCEL_SRV


def _cancel_leak_check(srv):
    eng = srv.engine
    assert all(s.request is None for s in eng.slots)
    owned = eng.radix.check_invariants()
    free = set(eng.kvpool._free)
    tails = {s.tail_page for s in eng._sessions.values() if s.tail_page >= 0}
    assert not (owned & free) and not (owned & tails) and not (free & tails)
    # exactly-once ownership: free list, radix tree, or a session tail
    assert (len(owned) + len(free) + len(tails)
            == eng.kvpool.num_pages - eng.kvpool.reserved)


@given(st.lists(st.tuples(st.integers(0, 3), st.integers(0, 3),
                          st.integers(2, 12)),
                min_size=3, max_size=10))
@settings(max_examples=40, deadline=None)
def test_cancel_no_page_leak(ops):
    """Random submit / session-turn / step / cancel interleavings (shared
    prefixes, LRU eviction from the deliberately tiny pool, draft
    rejections, retained session tails): after every drain each page is
    owned exactly once — free list, radix tree, or a session tail — so
    cancellation mid-prefill/mid-decode/mid-verify never leaks or
    double-frees."""
    srv = _cancel_server()
    sess = srv.open_session()
    pool = ["err 429 err 429 err 429. " + t for t in
            ("", "tail one", "go go go go go", "a longer tail that repeats")]
    handles = []
    for kind, variant, budget in ops:
        if kind == 0:
            handles.append(srv.submit(pool[variant],
                                      SamplingParams(max_new_tokens=budget)))
        elif kind == 1:
            live = srv.engine._sessions[sess.sid].live
            if live is None or live.finished:
                prompt = (sess.text or pool[variant]) + f" turn {variant}"
                handles.append(sess.submit(
                    prompt, SamplingParams(max_new_tokens=budget)))
        elif kind == 2:
            for _ in range(variant + 1):
                srv.step()
        elif handles:
            srv.cancel(handles[-(1 + variant % len(handles))])
    srv.run_until_idle()
    sess.close()
    _cancel_leak_check(srv)


def test_cancel_leak_server_exercised():
    """Companion gate (and no-hypothesis fallback): the shared cancel server
    must actually cancel mid-flight, evict, and retain session tails — a
    run that never cancelled anything live would make the property above
    vacuous."""
    import random
    srv = _cancel_server()
    rng = random.Random(0)
    mid_cancels = 0
    for _ in range(6):
        hs = [srv.submit("err 429 err 429 err 429. tail " + str(rng.randrange(3)),
                         SamplingParams(max_new_tokens=rng.randint(4, 16)))
              for _ in range(rng.randint(2, 5))]
        srv.step()
        victim = rng.choice(hs)
        if victim.status().value == "running":
            mid_cancels += 1
        srv.cancel(victim)
        srv.run_until_idle()
        _cancel_leak_check(srv)
    assert mid_cancels > 0
    assert srv.stats()["cancelled_requests"] >= mid_cancels


# ---------------------------------------------------------------------------
# deadlines: TIMED_OUT within one chunk sync, resources freed, cancel races
# ---------------------------------------------------------------------------


def test_deadline_times_out_midflight(qwen, qwen_params):
    """A running request whose deadline elapses terminates TIMED_OUT at the
    next chunk sync with partial output kept and every page freed; a
    co-batched request is untouched."""
    from repro.serving.server import DeadlineExceeded
    srv = LLMServer(qwen, num_slots=2, capacity=128, params=qwen_params,
                    engine_cfg=EngineConfig(cache_mode="paged",
                                            decode_chunk=2))
    h = srv.submit("deadline bounded request",
                   SamplingParams(max_new_tokens=64, deadline_s=30.0))
    survivor = srv.submit("co-batched survivor",
                          SamplingParams(max_new_tokens=48))
    while h.status().value != "running":
        srv.step()
    srv.step()
    h.request._submit_t -= 100.0          # push the submit past the deadline
    srv.step()                            # ... the next chunk sync notices
    assert h.done and h.status().value == "timed_out"
    assert h.status().terminal
    assert isinstance(h.exception(), DeadlineExceeded)
    assert h.result() == h.request.output_text   # partial output kept
    assert survivor.result() and survivor.status().value == "completed"
    assert srv.stats()["timed_out"] == 1
    eng = srv.engine
    assert all(s.request is None for s in eng.slots)
    owned = eng.radix.check_invariants()
    free = set(eng.kvpool._free)
    assert not (owned & free)
    assert len(owned) + len(free) == eng.kvpool.num_pages - eng.kvpool.reserved


def test_deadline_default_and_queued_expiry(qwen, qwen_params):
    """The server-level default deadline applies to every request that does
    not override it; a request can time out while still queued."""
    srv = LLMServer(qwen, num_slots=1, capacity=128, params=qwen_params,
                    default_deadline_s=1e-6)
    a = srv.submit("will expire", SamplingParams(max_new_tokens=8))
    b = srv.submit("will finish",
                   SamplingParams(max_new_tokens=8, deadline_s=300.0))
    a.result(), b.result()
    assert a.status().value == "timed_out"        # server default applied
    assert b.status().value == "completed"        # per-request override wins
    assert a.request.output_tokens == 0           # expired before admission


@given(st.lists(st.tuples(st.integers(0, 2), st.integers(2, 12),
                          st.integers(0, 3)),
                min_size=2, max_size=8))
@settings(max_examples=25, deadline=None)
def test_deadline_cancel_race_no_leak(ops):
    """Deadline expiry racing explicit cancel() (and normal completion)
    on the shared tiny-pool cancel server: whichever terminal state wins,
    every handle lands in exactly one of them and the exactly-once page
    ownership invariant holds after the drain."""
    srv = _cancel_server()
    handles = []
    for kind, budget, steps in ops:
        dl = (None, 1e-6, 0.02)[kind]
        handles.append(srv.submit(
            "err 429 err 429 err 429. tail %d" % (budget % 3),
            SamplingParams(max_new_tokens=budget, deadline_s=dl)))
        for _ in range(steps):
            srv.step()
        if kind == 2:
            srv.cancel(handles[-(1 + steps % len(handles))])
    srv.run_until_idle()
    for h in handles:
        assert h.status().terminal
        assert h.status().value in ("completed", "cancelled", "timed_out")
    _cancel_leak_check(srv)


# ---------------------------------------------------------------------------
# per-request RNG: seed-reproducible regardless of batch composition
# ---------------------------------------------------------------------------


def test_seed_reproducible_across_num_slots(qwen, qwen_params):
    """Same SamplingParams.seed -> same stochastic output at num_slots 1 vs
    4, and with or without co-batched neighbours: each request draws from
    its own fold_in(PRNGKey(seed), t) chain, never from a batch-shared
    stream."""
    sp = SamplingParams(max_new_tokens=10, temperature=0.9, top_k=8, seed=123)
    outs = []
    for slots in (1, 4):
        srv = LLMServer(qwen, num_slots=slots, capacity=96,
                        params=qwen_params)
        h = srv.submit("sample with a pinned seed", sp)
        outs.append(h.result())
    assert outs[0] == outs[1]
    # co-batched with three other (differently seeded) requests: unchanged
    srv = LLMServer(qwen, num_slots=4, capacity=96, params=qwen_params)
    h = srv.submit("sample with a pinned seed", sp)
    others = [srv.submit("noise neighbour %d" % i,
                         SamplingParams(max_new_tokens=10, temperature=1.3,
                                        seed=i))
              for i in range(3)]
    srv.run_until_idle()
    assert h.result() == outs[0]
    assert len({o.result() for o in others}) == 3   # distinct seeds, streams


# ---------------------------------------------------------------------------
# stop sequences
# ---------------------------------------------------------------------------


def test_stop_sequence_split_across_chunk_boundary(qwen, qwen_params):
    """A multi-token stop string whose pieces land in DIFFERENT decode
    chunks is still caught (the host-side check sees the whole decoded
    text), and tokens after the stop are trimmed from the result."""
    srv = LLMServer(qwen, num_slots=1, capacity=96,
                    params=without_eos(qwen_params, qwen),
                    engine_cfg=EngineConfig(decode_chunk=4))
    free_h = srv.submit("tell me something", SamplingParams(max_new_tokens=16))
    free_text = free_h.result()
    g = free_h.request.output_ids
    assert len(g) == 16
    dec = srv.engine.tokenizer.decode
    # a stop spanning output tokens 5..7: token 1 comes from prefill and
    # chunks are 4 tokens, so tokens 5/6 land in chunk 1 and token 7 in
    # chunk 2 — the stop is complete only after the SECOND chunk's sync
    stop = dec(g[:7])[len(dec(g[:4])):]
    assert stop and stop in free_text
    h2 = srv.submit("tell me something",
                    SamplingParams(max_new_tokens=16, stop=(stop,)))
    out = h2.result()
    assert stop in out                            # the stop itself is kept
    n = h2.request.output_tokens
    assert n < 16                                 # tokens after it trimmed
    assert h2.request.output_ids == g[:n]         # trim, not divergence
    # minimality at token granularity: one token fewer loses the stop
    assert stop not in dec(g[:n - 1])
    # a stop that never appears changes nothing
    h3 = srv.submit("tell me something",
                    SamplingParams(max_new_tokens=16, stop=("\x00unseen",)))
    assert h3.result() == free_text


# ---------------------------------------------------------------------------
# priority classes + deprecation shim
# ---------------------------------------------------------------------------


def test_priority_classes_admit_first(qwen, qwen_params):
    srv = LLMServer(qwen, num_slots=1, capacity=96, params=qwen_params)
    low = srv.submit("background batch job", SamplingParams(max_new_tokens=4))
    high = srv.submit("interactive user turn",
                      SamplingParams(max_new_tokens=4, priority=5))
    low2 = srv.submit("another batch job", SamplingParams(max_new_tokens=4))
    srv.run_until_idle()
    assert high.request.admit_index < low.request.admit_index
    assert low.request.admit_index < low2.request.admit_index  # FIFO in class


def test_deprecated_submit_shim_still_serves(qwen, qwen_params):
    """The ONE test keeping the old kwargs path covered: ServingEngine
    .submit/.generate warn but still produce the LLMServer output."""
    eng = ServingEngine(qwen, num_slots=2, capacity=96, params=qwen_params)
    with pytest.warns(DeprecationWarning):
        req = eng.submit("legacy caller", max_new_tokens=6)
    eng.run_until_drained()
    srv = LLMServer(qwen, num_slots=2, capacity=96, params=qwen_params)
    assert srv.submit("legacy caller",
                      SamplingParams(max_new_tokens=6)).result() \
        == req.output_text
