"""Paged KV pool + radix prefix sharing: allocator/trie invariants, the
paged decode-attention kernel vs the dense reference, and paged-vs-dense
engine equivalence (greedy outputs must be identical)."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs.registry import ARCHS
from repro.kernels.paged_decode_attention import paged_decode_attention
from repro.models import Model
from repro.models import attention as attn
from repro.serving.engine import EngineConfig, SamplingParams, ServingEngine
from repro.serving.kvpool import PagePool, block_table_array, supports_paged
from repro.serving.radix import RadixTree

from tests._hypothesis_compat import given, settings, st
from tests._no_eos import without_eos

PAGED_ARCHS = ["qwen2.5-3b", "chatglm3-6b", "granite-3-2b"]


def _cfg(arch):
    return ARCHS[arch].reduced(dtype="float32", param_dtype="float32",
                               vocab_size=512)


# ---------------------------------------------------------------------------
# page allocator
# ---------------------------------------------------------------------------


def test_page_pool_alloc_free_roundtrip():
    pool = PagePool(10)                       # page 0 reserved (trash)
    a = pool.alloc(4)
    b = pool.alloc(5)
    assert pool.num_free == 0 and pool.alloc(1) is None
    assert 0 not in a + b and len(set(a + b)) == 9
    pool.free(b)
    assert pool.num_free == 5
    with pytest.raises(ValueError):
        pool.free(b[:1])                      # double free
    with pytest.raises(ValueError):
        pool.free([0])                        # reserved page
    assert pool.alloc(6) is None              # all-or-nothing
    assert pool.num_free == 5


def test_block_table_padding_points_at_trash():
    bt = block_table_array([[3, 1], [], [2, 5, 7]], 4)
    assert bt.shape == (3, 4) and bt.dtype == jnp.int32
    assert bt[0].tolist() == [3, 1, 0, 0]
    assert bt[1].tolist() == [0, 0, 0, 0]
    assert bt[2].tolist() == [2, 5, 7, 0]


def test_supports_paged_gating():
    assert supports_paged(_cfg("qwen2.5-3b"))[0]
    assert supports_paged(_cfg("dbrx-132b"))[0]
    for arch in ("recurrentgemma-9b", "xlstm-350m", "mixtral-8x22b"):
        ok, why = supports_paged(_cfg(arch))
        assert not ok and why
        # ... but cache_mode="paged" still works: stateful archs resolve to
        # per-prefix recurrent-state snapshot sharing (tests/test_snapshots)
        eng = ServingEngine(_cfg(arch), num_slots=1, capacity=64,
                            engine_cfg=EngineConfig(cache_mode="paged"))
        assert eng.snapshots and not eng.paged
    full = ServingEngine(_cfg("qwen2.5-3b"), num_slots=1, capacity=64,
                         engine_cfg=EngineConfig(cache_mode="paged"))
    assert full.paged and not full.snapshots


# ---------------------------------------------------------------------------
# radix tree: directed cases + property test
# ---------------------------------------------------------------------------


def test_radix_match_insert_evict_basic():
    t = RadixTree(4)
    toks = list(range(11))                    # 2 complete blocks + remainder
    pages, node = t.match(toks)
    assert pages == [] and node is t.root
    assert t.insert(toks, [5, 6]) == []
    t.release(node)
    pages, node = t.match(toks)
    assert pages == [5, 6]
    # diverging suffix shares only the first block
    pages2, node2 = t.match(list(range(4)) + [99, 98, 97, 96])
    assert pages2 == [5]
    # pinned nodes (and their ancestors) survive eviction
    assert t.evict(10) == []
    t.release(node)
    assert t.evict(10) == [6]                 # leaf first; [5] still pinned via node2
    t.release(node2)
    assert t.evict(10) == [5]
    assert t.num_nodes == 0


def test_radix_insert_collision_returns_duplicates():
    t = RadixTree(2)
    assert t.insert([1, 2, 3, 4], [7, 8]) == []
    # identical blocks raced through prefill with different pages
    assert t.insert([1, 2, 3, 4, 5, 6], [17, 18, 9]) == [17, 18]
    pages, node = t.match([1, 2, 3, 4, 5, 6, 7])
    assert pages == [7, 8, 9]
    t.release(node)
    t.check_invariants()


@given(st.lists(st.tuples(st.integers(0, 3),
                          st.lists(st.integers(0, 3), min_size=0, max_size=12)),
                max_size=60))
@settings(max_examples=60, deadline=None)
def test_radix_property_invariants(ops):
    """Random interleavings of match/insert/release/evict keep: refcounts
    >= 0, every page owned exactly once (tree vs allocator), matches are
    true prefixes of prior inserts."""
    ps = 2
    t = RadixTree(ps)
    pool = PagePool(64)
    pinned = []                               # (node, tokens-match-len)
    inserted = {}                             # tuple(tokens blocks) -> page
    for kind, toks in ops:
        toks = list(toks)
        if kind == 0:                         # match + pin
            pages, node = t.match(toks)
            assert len(pages) <= len(toks) // ps
            # every matched page was inserted for exactly this block path
            for i, pg in enumerate(pages):
                key = tuple(toks[:(i + 1) * ps])
                assert inserted.get(key) == pg, (key, pg)
            pinned.append(node)
        elif kind == 1:                       # insert (simulate a prefill)
            n = len(toks) // ps
            pages = pool.alloc(n)
            if pages is None:
                continue
            rejected = t.insert(toks, pages)
            pool.free(rejected)
            kept = [p for p in pages if p not in rejected]
            for i in range(n):
                key = tuple(toks[:(i + 1) * ps])
                if pages[i] in kept:
                    inserted.setdefault(key, pages[i])
        elif kind == 2 and pinned:            # release one pin
            t.release(pinned.pop())
        else:                                 # evict
            freed = t.evict(len(toks) + 1)
            pool.free(freed)
            for key in [k for k, v in inserted.items() if v in set(freed)]:
                del inserted[key]
        owned = t.check_invariants()
        # exactly-once ownership: tree pages and free pages are disjoint and
        # account for every non-reserved page
        free = set(pool._free)
        assert not (owned & free)
        assert len(owned) + len(free) == pool.num_pages - pool.reserved
    for node in pinned:
        t.release(node)
    # with all pins dropped, everything is evictable
    pool.free(t.evict(10 ** 9))
    assert t.num_nodes == 0
    assert pool.num_free == pool.num_pages - pool.reserved


# ---------------------------------------------------------------------------
# paged decode-attention kernel vs dense reference (interpret mode)
# ---------------------------------------------------------------------------


# (block tables, cache_len) over a pool of 11 pages of 8, tables 4 wide:
# a live row reads pages 0 .. cache_len // 8 of its table
KERNEL_CASES = {
    "mixed": ([[3, 1, 7, 10], [2, 5, 0, 0], [9, 8, 6, 4]], [25, 10, 31]),
    # the new token opens a page (k * ps) / fills a page's last slot
    "page_boundary": ([[3, 1, 7, 10], [2, 5, 0, 0], [9, 8, 6, 4]],
                      [16, 15, 8]),
    "full_table": ([[3, 1, 7, 10], [2, 5, 4, 6], [9, 8, 6, 4]],
                   [31, 31, 31]),
    "empty_row": ([[3, 1, 7, 10], [0, 0, 0, 0], [9, 8, 6, 4]], [25, 0, 23]),
}


def _kernel_inputs(B=3, P=11, ps=8, K=2, G=2, hd=16):
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(0), 3)
    kpool = jax.random.normal(k1, (P, ps, K, hd), jnp.float32)
    vpool = jax.random.normal(k2, (P, ps, K, hd), jnp.float32)
    q = jax.random.normal(k3, (B, 1, K * G, hd), jnp.float32)
    return q, kpool, vpool, G


@pytest.mark.parametrize("case", sorted(KERNEL_CASES))
def test_paged_kernel_matches_dense_reference(case):
    q, kpool, vpool, G = _kernel_inputs()
    tables, lens = KERNEL_CASES[case]
    bt = jnp.asarray(tables, jnp.int32)
    clen = jnp.asarray(lens, jnp.int32)
    out = paged_decode_attention(q, kpool, vpool, bt, clen, q_per_kv=G)
    ref = attn.decode_attention(q, attn.paged_view(kpool, bt),
                                attn.paged_view(vpool, bt), clen, q_per_kv=G)
    assert out.shape == ref.shape
    assert float(jnp.max(jnp.abs(out - ref))) < 1e-5


def test_paged_kernel_never_reads_dead_pages():
    """Table entries past each row's last live page point at NaN pages:
    the output is finite and bit-identical to pointing them at the trash
    page, so nothing of a dead page reaches the result (not even the
    ``0 * NaN`` of a fully masked page)."""
    q, kpool, vpool, G = _kernel_inputs(P=12)
    clen = jnp.asarray([9, 0, 20], jnp.int32)          # 2, 1 and 3 live pages
    trash = jnp.asarray([[3, 1, 0, 0], [2, 0, 0, 0], [9, 8, 6, 0]], jnp.int32)
    dead = jnp.asarray([[3, 1, 4, 5], [2, 7, 10, 11], [9, 8, 6, 4]],
                       jnp.int32)
    nan_pages = jnp.asarray([4, 5, 7, 10, 11])
    kpool = kpool.at[nan_pages].set(jnp.nan)
    vpool = vpool.at[nan_pages].set(jnp.nan)
    want = paged_decode_attention(q, kpool, vpool, trash, clen, q_per_kv=G)
    got = paged_decode_attention(q, kpool, vpool, dead, clen, q_per_kv=G)
    assert bool(jnp.all(jnp.isfinite(got)))
    assert bool(jnp.all(got == want))


def test_paged_cache_update_routes_through_block_table():
    P, ps, K, hd = 6, 4, 1, 2
    kpool = jnp.zeros((P, ps, K, hd))
    vpool = jnp.zeros((P, ps, K, hd))
    bt = jnp.asarray([[3, 1], [2, 5]], jnp.int32)
    knew = jnp.ones((2, 1, K, hd))
    clen = jnp.asarray([5, 2], jnp.int32)     # -> page 1 off 1, page 2 off 2
    kp, vp = attn.paged_cache_update(kpool, vpool, knew, 2 * knew, bt, clen, ps)
    assert float(kp[1, 1, 0, 0]) == 1.0 and float(vp[2, 2, 0, 0]) == 2.0
    assert float(jnp.sum(kp)) == 2 * K * hd   # one write per batch row


# ---------------------------------------------------------------------------
# engine equivalence: paged == dense greedy outputs, across archs
# ---------------------------------------------------------------------------

SYS = ("You are one of several cooperating agents sharing this exact system "
       "prompt and the same conversation history prefix. ")
TURNS = ["Plan the next step of the task.",
         "Act: call the search tool now.",
         "Evaluate the tool output please.",
         "Plan again with the new facts."]


@pytest.mark.parametrize("arch", PAGED_ARCHS)
def test_paged_equals_dense_greedy(arch):
    cfg = _cfg(arch)
    dense = ServingEngine(cfg, num_slots=3, capacity=128)
    paged = ServingEngine(cfg, num_slots=3, capacity=128, params=dense.params,
                          engine_cfg=EngineConfig(cache_mode="paged",
                                                  page_size=16))
    prompts = [SYS + t for t in TURNS]
    d = [dense.generate(p, max_new_tokens=8) for p in prompts]
    p = [paged.generate(p_, max_new_tokens=8) for p_ in prompts]
    assert d == p
    s = paged.stats()
    assert s["prefix_hit_tokens"] > 0         # later turns reused the prefix
    assert s["prefix_hit_rate"] > 0.2


def test_paged_mixed_batch_and_slot_reuse():
    """More requests than slots, interleaved shared/unshared prompts: FIFO
    admission, page recycling, and identical outputs vs dense."""
    cfg = _cfg("qwen2.5-3b")
    dense = ServingEngine(cfg, num_slots=2, capacity=96)
    paged = ServingEngine(cfg, num_slots=2, capacity=96, params=dense.params,
                          engine_cfg=EngineConfig(cache_mode="paged",
                                                  page_size=16))
    prompts = ([SYS + t for t in TURNS[:3]]
               + ["completely unrelated prompt about log analytics",
                  SYS + "Plan the next step of the task."])  # exact repeat
    for eng in (dense, paged):
        reqs = [eng.submit(p, max_new_tokens=6) for p in prompts]
        eng.run_until_drained()
        assert all(r.output_tokens == 6 for r in reqs)
    d = [dense.generate(p, max_new_tokens=6) for p in prompts]
    p = [paged.generate(p_, max_new_tokens=6) for p_ in prompts]
    assert d == p
    # the exact repeat matches everything but the final token's page
    last = paged.stats()
    assert last["prefix_hit_rate"] > 0
    # all pages accounted for after drain: free + retained-in-tree = usable
    assert (paged.kvpool.num_free + len(paged.radix.cached_pages)
            == paged.kvpool.num_pages - paged.kvpool.reserved)


def test_paged_pool_exhaustion_evicts_and_recovers():
    cfg = _cfg("qwen2.5-3b")
    params = without_eos(Model(cfg).init(jax.random.PRNGKey(0)), cfg)
    eng = ServingEngine(cfg, num_slots=2, capacity=64, params=params,
                        engine_cfg=EngineConfig(cache_mode="paged",
                                                page_size=16, num_pages=9))
    reqs = [eng.submit(f"request number {i} with a shared tail of text",
                       max_new_tokens=8) for i in range(6)]
    eng.run_until_drained()
    assert all(r.output_tokens == 8 for r in reqs)
    assert eng.radix.evicted_pages > 0        # pressure forced LRU eviction
    eng.radix.check_invariants()
    # a request that can never fit raises instead of spinning
    tiny = ServingEngine(cfg, num_slots=1, capacity=64, params=eng.params,
                         engine_cfg=EngineConfig(cache_mode="paged",
                                                 page_size=16, num_pages=3))
    with pytest.raises(RuntimeError):
        tiny.generate("a prompt that needs more pages than the pool holds",
                      max_new_tokens=8)


def test_paged_sampling_determinism():
    """Stochastic decode: same seed + params -> same text in paged mode."""
    cfg = _cfg("qwen2.5-3b")
    e1 = ServingEngine(cfg, num_slots=2, capacity=96, seed=7,
                       engine_cfg=EngineConfig(cache_mode="paged"))
    e2 = ServingEngine(cfg, num_slots=2, capacity=96, params=e1.params, seed=7,
                       engine_cfg=EngineConfig(cache_mode="paged"))
    a = e1.generate("sample me", max_new_tokens=8, temperature=1.1, top_k=12)
    b = e2.generate("sample me", max_new_tokens=8, temperature=1.1, top_k=12)
    assert a == b


def test_decode_page_counters_count_grid_and_live_pages():
    """Three slots, tables 64 / 8 = 8 pages wide, chunks of 4 steps. A
    (prompt 16 tokens, 10 out) decodes at 16..24, B (23, 6 out) at 23..27
    then sits out chunk 2 at 28; the third slot is empty (cache_len 0,
    one page). Live pages per step: cache_len // 8 + 1."""
    cfg = _cfg("qwen2.5-3b")
    params = without_eos(Model(cfg).init(jax.random.PRNGKey(0)), cfg)
    eng = ServingEngine(cfg, num_slots=3, capacity=64, params=params,
                        engine_cfg=EngineConfig(cache_mode="paged",
                                                page_size=8, decode_chunk=4))
    assert eng.stats()["decode_pages_grid"] == 0
    a = eng.enqueue("1" * 15, SamplingParams(max_new_tokens=10))  # BOS + 15
    b = eng.enqueue("2" * 22, SamplingParams(max_new_tokens=6))
    eng.run_until_drained()
    assert (a.output_tokens, b.output_tokens) == (10, 6)
    s = eng.stats()
    assert s["decode_chunks"] == 3
    # chunk 1: A 16-19 -> 3 x 4; B 23, 24-26 -> 3 + 4 x 3; empty 4 x 1
    # chunk 2: A 20-23 -> 3 x 4; B 27, done at 28 -> 4 x 4; empty 4 x 1
    # chunk 3 (one step, B's slot freed): A 24 -> 4; two empty slots 1 + 1
    assert s["decode_pages_live"] == (12 + 15 + 4) + (12 + 16 + 4) + 6
    assert s["decode_pages_grid"] == (4 + 4 + 1) * 3 * 8
    dense = ServingEngine(cfg, num_slots=3, capacity=64, params=params)
    assert "decode_pages_grid" not in dense.stats()
