"""The traffic generator: one plan for every seed, which draws only the
tokens; clips kept, and no agent turn past the cache."""
import collections
import random

import numpy as np
import pytest

import generator
import manifest

BENCH = manifest.benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
SECONDS = BENCH["run_seconds"]


def _plan(name):
    wl = manifest.workload(BENCH, name)
    return wl, generator.plan(wl["mix"], wl["cell"]["load"],
                              wl["cell"]["engine"], SECONDS)


def _sizes(p):
    return collections.Counter((t.role, t.suffix, t.output, t.wait_s)
                               for c in p.conversations for t in c.turns)


@pytest.mark.parametrize("name", CELLS)
def test_same_seed_same_plan(name):
    _, a = _plan(name)
    _, b = _plan(name)
    assert a == b


@pytest.mark.parametrize("name", CELLS)
def test_seeds_share_sizes_not_order(name):
    """Two seeds get the same plan, sizes and order alike, and different
    tokens."""
    from client import LoadClient
    wl, p = _plan(name)
    vocab = wl["spec"]["vocab_size"]
    a, b = (LoadClient(None, p, vocab=vocab, seed=s, sampling=None)
            for s in (1, 2 ** 31 + 3))
    turn = p.conversations[0].turns[0]
    pa, pb = a._prompt(0, turn, {}), b._prompt(0, turn, {})
    assert len(pa) == len(pb) and (pa != pb).any()
    for x, y in zip(a.prefixes, b.prefixes):
        assert len(x) == len(y) and (x != y).any()


@pytest.mark.parametrize("name", CELLS)
def test_clips_and_capacity(name):
    wl, p = _plan(name)
    mix, eng = wl["mix"], wl["cell"]["engine"]
    cap, top = eng["capacity"], max(eng["prefill_buckets"])
    turns = [t for c in p.conversations for t in c.turns]
    assert turns
    for c in p.conversations:
        ctx = collections.defaultdict(int)
        for t in c.turns:
            base = ctx[t.role] if t.role >= 0 and ctx[t.role] else (
                p.prefix_tokens[t.prefix] if t.prefix >= 0 else 0)
            prompt = base + t.suffix
            assert prompt + t.output + 1 <= cap
            assert prompt + top <= cap
            if t.role >= 0:
                ctx[t.role] = prompt + t.output
    if mix["kind"] == "agent":
        lo, hi = mix["output_tokens"]["min"], mix["output_tokens"]["max"]
        assert all(lo <= t.output <= hi for t in turns)
        tool = mix["roles"].index(mix["tool_output_to"])
        s = mix["suffix_tokens"]
        to = mix["tool_output_tokens"]
        for t in turns:
            extra = (to["min"], to["max"]) if t.role == tool else (0, 0)
            assert s["min"] + extra[0] <= t.suffix <= s["max"] + extra[1]
    elif mix["kind"] == "open":
        by = [(c["prompt_tokens"], c["output_tokens"])
              for c in mix["classes"]]
        for t in turns:
            assert any(pp["min"] <= t.suffix <= pp["max"]
                       and oo["min"] <= t.output <= oo["max"]
                       for pp, oo in by)
    else:
        pp, oo = mix["prompt_tokens"], mix["output_tokens"]
        assert all(pp["min"] <= t.suffix <= pp["max"]
                   and oo["min"] <= t.output <= oo["max"] for t in turns)


@pytest.mark.parametrize("name", CELLS)
def test_arrivals_fill_lead_in_and_window(name):
    wl, p = _plan(name)
    lead = wl["cell"]["load"].get("lead_in_s", 0.0)
    arr = [c.arrival_s for c in p.conversations]
    assert min(arr) >= -lead - 1e-9 and max(arr) < SECONDS


def test_stratified_is_a_shuffle_of_quantiles():
    spec = {"dist": "lognormal", "median": 512, "sigma": 0.8,
            "min": 128, "max": 2048}
    a = generator.stratified(spec, 101, random.Random(1))
    b = generator.stratified(spec, 101, random.Random(2))
    assert sorted(a) == sorted(b) and a != b
    assert sorted(a)[50] == 512
    assert min(a) >= 128 and max(a) <= 2048


def test_token_ids_avoid_specials():
    ids = generator.token_ids(np.random.default_rng(0), 10000, 300)
    assert ids.min() >= 0 and ids.max() < 297


def test_percentile_interpolates():
    assert generator.percentile(list(range(1, 101)), 95) == pytest.approx(
        95.05)
    assert generator.percentile([], 95) is None


@pytest.mark.parametrize("kind", ["agent", "open"])
def test_every_traffic_kind_keeps_its_sizes_across_seeds(kind):
    import _tiny
    wl = _tiny.workload(kind)
    args = (wl["mix"], wl["cell"]["load"], wl["cell"]["engine"], 10.0)
    a, b = generator.plan(*args), generator.plan(*args)
    assert _sizes(a) == _sizes(b) and a.conversations
    assert a == b


def test_closed_loop_chains_each_clients_conversations():
    import _tiny
    wl = _tiny.workload("agent", load="closed")
    load = wl["cell"]["load"]
    n = load["clients"]
    p = generator.plan(wl["mix"], load, wl["cell"]["engine"], 10.0)
    convs = p.conversations
    assert len(convs) == n * load["conversations_per_client"]
    firsts = [c for c in convs if c.after < 0]
    assert [c.arrival_s for c in firsts] == pytest.approx(
        [-load["lead_in_s"] + load["lead_in_s"] * i / n for i in range(n)])
    # every later conversation follows the one before it on its client
    assert all(c.after == j - n for j, c in enumerate(convs) if j >= n)


def test_bursts_keep_the_count_and_stay_in_on_periods():
    load = {"bursts": {"on_s": 5.0, "off_s": 10.0}}
    a = generator.open_arrivals(40, 62.0, load, random.Random(7))
    assert a == generator.open_arrivals(40, 62.0, load, random.Random(7))
    assert len(a) == 40 and min(a) == 0.0 and max(a) < 62.0
    assert all(t % 15.0 < 5.0 for t in a)
    steady = generator.open_arrivals(40, 62.0, {}, random.Random(7))
    assert max(steady) < 62.0 and any(t % 15.0 >= 5.0 for t in steady)


def test_a_kind_of_its_own_comes_from_its_file(tmp_path, monkeypatch):
    (tmp_path / "traffic").mkdir()
    (tmp_path / "traffic" / "pairs.py").write_text(
        "from generator import Turn\n"
        "def conversations(mix, load, seconds, engine, rng):\n"
        "    n = round(load['rate_per_s'] * seconds)\n"
        "    return [[Turn(-1, mix['prompt'], 4)] * 2] * n, []\n")
    monkeypatch.setattr(manifest, "HERE", tmp_path)
    p = generator.plan({"kind": "pairs", "prompt": 9}, {"rate_per_s": 1.0},
                       {"capacity": 64, "prefill_buckets": [16]}, 6.0)
    assert len(p.conversations) == 6
    assert {t.suffix for c in p.conversations for t in c.turns} == {9}
    with pytest.raises(ValueError, match="unknown traffic kind"):
        generator.shapes("nowhere")
