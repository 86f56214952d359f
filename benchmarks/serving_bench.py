"""Serving fast-path benchmark: bucketed prefill + chunked decode A/B.

Runs the same mixed-length request stream through three engines sharing one
set of weights:

* **fast**   — bucketed prefill, chunked on-device decode (the fast path)
* **chunk1** — ablation: bucketed prefill but one engine step per token
* **seed**   — a frozen copy of the pre-fast-path engine (one jit compile per
  distinct prompt length, host-side tree-map cache splice on admission,
  host-side sampling, per-slot blocking ``int()`` pulls every token)

and reports decode throughput, prefill compile counts, host syncs per token,
and request-latency percentiles as JSON (the repo's BENCH trajectory):

    PYTHONPATH=src python benchmarks/serving_bench.py [--smoke] [--arch A]

Throughput is wall-clock based (drain wall minus prefill time, on a warmed
engine): the seed engine's own ``decode_s`` was measured before its blocking
host pulls and badly under-counts, so per-engine timers are not comparable.

Acceptance floor (ISSUE 1): fast decode tokens/sec >= 3x the seed engine on
CPU with num_slots=4 and mixed prompt lengths; prefill compiles <= number of
buckets; <= 1 host sync per decode chunk.
"""
from __future__ import annotations

import argparse
import json
import time

from _artifact import write_artifact

import jax
import jax.numpy as jnp


# ---------------------------------------------------------------------------
# The seed engine, frozen for A/B (do not "fix" it — it is the baseline).
# ---------------------------------------------------------------------------


class SeedEngine:
    """Pre-fast-path serving loop: per-length prefill compiles, host-side
    cache splice, host-side sampling, one blocking pull per slot per token."""

    def __init__(self, cfg, *, num_slots=4, capacity=512, params=None, seed=0):
        from repro.models import Model
        from repro.serving.tokenizer import ByteTokenizer
        self.cfg = cfg
        self.model = Model(cfg)
        self.tokenizer = ByteTokenizer(cfg.vocab_size)
        self.num_slots = num_slots
        self.capacity = capacity
        key = jax.random.PRNGKey(seed)
        self.params = params if params is not None else self.model.init(key)
        self.cache = self.model.init_cache(num_slots, capacity)
        self.slots = [None] * num_slots          # (req, generated, remaining)
        self.cache_lens = jnp.zeros((num_slots,), jnp.int32)
        self._rng = jax.random.PRNGKey(seed + 1)
        self._pending = []
        self._prefill_shapes = set()
        self._decode_syncs = 0
        self._decode_tokens = 0
        self._jit_decode = jax.jit(self._decode_step_fn)
        self._jit_prefill = jax.jit(self._prefill_fn)

    def _prefill_fn(self, params, tokens, positions):
        cache1 = self.model.init_cache(1, self.capacity)
        logits, cache1 = self.model.prefill(
            params, {"tokens": tokens, "positions": positions}, cache1)
        return logits[:, -1], cache1

    def _decode_step_fn(self, params, cache, tokens, positions, cache_len):
        logits, cache = self.model.decode_step(
            params, {"tokens": tokens, "positions": positions}, cache, cache_len)
        return logits[:, 0], cache

    def submit(self, prompt, *, max_new_tokens=64):
        req = {"prompt": prompt, "max_new": max_new_tokens, "prefill_s": 0.0,
               "out": [], "t0": time.perf_counter(), "latency_s": 0.0}
        self._pending.append(req)
        return req

    def _admit(self):
        from repro.serving.sampler import sample
        for si in range(self.num_slots):
            if self.slots[si] is not None or not self._pending:
                continue
            req = self._pending.pop(0)
            t0 = time.perf_counter()
            ids = self.tokenizer.encode(req["prompt"])[
                -(self.capacity - req["max_new"] - 1):]
            tokens = jnp.asarray([ids], jnp.int32)
            positions = jnp.arange(len(ids), dtype=jnp.int32)[None, :]
            self._prefill_shapes.add(len(ids))
            last_logits, cache1 = self._jit_prefill(self.params, tokens, positions)

            def _scan_leaf(full, one):
                return jax.lax.dynamic_update_slice(
                    full, one.astype(full.dtype), (0, si) + (0,) * (full.ndim - 2))

            def _tail_leaf(full, one):
                return jax.lax.dynamic_update_slice(
                    full, one.astype(full.dtype), (si,) + (0,) * (full.ndim - 1))

            self.cache = {
                k: jax.tree.map(_scan_leaf if k == "scan" else _tail_leaf,
                                self.cache[k], cache1[k])
                for k in self.cache}
            self.cache_lens = self.cache_lens.at[si].set(len(ids))
            self._rng, k = jax.random.split(self._rng)
            first = sample(last_logits, k, vocab_limit=self.cfg.vocab_size)
            self.slots[si] = (req, [int(first[0])], req["max_new"] - 1,
                              len(ids))
            req["prefill_s"] += time.perf_counter() - t0

    def step(self):
        from repro.serving.sampler import sample
        self._admit()
        active = [i for i, s in enumerate(self.slots) if s is not None]
        if not active:
            return False
        last = [self.slots[i][1][-1] if self.slots[i] else 0
                for i in range(self.num_slots)]
        tokens = jnp.asarray(last, jnp.int32)[:, None]
        positions = self.cache_lens[:, None]
        logits, self.cache = self._jit_decode(self.params, self.cache, tokens,
                                              positions, self.cache_lens)
        self._rng, k = jax.random.split(self._rng)
        nxt = sample(logits, k, vocab_limit=self.cfg.vocab_size)
        self.cache_lens = self.cache_lens + jnp.asarray(
            [1 if s else 0 for s in self.slots], jnp.int32)
        for i in active:
            req, gen, rem, clen = self.slots[i]
            gen.append(int(nxt[i]))                  # blocking pull per slot
            self._decode_syncs += 1
            self._decode_tokens += 1
            rem -= 1
            clen += 1
            if (rem <= 0 or gen[-1] == self.tokenizer.eos_id
                    or clen >= self.capacity - 1):
                req["out"] = gen
                req["latency_s"] = time.perf_counter() - req["t0"]
                self.slots[i] = None
                self.cache_lens = self.cache_lens.at[i].set(0)
            else:
                self.slots[i] = (req, gen, rem, clen)
        return True

    def run_until_drained(self):
        while self.step() or self._pending:
            pass

    def stats(self):
        return {"prefill_compiles": len(self._prefill_shapes),
                "prefill_buckets": [],
                "decode_chunk": 1,
                "decode_chunks": self._decode_syncs,
                "decode_tokens": self._decode_tokens,
                "host_syncs": self._decode_syncs,
                "host_syncs_per_token": (self._decode_syncs
                                         / max(self._decode_tokens, 1))}


# ---------------------------------------------------------------------------
# Harness
# ---------------------------------------------------------------------------


def _percentile(xs, p):
    xs = sorted(xs)
    if not xs:
        return 0.0
    i = min(len(xs) - 1, int(round(p / 100 * (len(xs) - 1))))
    return xs[i]


def make_prompts(n: int):
    """Mixed-length prompts: short / medium / long, interleaved."""
    base = [
        "ping",
        "summarize the introduction of the paper on FaaS-hosted agents",
        ("a much longer request: characterize the network and systems "
         "performance of MCP-enabled LLM agent workflows end to end, "
         "including tool-call fan-out, memory injection, and the serving "
         "engine's prefill and decode phases under continuous batching"),
    ]
    return [f"[{i}] {base[i % len(base)]}" for i in range(n)]


def run_engine(engine, prompts, max_new_tokens, *, is_seed=False):
    """Two passes: cold (counts compiles) then warm (throughput/latency)."""
    submit = (lambda p: engine.submit(p, max_new_tokens=max_new_tokens))
    reqs = [submit(p) for p in prompts]
    engine.run_until_drained()                     # cold pass: compiles
    cold = engine.stats()
    t0 = time.perf_counter()
    reqs = [submit(p) for p in prompts]
    engine.run_until_drained()
    wall = time.perf_counter() - t0
    if is_seed:
        prefill_s = sum(r["prefill_s"] for r in reqs)
        toks = sum(len(r["out"]) - 1 for r in reqs)
        lats = [r["latency_s"] for r in reqs]
    else:
        prefill_s = sum(r.prefill_s for r in reqs)
        toks = sum(r.output_tokens - 1 for r in reqs)
        lats = [r.latency_s for r in reqs]
    decode_wall = max(wall - prefill_s, 1e-9)
    warm = engine.stats()
    return {
        "warm_wall_s": round(wall, 4),
        "decode_wall_s": round(decode_wall, 4),
        "decode_tokens": toks,
        "decode_tok_s": round(toks / decode_wall, 2),
        "prefill_compiles": cold["prefill_compiles"],
        "prefill_buckets": cold["prefill_buckets"],
        "decode_chunk": warm["decode_chunk"],
        "decode_chunks": warm["decode_chunks"],
        "host_syncs": warm["host_syncs"],
        "host_syncs_per_token": round(warm["host_syncs_per_token"], 4),
        "p50_latency_s": round(_percentile(lats, 50), 4),
        "p95_latency_s": round(_percentile(lats, 95), 4),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--capacity", type=int, default=192)
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--chunk", type=int, default=16)
    ap.add_argument("--block-w", type=int, default=256)
    ap.add_argument("--out", default="results/serving_bench.json")
    ap.add_argument("--smoke", action="store_true",
                    help="small fast run for CI perf gating")
    args = ap.parse_args()
    if args.smoke:
        args.requests, args.max_new = 6, 16

    from repro.configs.registry import ARCHS
    from repro.serving.engine import EngineConfig, ServingEngine

    cfg = ARCHS[args.arch].reduced(dtype="float32", param_dtype="float32",
                                   vocab_size=512)
    prompts = make_prompts(args.requests)

    fast = ServingEngine(
        cfg, num_slots=args.slots, capacity=args.capacity,
        engine_cfg=EngineConfig(decode_chunk=args.chunk, block_w=args.block_w))
    chunk1 = ServingEngine(
        cfg, num_slots=args.slots, capacity=args.capacity, params=fast.params,
        engine_cfg=EngineConfig(decode_chunk=1, block_w=args.block_w))
    seed = SeedEngine(cfg, num_slots=args.slots, capacity=fast.capacity,
                      params=fast.params)

    fast_r = run_engine(fast, prompts, args.max_new)
    chunk1_r = run_engine(chunk1, prompts, args.max_new)
    seed_r = run_engine(seed, prompts, args.max_new, is_seed=True)
    speedup = fast_r["decode_tok_s"] / max(seed_r["decode_tok_s"], 1e-9)

    result = {
        "bench": "serving_fast_path",
        "arch": args.arch,
        "num_slots": args.slots,
        "capacity": fast.capacity,
        "requests": args.requests,
        "max_new_tokens": args.max_new,
        "fast": fast_r,
        "chunk1_ablation": chunk1_r,
        "seed_baseline": seed_r,
        "decode_speedup_vs_seed": round(speedup, 2),
        "p50_speedup_vs_seed": round(
            seed_r["p50_latency_s"] / max(fast_r["p50_latency_s"], 1e-9), 2),
        "checks": {
            "decode_speedup_ge_3x": speedup >= 3.0,
            "prefill_compiles_le_buckets":
                fast_r["prefill_compiles"] <= len(fast_r["prefill_buckets"]),
            "le_one_sync_per_chunk":
                fast_r["host_syncs"] <= fast_r["decode_chunks"],
        },
    }
    write_artifact(args.out, result)
    print(json.dumps(result, indent=2))
    if not all(result["checks"].values()):
        raise SystemExit("serving_bench: perf checks FAILED")
    print(f"serving_bench: OK ({speedup:.1f}x decode throughput vs seed, "
          f"{fast_r['prefill_compiles']} prefill compiles, "
          f"{fast_r['host_syncs_per_token']:.3f} syncs/token) -> {args.out}")


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
