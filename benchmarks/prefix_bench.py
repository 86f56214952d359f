"""Prefix-sharing benchmark: radix prefix reuse vs the dense PR-1 cache.

Workload: the FAME multi-agent shape (PAPER.md §3.3) — N agents (Planner /
Actor / Evaluator) share one system prompt, and every turn's prompt is the
*whole conversation so far* plus a short new instruction, exactly the traffic
pattern whose re-sent prefix dominated input tokens in the paper. The same
request stream runs through two engines sharing one set of weights:

* **paged** — ``EngineConfig(cache_mode="paged")``: radix-matched prefixes
  are never re-prefilled. On full-attention archs the prefix's KV *pages*
  are reused outright; on stateful archs (``--arch recurrentgemma-9b`` /
  ``xlstm-350m`` / ``mixtral-8x22b``) the engine restores the nearest
  per-prefix recurrent-state *snapshot* and prefills only the suffix.
* **dense** — the PR-1 per-slot cache: every turn re-prefills its full
  prompt from scratch.

Reported: total prefill seconds (warm), prefill speedup, shared-prefix hit
rate (plus snapshot hit/capture counters on stateful archs), padding waste,
and an output-equality check (greedy decode must be identical between
modes):

    PYTHONPATH=src python benchmarks/prefix_bench.py [--smoke] [--arch A]

Acceptance floors (ISSUEs 2 and 4): paged prefill time <= 1/2 dense prefill
time on CPU with the multi-agent workload — for full-attention archs AND
for stateful archs via snapshots — identical greedy outputs, hit rate
reported in the JSON (CI runs ``--smoke`` for both as perf gates).
"""
from __future__ import annotations

import argparse
import json
import time

from _artifact import write_artifact


SYSTEM_PROMPT = (
    "System: You are one of several cooperating agents in a FaaS-hosted MCP "
    "workflow. Shared rules: keep tool calls minimal, cite evidence for "
    "every claim, prefer cached tool outputs when the arguments are "
    "identical, and hand off to the evaluator after each action. The "
    "conversation below is shared verbatim by every agent in this workflow "
    "session, so treat it as common ground. ")

AGENT_TURNS = [
    ("planner", "Plan: decompose the user goal into the next tool call."),
    ("actor", "Act: execute the planned tool call and record the output."),
    ("evaluator", "Evaluate: check the output against the goal; pass or retry."),
]


def make_workload(rounds: int):
    """Prompt stream: a growing conversation walked by 3 agents per round —
    every prompt is the long shared system prompt + the full history so far
    + a short per-turn instruction (the paper's re-sent-prefix shape). The
    bench's ``no_truncation`` check catches capacity/rounds mismatches (a
    truncated prompt would silently shrink the shareable prefix)."""
    history = ""
    prompts = []
    for r in range(rounds):
        for agent, turn in AGENT_TURNS:
            prompts.append(f"{SYSTEM_PROMPT}{history}[{agent}] {turn}")
        history += f"(round {r}: plan->act->eval ok) "
    return prompts


def run_engine(engine, prompts, max_new):
    """Two cold passes, then a warm measured pass. Two because the paged
    engine's steady state differs from its first pass: once the radix tree
    holds the conversation, suffix chunks take different (smaller) bucket
    shapes, and those compiles must not land in the measured pass."""
    for _ in range(2):
        for p in prompts:
            engine.submit(p, max_new_tokens=max_new)
        engine.run_until_drained()
    cold = engine.stats()
    t0 = time.perf_counter()
    reqs = [engine.submit(p, max_new_tokens=max_new) for p in prompts]
    engine.run_until_drained()
    wall = time.perf_counter() - t0
    warm = engine.stats()
    # engine counters are lifetime totals; report the measured pass only
    # (the warm-up passes' compulsory misses and padding would otherwise
    # skew the steady-state numbers README tells users to tune from)
    d = lambda k: warm.get(k, 0) - cold.get(k, 0)
    prefill_s = sum(r.prefill_s for r in reqs)
    computed = max(d("prefill_pad_tokens") + d("prompt_tokens")
                   - d("prefix_hit_tokens"), 1)
    return {
        "warm_wall_s": round(wall, 4),
        "prefill_s": round(prefill_s, 4),
        "decode_wall_s": round(max(wall - prefill_s, 1e-9), 4),
        "prefill_compiles": cold["prefill_compiles"],
        "extend_compiles": cold["extend_compiles"],
        # compiles landing in the measured pass would silently absorb compile
        # time into prefill_s — surface them (0 in a healthy run)
        "measured_pass_compiles": (d("prefill_compiles")
                                   + d("extend_compiles")),
        "prefill_pad_tokens": d("prefill_pad_tokens"),
        "prefill_pad_frac": round(d("prefill_pad_tokens") / computed, 4),
        "prompt_tokens": d("prompt_tokens"),
        "truncated_tokens": d("truncated_tokens"),
        "prefix_hit_tokens": d("prefix_hit_tokens"),
        "prefix_hit_rate": round(d("prefix_hit_tokens")
                                 / max(d("prompt_tokens"), 1), 4),
        "pages_peak_in_use": warm.get("pages_peak_in_use", 0),
        "radix_evicted_pages": warm.get("radix_evicted_pages", 0),
        # snapshot mode (stateful archs): restored vs from-scratch admissions
        "snapshot_hits": d("snapshot_hits"),
        "snapshot_misses": d("snapshot_misses"),
        "snapshot_captures": d("snapshot_captures"),
        "snapshots_peak_in_use": warm.get("snapshots_peak_in_use", 0),
        "snapshot_evictions": warm.get("snapshot_evictions", 0),
    }, [r.output_text for r in reqs]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--capacity", type=int, default=512)
    ap.add_argument("--rounds", type=int, default=4,
                    help="conversation rounds (3 agent turns each)")
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--chunk", type=int, default=16)
    ap.add_argument("--out", default="results/prefix_bench.json")
    ap.add_argument("--smoke", action="store_true",
                    help="small fast run for CI perf gating")
    args = ap.parse_args()
    if args.smoke:
        args.rounds, args.capacity = 3, 448

    from repro.configs.registry import ARCHS
    from repro.serving.engine import EngineConfig, ServingEngine

    # a notch bigger than the test-suite smoke dims: prefill must be
    # compute-bound (not jit-dispatch-bound) for the A/B to measure the
    # algorithmic win rather than per-call overhead
    cfg = ARCHS[args.arch].reduced(dtype="float32", param_dtype="float32",
                                   vocab_size=512, d_model=256, num_heads=8,
                                   head_dim=32, d_ff=512, num_layers=4)
    prompts = make_workload(args.rounds)

    paged = ServingEngine(
        cfg, num_slots=args.slots, capacity=args.capacity,
        engine_cfg=EngineConfig(decode_chunk=args.chunk, cache_mode="paged",
                                page_size=args.page_size))
    dense = ServingEngine(
        cfg, num_slots=args.slots, capacity=args.capacity, params=paged.params,
        engine_cfg=EngineConfig(decode_chunk=args.chunk))

    paged_r, paged_out = run_engine(paged, prompts, args.max_new)
    dense_r, dense_out = run_engine(dense, prompts, args.max_new)
    speedup = dense_r["prefill_s"] / max(paged_r["prefill_s"], 1e-9)

    result = {
        "bench": "prefix_sharing",
        "arch": args.arch,
        "num_slots": args.slots,
        "capacity": paged.capacity,
        "page_size": args.page_size,
        "requests": len(prompts),
        "max_new_tokens": args.max_new,
        "paged": paged_r,
        "dense_baseline": dense_r,
        "prefill_speedup_vs_dense": round(speedup, 2),
        "checks": {
            "prefill_speedup_ge_2x": speedup >= 2.0,
            "outputs_bit_identical": paged_out == dense_out,
            "prefix_hit_rate_reported": paged_r["prefix_hit_rate"] > 0.0,
            "no_truncation": paged_r["truncated_tokens"] == 0,
        },
    }
    write_artifact(args.out, result)
    print(json.dumps(result, indent=2))
    if not all(result["checks"].values()):
        raise SystemExit("prefix_bench: perf checks FAILED")
    print(f"prefix_bench: OK ({speedup:.1f}x prefill vs dense, "
          f"{paged_r['prefix_hit_rate']:.0%} prefix hit rate, "
          f"outputs identical) -> {args.out}")


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
