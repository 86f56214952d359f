"""Session-serving benchmark: multi-turn conversation reuse + co-batching
through the session-oriented API (``repro.serving.server.LLMServer``) vs
fresh-prefill-per-turn.

Workload: the FAME multi-agent conversation shape (PAPER.md §memory
persistence) — W concurrent workflows, each a growing Planner / Actor /
Evaluator conversation. Every turn's prompt is the whole conversation so
far plus a short new instruction, and all W workflows submit their turn's
handle BEFORE any is drained, so the turns co-batch inside the same engine
steps. Two backends serve the identical token streams off one set of
weights:

* **sessions** — ``LLMServer`` with one session per workflow
  (``cache_mode="paged"``): turn N+1 restores turn N's end-of-generation
  state (radix-shared pages + the session's partial tail page, or the tail
  state snapshot on stateful archs) and prefills only the new instruction.
* **fresh** — the same scheduler in dense mode, sessionless: every turn
  re-prefills its full conversation from scratch (the pre-redesign
  behaviour). It replays the *exact token ids* the session engine served,
  so greedy outputs must be bit-identical.

Reported: per-turn time-to-first-token (admission prefill seconds) split by
turn index, TTFT speedup on turns >= 2 (the reuse turns), co-batching
(active slots per engine step in the session run), tail-reuse hit counters,
and the output-equality check:

    PYTHONPATH=src python benchmarks/session_bench.py [--smoke] [--arch A]

Acceptance floors (ISSUE 5): session TTFT on turns >= 2 must be <= 1/2 the
fresh-prefill TTFT, co-batching must keep > 1 active slot per engine step,
and greedy outputs must match token-for-token (CI runs ``--smoke``).

``--chaos`` (ISSUE 6) reruns the session side under a seeded
``FaultInjector`` (serving/faults.py) firing transient faults on ~5% of
decode / prefill-extend dispatches and page allocations. The timing floors
are replaced by graceful-degradation gates: every handle terminal, completed
outputs still token-identical to the fault-free fresh baseline, faults
actually injected, and p99 turn latency bounded (no deadlock, no stall).
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
    "identical, and hand off to the evaluator after each action. ")

AGENT_TURNS = [
    ("planner", "Plan: decompose the user goal into the next tool call."),
    ("actor", "Act: execute the planned tool call and record the output."),
    ("evaluator", "Evaluate: check the output against the goal; pass or retry."),
]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b")
    ap.add_argument("--workflows", type=int, default=4,
                    help="concurrent conversations (one session each)")
    ap.add_argument("--rounds", type=int, default=3,
                    help="Planner/Actor/Evaluator rounds per workflow")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--capacity", type=int, default=768)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--chunk", type=int, default=16)
    ap.add_argument("--floor", type=float, default=2.0,
                    help="required TTFT speedup on turns >= 2")
    ap.add_argument("--out", default="results/session_bench.json")
    ap.add_argument("--smoke", action="store_true",
                    help="small fast run for CI perf gating")
    ap.add_argument("--chaos", action="store_true",
                    help="inject seeded transient faults into the session "
                         "side and gate on graceful degradation instead of "
                         "the timing floors")
    ap.add_argument("--fault-rate", type=float, default=0.05,
                    help="per-dispatch fault probability in --chaos mode")
    args = ap.parse_args()
    if args.smoke:
        args.workflows, args.rounds = 3, 2

    from repro.configs.registry import ARCHS
    from repro.serving.scheduler import (EngineConfig, SamplingParams,
                                         Scheduler)
    from repro.serving.server import FaultInjector, LLMServer, RetryPolicy

    # a notch bigger than the test-suite smoke dims: prefill must be
    # compute-bound (not jit-dispatch-bound) for the A/B to measure the
    # algorithmic win rather than per-call overhead
    cfg = ARCHS[args.arch].reduced(dtype="float32", param_dtype="float32",
                                   vocab_size=512, d_model=256, num_heads=8,
                                   head_dim=32, d_ff=512, num_layers=4)
    injector = None
    if args.chaos:
        # seeded chaos on the session side only (the fresh baseline stays
        # clean — it IS the output reference); enough retry headroom that a
        # 5% transient rate dead-letters essentially nothing
        r = args.fault_rate
        injector = FaultInjector(seed=0, rates={"decode": r,
                                                "extend_paged": r,
                                                "pool.alloc": r})
    server = LLMServer(
        cfg, num_slots=args.slots, capacity=args.capacity,
        engine_cfg=EngineConfig(decode_chunk=args.chunk, cache_mode="paged",
                                page_size=args.page_size),
        injector=injector,
        retry=RetryPolicy(max_attempts=4, backoff_s=0.005))
    fresh = Scheduler(
        cfg, num_slots=args.slots, capacity=args.capacity,
        params=server.params,
        engine_cfg=EngineConfig(decode_chunk=args.chunk))
    sp = SamplingParams(max_new_tokens=args.max_new)

    def run_conversations(record: bool):
        """One full pass of W growing conversations. ``record=False`` is the
        compile warm-up — DISTINCT conversation content (same shapes), so
        the measured pass exercises the session-tail path itself rather
        than finding its whole conversation pre-cached in the radix trie."""
        sessions = [server.open_session() for _ in range(args.workflows)]
        tag = "Warmup" if not record else "Workflow"
        convs = [SYSTEM_PROMPT + f"{tag} {w}: summarize incident {w}. "
                 for w in range(args.workflows)]
        ttft_sess, ttft_fresh, match, turn_idx = [], [], [], 0
        latencies, statuses = [], []
        for r in range(args.rounds):
            for role, ask in AGENT_TURNS:
                prompts = [convs[w] + f"[{role} r{r}] {ask} "
                           for w in range(args.workflows)]
                # submit EVERY workflow's turn before draining any — the
                # co-batching the session API exists for
                handles = [sessions[w].submit(prompts[w], sp)
                           for w in range(args.workflows)]
                server.run_until_idle()
                if record:
                    # chaos mode may dead-letter a turn: count it, gate on
                    # terminal status, and replay only completed turns
                    # against the fresh baseline (the output reference)
                    done = []
                    for w, h in enumerate(handles):
                        ttft_sess.append((turn_idx, h.request.prefill_s))
                        latencies.append(h.request.latency_s)
                        statuses.append(h.request.status)
                        if h.request.status == "completed":
                            done.append((w, h))
                    reqs = [(h, fresh.enqueue(prompts[w], sp,
                                              token_ids=h.request._ids))
                            for w, h in done]
                    fresh.run_until_drained()
                    for h, fr in reqs:
                        ttft_fresh.append((turn_idx, fr.prefill_s))
                        match.append(fr.output_text == h.request.output_text)
                for w in range(args.workflows):
                    convs[w] = sessions[w].text
                turn_idx += 1
        for s in sessions:
            s.close()
        return ttft_sess, ttft_fresh, match, latencies, statuses

    run_conversations(record=False)            # compile warm-up pass
    pre = server.stats()
    t0 = time.perf_counter()
    ttft_sess, ttft_fresh, match, latencies, statuses = \
        run_conversations(record=True)
    wall = time.perf_counter() - t0
    post = server.stats()
    d = lambda k: post.get(k, 0) - pre.get(k, 0)

    def mean_ttft(rows, lo):
        vals = [s for t, s in rows if t >= lo]
        return sum(vals) / max(len(vals), 1)

    reuse_ttft = mean_ttft(ttft_sess, 1)
    fresh_ttft = mean_ttft(ttft_fresh, 1)
    speedup = fresh_ttft / max(reuse_ttft, 1e-9)
    active_per_step = ((post["active_slots_per_step"] * post["engine_steps"]
                        - pre["active_slots_per_step"] * pre["engine_steps"])
                       / max(d("engine_steps"), 1))

    result = {
        "bench": "session_serving",
        "arch": args.arch,
        "workflows": args.workflows,
        "rounds": args.rounds,
        "turns_per_workflow": args.rounds * len(AGENT_TURNS),
        "num_slots": args.slots,
        "capacity": server.capacity,
        "max_new_tokens": args.max_new,
        "warm_wall_s": round(wall, 4),
        "ttft_turn1_s": round(
            sum(s for t, s in ttft_sess if t == 0)
            / max(sum(1 for t, _ in ttft_sess if t == 0), 1), 5),
        "sessions": {
            "ttft_turns_ge2_s": round(reuse_ttft, 5),
            "turn_prefix_hits": d("turn_prefix_hits"),
            "session_turns": d("session_turns"),
            "prefix_hit_tokens": d("prefix_hit_tokens"),
            "prompt_tokens": d("prompt_tokens"),
            "active_slots_per_step": round(active_per_step, 3),
            "stream_chunks": d("stream_chunks"),
            "truncated_tokens": d("truncated_tokens"),
        },
        "fresh_baseline": {
            "ttft_turns_ge2_s": round(fresh_ttft, 5),
        },
        "ttft_speedup_turns_ge2": round(speedup, 2),
    }
    if args.chaos:
        lat = sorted(latencies)
        p99 = lat[int(0.99 * (len(lat) - 1))] if lat else 0.0
        terminal = {"completed", "cancelled", "timed_out", "failed", "shed"}
        result["chaos"] = {
            "fault_rate": args.fault_rate,
            "faults_injected": sum(injector.injected.values()),
            "faults_by_site": dict(injector.injected),
            "dispatch_retries": d("dispatch_retries"),
            "admission_retries": d("admission_retries"),
            "dead_lettered": d("dead_lettered"),
            "turns_completed": statuses.count("completed"),
            "turns_total": len(statuses),
            "p99_turn_latency_s": round(p99, 4),
        }
        # graceful degradation replaces the timing floors: faults really
        # fired, every handle reached a terminal status (no deadlock), the
        # completed outputs are still bit-identical to the fault-free
        # baseline, and tail latency stayed bounded (no unbounded stall)
        result["checks"] = {
            "faults_injected_gt_0": sum(injector.injected.values()) > 0,
            "all_handles_terminal": all(s in terminal for s in statuses),
            "outputs_token_identical": all(match) and bool(match),
            "bounded_p99_turn_latency_s": p99 < 30.0,
        }
    else:
        result["checks"] = {
            f"ttft_speedup_ge_{args.floor:g}x": speedup >= args.floor,
            "co_batching_gt_1_slot_per_step": active_per_step > 1.0,
            "outputs_token_identical": all(match) and bool(match),
            "tail_reuse_on_every_later_turn":
                d("turn_prefix_hits")
                >= args.workflows * (args.rounds * len(AGENT_TURNS) - 1),
            "no_truncation": d("truncated_tokens") == 0,
        }
    write_artifact(args.out, result)
    print(json.dumps(result, indent=2))
    if not all(result["checks"].values()):
        raise SystemExit("session_bench: perf checks FAILED")
    print(f"session_bench: OK ({speedup:.1f}x TTFT on turns >= 2, "
          f"{active_per_step:.2f} active slots/step, outputs identical) "
          f"-> {args.out}")


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
