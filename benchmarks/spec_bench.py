"""Speculative-decode benchmark: drafter-free n-gram speculation A/B.

Workload: the FAME copy-heavy decode shape (PAPER.md — research-paper
summarization / log analytics) — agent answers that re-surface spans already
sitting in the context (tool results, fetched text, log lines), exactly the
traffic where "Network and Systems Performance Characterization of
MCP-Enabled LLM Agents" (arXiv 2511.07426) measures token-generation time
dwarfing MCP overhead. The same request stream runs through engines sharing
one set of weights:

* **spec**  — ``EngineConfig(spec_len=N)``: a host-side n-gram lookup over
  prompt + generated tokens drafts up to N continuation tokens per engine
  step; ONE jit'd verify forward scores every draft position and commits the
  accepted prefix (greedy: exact match, bit-identical output).
* **base**  — ``spec_len=0``: the PR-1/2 chunked decode loop.

Both dense and paged cache modes are measured; greedy outputs must be
bit-identical between spec and base within each mode.

Reported: decode tokens/sec (wall-clock: warm drain wall minus prefill
time), speedup, draft acceptance rate, verify steps:

    PYTHONPATH=src python benchmarks/spec_bench.py [--smoke] [--arch A]

Acceptance floor (ISSUE 3): spec decode >= 1.8x base tokens/sec at >= 60%
draft acceptance on the copy-heavy workload, outputs bit-identical in dense
AND paged modes (CI runs ``--smoke`` as a perf gate).
"""
from __future__ import annotations

import argparse
import json
import time

from _artifact import write_artifact


LOG_LINES = (
    "2026-07-28T09:14:02 gateway ERROR 429 rate limit exceeded for "
    "tool=search retry_after=30s trace=ab12f9; "
    "2026-07-28T09:14:03 runner WARN cold start 812ms for fn=summarize "
    "mem=512MB; "
    "2026-07-28T09:14:05 gateway ERROR 429 rate limit exceeded for "
    "tool=fetch retry_after=30s trace=ab1301; ")

FLAP_LINES = "err 429; ok 200; "


def make_workload(n_agents: int, kind: str):
    """Prompt stream: each agent gets a shared tool-result/log context and
    an instruction whose faithful answer copies spans of it verbatim.
    ``copy``: long-period log lines (the attention-arch shape — verbatim
    span re-surfacing). ``flap``: short-period status flapping (the
    stateful-arch shape — a recurrent state locked into the cycle keeps
    emitting it, which is exactly what the n-gram drafter predicts)."""
    if kind == "copy":
        ctx = LOG_LINES * 3
        ask = "Analyze the log and list every failing line verbatim, " \
              "then name the failing tools: "
    else:
        ctx = FLAP_LINES * 20
        ask = "The status stream below flaps; continue it verbatim: "
    return [f"[agent {i}] {ask}" + ctx for i in range(n_agents)]


def run_engine(engine, prompts, max_new):
    """One cold pass (compiles + drafter warm-path shapes), then a warm
    measured pass. Engine counters are lifetime totals, so the measured pass
    reports deltas."""
    for p in prompts:
        engine.submit(p, max_new_tokens=max_new)
    engine.run_until_drained()
    cold = engine.stats()
    reqs = [engine.submit(p, max_new_tokens=max_new) for p in prompts]
    t0 = time.perf_counter()
    engine.run_until_drained()
    wall = time.perf_counter() - t0
    warm = engine.stats()
    d = lambda k: warm.get(k, 0) - cold.get(k, 0)
    prefill_s = sum(r.prefill_s for r in reqs)
    decode_s = max(wall - prefill_s, 1e-9)
    toks = d("decode_tokens")
    return {
        "warm_wall_s": round(wall, 4),
        "prefill_s": round(prefill_s, 4),
        "decode_wall_s": round(decode_s, 4),
        "decode_tokens": toks,
        "decode_tok_s": round(toks / decode_s, 2),
        "host_syncs": d("host_syncs"),
        "verify_steps": d("verify_steps"),
        "decode_chunks": d("decode_chunks"),
        "draft_tokens": d("draft_tokens"),
        "accepted_tokens": d("accepted_tokens"),
        "acceptance_rate": round(d("accepted_tokens")
                                 / max(d("draft_tokens"), 1), 4),
    }, [r.output_text for r in reqs]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b",
                    help="any registry arch — full attention verifies over "
                         "its KV cache, stateful archs (recurrentgemma / "
                         "xlstm / mixtral) through staged per-position "
                         "states + accept-length rewind")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--capacity", type=int, default=512)
    ap.add_argument("--agents", type=int, default=6)
    ap.add_argument("--max-new", type=int, default=160)
    ap.add_argument("--spec-len", type=int, default=8)
    ap.add_argument("--chunk", type=int, default=16)
    ap.add_argument("--num-layers", type=int, default=4,
                    help="reduced-config depth (use a multiple of the "
                         "arch's block pattern length)")
    ap.add_argument("--modes", default="dense,paged",
                    help="comma-separated cache modes to A/B")
    ap.add_argument("--floor", type=float, default=1.8,
                    help="CI gate: min decode tok/s speedup per mode")
    ap.add_argument("--min-accept", type=float, default=0.60,
                    help="CI gate: min draft acceptance rate")
    ap.add_argument("--workload", choices=("copy", "flap"), default=None,
                    help="copy: long-period log lines (attention copy "
                         "shape); flap: short-period status cycle "
                         "(stateful-arch shape). Default: flap for "
                         "stateful archs, copy otherwise")
    ap.add_argument("--tie-embeddings", action="store_true",
                    help="tie embed/unembed in the reduced config. Random "
                         "(untrained) stateful archs only produce the "
                         "copyable outputs this bench measures when the "
                         "residual stream reaches the unembed — trained "
                         "models copy on their own; this keeps the A/B in "
                         "the same acceptance regime (use for "
                         "recurrentgemma)")
    ap.add_argument("--out", default="results/spec_bench.json")
    ap.add_argument("--smoke", action="store_true",
                    help="small fast run for CI perf gating")
    args = ap.parse_args()
    if args.smoke:
        # decode-heavy enough that the wall-clock A/B is stable: the spec
        # engine's decode phase is several times shorter than base, so short
        # runs would put CI-runner noise right against the floor
        args.agents, args.max_new = 4, 176

    from repro.configs.registry import ARCHS
    from repro.serving.engine import EngineConfig, ServingEngine
    from repro.serving.kvpool import supports_paged

    # prefix_bench-sized dims: decode must be compute-bound (not
    # jit-dispatch-bound) so the A/B measures fewer-forwards-per-token, not
    # per-call overhead
    over = dict(vocab_size=512, d_model=256, num_heads=8, head_dim=32,
                d_ff=512, num_layers=args.num_layers)
    if args.tie_embeddings:
        over["tie_embeddings"] = True
    cfg = ARCHS[args.arch].reduced(dtype="float32", param_dtype="float32",
                                   **over)
    if args.workload is None:
        args.workload = "copy" if supports_paged(cfg)[0] else "flap"
    prompts = make_workload(args.agents, args.workload)
    modes = [m.strip() for m in args.modes.split(",") if m.strip()]

    results, outputs = {}, {}
    params = None
    for mode in modes:
        for tag, spec_len in (("spec", args.spec_len), ("base", 0)):
            eng = ServingEngine(
                cfg, num_slots=args.slots, capacity=args.capacity,
                params=params,
                engine_cfg=EngineConfig(decode_chunk=args.chunk,
                                        cache_mode=mode,
                                        spec_len=spec_len))
            params = eng.params
            results[f"{mode}_{tag}"], outputs[f"{mode}_{tag}"] = \
                run_engine(eng, prompts, args.max_new)

    speedup = {m: round(results[f"{m}_spec"]["decode_tok_s"]
                        / max(results[f"{m}_base"]["decode_tok_s"], 1e-9), 2)
               for m in modes}
    acc = results[f"{modes[0]}_spec"]["acceptance_rate"]

    checks = {"acceptance_floor": acc >= args.min_accept}
    for m in modes:
        checks[f"{m}_speedup_floor"] = speedup[m] >= args.floor
        checks[f"{m}_outputs_bit_identical"] = \
            outputs[f"{m}_spec"] == outputs[f"{m}_base"]
    result = {
        "bench": "speculative_decode",
        "arch": args.arch,
        "workload": args.workload,
        "num_slots": args.slots,
        "capacity": args.capacity,
        "spec_len": args.spec_len,
        "requests": len(prompts),
        "max_new_tokens": args.max_new,
        "speedup_floor": args.floor,
        "acceptance_floor": args.min_accept,
        **{k: v for k, v in results.items()},
        **{f"decode_speedup_{m}": speedup[m] for m in modes},
        "checks": checks,
    }
    write_artifact(args.out, result)
    print(json.dumps(result, indent=2))
    if not all(result["checks"].values()):
        raise SystemExit("spec_bench: perf checks FAILED")
    print("spec_bench: OK ("
          + " / ".join(f"{speedup[m]:.1f}x {m}" for m in modes)
          + f" decode vs non-speculative, {acc:.0%} draft acceptance, "
            f"outputs identical) -> {args.out}")


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
