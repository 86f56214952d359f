"""Replica-fleet benchmark: 1 vs N engines under bursty open-loop load.

Drives the same trace (load_bench's generator) against a single-replica
``FleetServer`` and an N-replica fleet with **identical per-replica
resources** (slots, admission queue depth, decode chunk), and reports the
capacity the fleet adds:

* **goodput** — completed-within-SLO requests (and their tokens) per wall
  second. Under the built-in bursty trace each clump oversubscribes a
  single replica's bounded admission queue, so the single-replica phase
  sheds a large fraction while the fleet absorbs the burst across N
  queues (plus fleet-level spill before any replica's shed path engages).
  The smoke gate requires fleet goodput >= ``--goodput-gate`` (1.6x) the
  single-replica phase at N=2.
* **prefix affinity** — arrivals are drawn from a small set of prompt
  groups sharing a >= page-size token prefix (the multi-tenant "same
  system prompt" shape). The router lands repeat groups on the replica
  whose radix keyspace already holds their first block; reported as
  ``affinity_hits`` / ``affinity_rate`` alongside each replica's
  ``prefix_hit_tokens``.
* **per-step service floor** — ``--step-delay-ms`` wedges every replica's
  engine loop with a fixed sleep. The reduced CPU model decodes so fast
  that bursts would drain before admission control engages; the floor
  makes per-request service time deterministic and host-speed-independent,
  so the 1-vs-N comparison measures *placement and admission capacity*,
  not the CI box's flops. Both phases get the same floor.
* **crash-migration probe** — a fresh 2-replica fleet pins K sessions to
  one replica (same first turn => prefix affinity co-pins them), completes
  turn 1, kills that replica's pump (chaos-style ``_step_impl`` raiser),
  then submits turn 2: the fleet must journal-replay every session onto
  the healthy peer and the continuations must be **bit-identical** to an
  uninterrupted single-server reference run with the same weights.

    PYTHONPATH=src python benchmarks/fleet_bench.py [--smoke] [--replicas N]

Acceptance gates (ISSUE 10, CI runs ``--smoke``): fleet goodput >= 1.6x
single-replica at N=2 under the bursty trace, affinity hits > 0 (rate
reported), every submitted request reaches a terminal status in both
phases, and the crash-migration probe's turn-2 outputs are token-identical
with ``migrated_sessions`` covering every pinned session.
"""
from __future__ import annotations

import argparse
import json
import threading
import time

from _artifact import write_artifact
from load_bench import make_arrivals, pctl

# prompt groups sharing a >= page-size token prefix (distinct from the
# first character so ungrouped traffic would spread by load instead);
# kept short so prefill is a single engine step and the --step-delay-ms
# floor, not prefill compute, sets the service time
GROUPS = [f"{g} tenant {g}: " for g in range(6)]
T1 = "user: summarize the incident report assistant:"
DELTA = " user: and what is the root cause? assistant:"


def _slow_steps(server, delay_s: float):
    """Deterministic per-step service-time floor (see module docstring)."""
    if delay_s <= 0:
        return
    real = server._step_impl

    def slow():
        time.sleep(delay_s)
        return real()

    server._step_impl = slow


def run_phase(args, cfg, n_replicas: int, params=None):
    """One open-loop run against an ``n_replicas`` fleet; returns
    (metrics dict, shared weight arrays)."""
    from repro.serving.faults import OverloadError
    from repro.serving.fleet import FleetServer
    from repro.serving.server import (EngineConfig, OverloadPolicy,
                                      SamplingParams)

    fleet = FleetServer(
        cfg, num_replicas=n_replicas, num_slots=args.slots,
        capacity=args.capacity, seed=args.seed, params=params,
        engine_cfg=EngineConfig(cache_mode="paged",
                                page_size=args.page_size,
                                decode_chunk=args.chunk),
        overload=OverloadPolicy(max_queue_depth=args.queue_depth),
        pump=True)
    sp = SamplingParams(max_new_tokens=args.max_new, temperature=0.0)

    # absorb jit compiles on EVERY replica before the clock (and the
    # service-time floor) starts
    for r in fleet.replicas:
        r.server.submit("warmup " * 4,
                        SamplingParams(max_new_tokens=4)).result()
    for r in fleet.replicas:
        _slow_steps(r.server, args.step_delay_ms / 1000.0)

    arrivals = make_arrivals(args)
    plan = [(off, GROUPS[i % len(GROUPS)] + f"req {i}. ")
            for i, off in enumerate(arrivals)]
    done, rejected = [], [0]
    io_lock = threading.Lock()

    def client(shard):
        for off, prompt in shard:
            now = time.perf_counter() - t0
            if off > now:
                time.sleep(off - now)
            try:
                h = fleet.submit(prompt, sp)
            except OverloadError:
                with io_lock:
                    rejected[0] += 1
                continue
            with io_lock:
                done.append(h)

    t0 = time.perf_counter()
    clients = [threading.Thread(target=client,
                                args=(plan[c::args.clients],), daemon=True)
               for c in range(args.clients)]
    for c in clients:
        c.start()
    for c in clients:
        c.join()
    fleet.run_until_idle()
    wall = time.perf_counter() - t0
    st = fleet.stats()
    weights = fleet.params
    fleet.close()

    reqs = [h.request for h in done]
    comp = [r for r in reqs if r.status == "completed"]
    ttft = [r.first_token_s for r in reqs if r.first_token_s > 0]
    good = [r for r in comp if 0 < r.first_token_s <= args.slo_ttft]
    terminal = {"completed", "cancelled", "timed_out", "failed", "shed"}
    metrics = {
        "replicas": n_replicas,
        "admitted": len(reqs),
        "rejected": rejected[0],
        "completed": len(comp),
        "shed": sum(1 for r in reqs if r.status == "shed"),
        "wall_s": round(wall, 4),
        "ttft_p50_s": round(pctl(ttft, 0.50), 5),
        "ttft_p99_s": round(pctl(ttft, 0.99), 5),
        "goodput_req_s": round(len(good) / wall, 3),
        "goodput_tok_s": round(sum(r.output_tokens for r in good) / wall, 2),
        "affinity_hits": st["affinity_hits"],
        "affinity_rate": st["affinity_rate"],
        "spilled_admissions": st["spilled_admissions"],
        "routed_requests": st["routed_requests"],
        "prefix_hit_tokens": st["prefix_hit_tokens"],
        "all_terminal": all(r.status in terminal for r in reqs),
        "nothing_live_after_drain": (st["queued_requests"] == 0
                                     and st["live_requests"] == 0),
    }
    return metrics, weights


def migration_probe(args, cfg, params):
    """Crash one replica under K live sessions; turn 2 after failover must
    equal an uninterrupted single-server reference, bit for bit."""
    from repro.serving.fleet import FleetServer
    from repro.serving.server import (EngineConfig, LLMServer,
                                      SamplingParams)

    ecfg = EngineConfig(cache_mode="paged", page_size=args.page_size,
                        decode_chunk=args.chunk)
    sp = SamplingParams(max_new_tokens=args.max_new, temperature=0.0)

    ref = LLMServer(cfg, num_slots=2, capacity=128, seed=args.seed,
                    params=params, engine_cfg=ecfg)
    sess = ref.open_session()
    ref1 = sess.submit(T1, sp).result()
    ref2 = sess.submit(sess.text + DELTA, sp).result()
    ref.close()

    k = 3
    with FleetServer(cfg, num_replicas=2, num_slots=2, capacity=128,
                     seed=args.seed, params=params, engine_cfg=ecfg,
                     pump=True, digest_ttl_s=0.0) as fleet:
        sessions = [fleet.open_session() for _ in range(k)]
        turn1 = [fs.submit(T1, sp).result() for fs in sessions]
        victim = sessions[0].replica_index  # same prompt => all co-pinned
        srv = fleet.replicas[victim].server

        def boom():
            raise RuntimeError("fleet_bench: injected replica crash")

        srv._step_impl = boom
        deadline = time.monotonic() + 30.0
        while srv.pumping and time.monotonic() < deadline:
            time.sleep(0.01)
        turn2 = [fs.submit(fs.text + DELTA, sp).result() for fs in sessions]
        st = fleet.stats()
    return {
        "sessions": k,
        "victim_replica": victim,
        "turn1_identical": turn1 == [ref1] * k,
        "turn2_identical_after_migration": turn2 == [ref2] * k,
        "migrated_sessions": st["migrated_sessions"],
        "replicas_failed": st["replicas_failed"],
        "fleet_replicas_after": st["fleet_replicas"],
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b")
    ap.add_argument("--replicas", type=int, default=2,
                    help="fleet size N for the scaled phase")
    ap.add_argument("--requests", type=int, default=96,
                    help="total arrivals per phase")
    ap.add_argument("--rate", type=float, default=200.0,
                    help="Poisson arrival rate (only with --trace poisson)")
    ap.add_argument("--trace", default="burst",
                    help="'burst' (default), 'poisson', or a JSON offsets "
                         "file — same formats as load_bench")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--slots", type=int, default=2,
                    help="slots PER replica (held fixed across phases)")
    ap.add_argument("--capacity", type=int, default=256)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--chunk", type=int, default=4)
    ap.add_argument("--page-size", type=int, default=8)
    ap.add_argument("--queue-depth", type=int, default=6,
                    help="OverloadPolicy.max_queue_depth PER replica")
    ap.add_argument("--step-delay-ms", type=float, default=30.0,
                    help="per-engine-step service-time floor (0 disables)")
    ap.add_argument("--slo-ttft", type=float, default=30.0)
    ap.add_argument("--goodput-gate", type=float, default=1.6,
                    help="required fleet/single goodput ratio")
    ap.add_argument("--out", default="results/fleet_bench.json")
    ap.add_argument("--smoke", action="store_true",
                    help="small fast run for CI gating")
    args = ap.parse_args()
    if args.smoke:
        args.requests, args.max_new = 48, 8

    from repro.configs.registry import ARCHS

    cfg = ARCHS[args.arch].reduced(dtype="float32", param_dtype="float32",
                                   vocab_size=512, d_model=256, num_heads=8,
                                   head_dim=32, d_ff=512, num_layers=4)

    single, params = run_phase(args, cfg, 1)
    fleet_m, _ = run_phase(args, cfg, args.replicas, params=params)
    probe = migration_probe(args, cfg, params)

    ratio = (fleet_m["goodput_req_s"] / single["goodput_req_s"]
             if single["goodput_req_s"] > 0 else float("inf"))
    result = {
        "bench": "fleet_serving",
        "arch": args.arch,
        "trace": args.trace,
        "requests": args.requests,
        "slots_per_replica": args.slots,
        "queue_depth_per_replica": args.queue_depth,
        "step_delay_ms": args.step_delay_ms,
        "single_replica": single,
        "fleet": fleet_m,
        "goodput_ratio": round(ratio, 3),
        "migration_probe": probe,
    }
    checks = {
        "goodput_scales": ratio >= args.goodput_gate,
        "affinity_engaged": fleet_m["affinity_hits"] > 0,
        "all_requests_terminal": (single["all_terminal"]
                                  and fleet_m["all_terminal"]),
        "nothing_live_after_drain": (
            single["nothing_live_after_drain"]
            and fleet_m["nothing_live_after_drain"]),
        "migration_bit_identical": (
            probe["turn1_identical"]
            and probe["turn2_identical_after_migration"]),
        "all_sessions_migrated": (probe["migrated_sessions"]
                                  == probe["sessions"]),
    }
    result["checks"] = checks
    write_artifact(args.out, result, seed=args.seed)
    print(json.dumps(result, indent=2, default=str))
    if not all(checks.values()):
        raise SystemExit("fleet_bench: fleet gates FAILED")
    print(f"fleet_bench: OK (goodput x{ratio:.2f} at N={args.replicas}, "
          f"{fleet_m['affinity_hits']} affinity hits, migration "
          f"bit-identical) -> {args.out}")


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
