"""Overload / load-shedding benchmark for the always-on serving stack.

Drives an ``LLMServer(pump=True, overload=OverloadPolicy(...))`` — the
standing-service deployment shape (PAPER.md's always-warm Lambda analogue)
— with open-loop arrivals over many short-lived sessions and measures how
the overload controls degrade service when demand exceeds capacity:

* **arrivals** — Poisson (seeded exponential inter-arrivals, ``--rate``)
  or trace-driven (``--trace burst`` built-in bursty trace, or a JSON file
  of arrival-time offsets in seconds). Each arrival opens its own session,
  submits one turn, and closes the session when the request reaches a
  terminal status — so the retained-tail pool churns the way a fleet of
  real conversations would.
* **priority mix** — ~30% of arrivals are high-priority (``priority=1``,
  interactive SLO); the rest are low-priority batch
  (``priority=0``). Under overload the policy sheds and preempts LOW to
  protect HIGH: bounded admission refuses (typed ``OverloadError``) or
  displaces the youngest queued low request (typed ``ShedError``), and a
  queued high request preempts a running low slot at the chunk boundary.
* **directed preemption probe** — after the open-loop phase drains, two
  long low-priority decodes are parked in every slot and a high-priority
  request submitted on top, forcing a preemption deterministically; the
  preempted request's greedy output is then replayed uncontended and must
  be **bit-identical** (resume re-prefills prompt + the k pre-generated
  tokens and continues the RNG chain at fold_in(key, k)).

Reported: per-class TTFT p50/p99 (``first_token_s`` — preserved across
preemption), time-per-output-token, goodput (completed-within-SLO requests
and their tokens per wall second), shed / preempt / timeout / dead-letter
counts, and peak queue depth/age gauges sampled during the run:

    PYTHONPATH=src python benchmarks/load_bench.py [--smoke] [--chaos]

Acceptance gates (ISSUE 8, CI runs ``--smoke`` with and without
``--chaos``): the server stays live under overload (every submitted
request reaches a terminal typed status — nothing stranded), overload
control actually engaged (sheds + admission rejections + preemptions > 0),
shed requests carry a typed ``OverloadError``/``ShedError`` (not a bare
failure), high-priority p99 TTFT stays under the gate while low-priority
degrades, and the preempted-then-resumed greedy output is bit-identical.
``--chaos`` layers the PR-6 seeded ``FaultInjector`` on top of overload
and keeps the same gates with faults actually firing (faults > 0).
"""
from __future__ import annotations

import argparse
import json
import random
import threading
import time

from _artifact import write_artifact


def make_arrivals(args) -> list:
    """Arrival-time offsets (seconds from t0), sorted ascending."""
    if args.trace == "poisson":
        rng = random.Random(args.seed)
        t, out = 0.0, []
        for _ in range(args.requests):
            t += rng.expovariate(args.rate)
            out.append(t)
        return out
    if args.trace == "burst":
        # deterministic bursty trace: requests arrive in 3 tight clumps
        # (t = 0, 0.5, 1.0) so the admission queue fills, drains, refills
        per = max(args.requests // 3, 1)
        out = []
        for b in range(3):
            n = per if b < 2 else args.requests - 2 * per
            out.extend(b * 0.5 + i * 0.002 for i in range(n))
        return sorted(out[:args.requests])
    with open(args.trace) as f:                  # JSON list of offsets
        offs = sorted(float(x) for x in json.load(f))
    return offs[:args.requests] if args.requests else offs


def pctl(vals, q):
    if not vals:
        return 0.0
    vals = sorted(vals)
    return vals[min(int(q * (len(vals) - 1) + 0.5), len(vals) - 1)]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b")
    ap.add_argument("--requests", type=int, default=300,
                    help="total arrivals (each its own session)")
    ap.add_argument("--rate", type=float, default=200.0,
                    help="Poisson arrival rate, req/s (open loop)")
    ap.add_argument("--trace", default="poisson",
                    help="'poisson', 'burst' (built-in bursty trace), or a "
                         "path to a JSON list of arrival offsets in seconds")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--clients", type=int, default=8,
                    help="concurrent submitter threads (independent open-"
                         "loop clients; keeps arrivals from self-throttling "
                         "on the pump's command round-trip)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="serve through a FleetServer of N replicas instead "
                         "of a single LLMServer (slots / queue-depth are "
                         "PER replica; see benchmarks/fleet_bench.py for "
                         "the dedicated 1-vs-N comparison)")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--capacity", type=int, default=512)
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--chunk", type=int, default=4)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--queue-depth", type=int, default=16,
                    help="OverloadPolicy.max_queue_depth")
    ap.add_argument("--deadline-lo", type=float, default=None,
                    help="deadline_s for low-priority arrivals (enables "
                         "deadline-aware shedding of the batch class)")
    ap.add_argument("--slo-ttft", type=float, default=30.0,
                    help="per-request TTFT SLO used for goodput accounting")
    ap.add_argument("--hi-ttft-gate", type=float, default=30.0,
                    help="gate: high-priority p99 TTFT must stay under this")
    ap.add_argument("--out", default="results/load_bench.json")
    ap.add_argument("--smoke", action="store_true",
                    help="small fast run for CI robustness gating")
    ap.add_argument("--chaos", action="store_true",
                    help="layer seeded transient faults on top of overload")
    ap.add_argument("--fault-rate", type=float, default=0.05)
    args = ap.parse_args()
    if args.smoke:
        args.requests, args.slots, args.queue_depth = 36, 2, 8
        args.max_new, args.capacity = 8, 256
        # hold PER-REPLICA offered load constant so the overload controls
        # still engage when the fleet doubles service capacity
        args.requests *= args.replicas

    from repro.configs.registry import ARCHS
    from repro.serving.faults import OverloadError
    from repro.serving.server import (EngineConfig, FaultInjector, LLMServer,
                                      OverloadPolicy, RetryPolicy,
                                      SamplingParams)

    cfg = ARCHS[args.arch].reduced(dtype="float32", param_dtype="float32",
                                   vocab_size=512, d_model=256, num_heads=8,
                                   head_dim=32, d_ff=512, num_layers=4)
    injector = None
    if args.chaos:
        r = args.fault_rate
        injector = FaultInjector(seed=args.seed,
                                 rates={"decode": r, "extend_paged": r,
                                        "pool.alloc": r})
    policy = OverloadPolicy(max_queue_depth=args.queue_depth, preempt=True,
                            shed_on_deadline=True)
    server_kw = dict(
        num_slots=args.slots, capacity=args.capacity, seed=args.seed,
        engine_cfg=EngineConfig(cache_mode="paged", page_size=args.page_size,
                                decode_chunk=args.chunk),
        injector=injector, overload=policy,
        retry=RetryPolicy(max_attempts=4, backoff_s=0.005),
        pump=True)
    if args.replicas > 1:
        # same per-replica knobs, fronted by the fleet router: sessions
        # stay sticky, overload spills across replicas before shedding
        from repro.serving.fleet import FleetServer
        server = FleetServer(cfg, num_replicas=args.replicas, **server_kw)
    else:
        server = LLMServer(cfg, **server_kw)

    rng = random.Random(args.seed + 1)
    arrivals = make_arrivals(args)
    # greedy everywhere: the bit-identity gates are RNG-independent and the
    # outputs replayable on any reference engine
    lo_sp = SamplingParams(max_new_tokens=args.max_new, temperature=0.0,
                           priority=0, deadline_s=args.deadline_lo)
    hi_sp = SamplingParams(max_new_tokens=args.max_new, temperature=0.0,
                           priority=1)

    inflight = []                   # (handle, session, class, submit_off)
    done = []
    rejected = {"hi": 0, "lo": 0}   # typed admission refusals per class
    gauges = {"queue_depth": 0, "queue_age_s": 0.0}
    io_lock = threading.Lock()
    draining = threading.Event()

    def reaper():
        """Close each arrival's session the moment its turn is terminal
        (so hundreds of sessions churn the tail pool instead of pinning
        it), and sample the queue-shape gauges while load is on."""
        while True:
            with io_lock:
                still = []
                for rec in inflight:
                    h, sess = rec[0], rec[1]
                    if h.request.finished:
                        sess.close()
                        done.append(rec)
                    else:
                        still.append(rec)
                inflight[:] = still
                idle = draining.is_set() and not inflight
            st = server.stats()
            gauges["queue_depth"] = max(gauges["queue_depth"],
                                        st["queued_requests"])
            gauges["queue_age_s"] = max(gauges["queue_age_s"],
                                        st["queue_age_max_s"])
            if idle:
                return
            time.sleep(0.02)

    # throwaway turns to absorb jit compiles before the clock starts (one
    # per replica when fronted by a fleet — each engine compiles its own)
    if args.replicas > 1:
        for r in server.replicas:
            r.server.submit("warmup " * 4,
                            SamplingParams(max_new_tokens=4)).result()
    else:
        server.submit("warmup " * 4,
                      SamplingParams(max_new_tokens=4)).result()

    # the full arrival schedule, decided up front (deterministic for a
    # given seed) and sharded round-robin across independent client
    # threads: each arrival = (offset_s, class, prompt)
    plan = []
    for i, off in enumerate(arrivals):
        is_hi = rng.random() < 0.3
        plan.append((off, "hi" if is_hi else "lo",
                     (f"[{'hi' if is_hi else 'lo'} {i}] summarize incident "
                      f"{i % 7} in the {i % 5} region. ") * 2))

    def client(shard):
        for off, cls, prompt in shard:
            now = time.perf_counter() - t0
            if off > now:
                time.sleep(off - now)
            sess = server.open_session()
            try:
                h = sess.submit(prompt, hi_sp if cls == "hi" else lo_sp)
            except OverloadError:
                with io_lock:
                    rejected[cls] += 1
                sess.close()
                continue
            with io_lock:
                inflight.append((h, sess, cls,
                                 time.perf_counter() - t0))

    reap = threading.Thread(target=reaper, daemon=True)
    reap.start()
    t0 = time.perf_counter()
    clients = [threading.Thread(target=client,
                                args=(plan[c::args.clients],), daemon=True)
               for c in range(args.clients)]
    for c in clients:
        c.start()
    for c in clients:
        c.join()
    draining.set()
    server.run_until_idle()
    reap.join()
    wall = time.perf_counter() - t0
    st = server.stats()

    # ---- directed preemption probe + bit-identity gate ---------------------
    # park a long low-priority decode in every slot, then submit a
    # high-priority request: with no free slot and a strict priority gap the
    # scheduler MUST preempt one low slot at its next chunk boundary
    long_sp = SamplingParams(max_new_tokens=48, temperature=0.0, priority=0)
    if args.replicas > 1:
        # park straight onto every replica's slots (bypassing the router —
        # least-loaded placement is noisy right after the open-loop phase)
        # so the fleet has no idle slot anywhere when the probe arrives;
        # longer decodes than the single-server probe because the probe's
        # own fleet placement (digest refresh + routing) takes extra pump
        # round-trips that the parked jobs must outlive
        long_sp = SamplingParams(max_new_tokens=128, temperature=0.0,
                                 priority=0)
        parked = [r.server.submit(f"long batch job {r.idx}-{s} " * 3,
                                  long_sp)
                  for r in server.replicas for s in range(args.slots)]
    else:
        parked = [server.submit(f"long batch job {i} " * 3, long_sp)
                  for i in range(args.slots)]
    deadline = time.perf_counter() + 60.0
    while (any(p.request.status != "running" for p in parked)
           and time.perf_counter() < deadline):
        time.sleep(0.005)
    probe_hi = server.submit("interactive probe",
                             SamplingParams(max_new_tokens=8, temperature=0.0,
                                            priority=5))
    probe_hi.result()
    for p in parked:
        p.result()
    victims = [p for p in parked if p.request.preempted > 0]
    probe_preempted = len(victims)
    # uncontended greedy replay of each victim's ORIGINAL prompt tokens on
    # the (now idle) server: resume must have been bit-identical
    probe_identical = True
    for v in victims:
        ref = server.submit(
            "", long_sp,
            token_ids=list(v.request._ids[:v.request._orig_plen]))
        if ref.result() != v.request.output_text:
            probe_identical = False
    probe_stats = server.stats()

    # ---- metrics -----------------------------------------------------------
    by_cls = {"hi": [], "lo": []}
    for h, _sess, cls, _off in done:
        by_cls[cls].append(h.request)
    statuses = [h.request.status for h, *_ in done]
    terminal = {"completed", "cancelled", "timed_out", "failed", "shed"}
    shed_reqs = [h.request for h, *_ in done if h.request.status == "shed"]
    sheds_typed = all(isinstance(r.error, OverloadError) for r in shed_reqs)

    def cls_metrics(reqs):
        ttft = [r.first_token_s for r in reqs if r.first_token_s > 0]
        comp = [r for r in reqs if r.status == "completed"]
        tpot = [r.decode_s / r.output_tokens for r in comp
                if r.output_tokens and r.decode_s > 0]
        good = [r for r in comp
                if 0 < r.first_token_s <= args.slo_ttft]
        return {
            "requests": len(reqs),
            "completed": len(comp),
            "shed": sum(1 for r in reqs if r.status == "shed"),
            "timed_out": sum(1 for r in reqs if r.status == "timed_out"),
            "failed": sum(1 for r in reqs if r.status == "failed"),
            "preempted": sum(1 for r in reqs if r.preempted),
            "ttft_p50_s": round(pctl(ttft, 0.50), 5),
            "ttft_p99_s": round(pctl(ttft, 0.99), 5),
            "tpot_mean_s": round(sum(tpot) / max(len(tpot), 1), 6),
            "goodput_req_s": round(len(good) / wall, 3),
            "goodput_tok_s": round(sum(r.output_tokens for r in good) / wall,
                                   2),
        }

    hi_m, lo_m = cls_metrics(by_cls["hi"]), cls_metrics(by_cls["lo"])
    overload_events = (st["shed_requests"] + st["preemptions"]
                      + rejected["hi"] + rejected["lo"])
    result = {
        "bench": "load_serving",
        "arch": args.arch,
        "trace": args.trace,
        "requests": args.requests,
        "rate_req_s": args.rate,
        "replicas": args.replicas,
        "num_slots": args.slots,
        "queue_depth": args.queue_depth,
        "max_new_tokens": args.max_new,
        "wall_s": round(wall, 4),
        "offered_load_req_s": round(len(arrivals) / max(arrivals[-1], 1e-9),
                                    2),
        "high_priority": hi_m,
        "low_priority": lo_m,
        "admission_rejected": dict(rejected),
        "overload": {
            "shed_requests": st["shed_requests"],
            "preemptions": st["preemptions"],
            "preempt_resumes": st["preempt_resumes"],
            "breaker_trips": st["breaker_trips"],
            "timed_out": st["timed_out"],
            "dead_lettered": st["dead_lettered"],
            "peak_queue_depth": gauges["queue_depth"],
            "peak_queue_age_s": round(gauges["queue_age_s"], 4),
            "ewma_decode_s_per_tok": round(st["ewma_decode_s_per_tok"], 6),
        },
        "pump": {
            "pump_steps": st["pump_steps"],
            "pump_stall_notices": st["pump_stall_notices"],
        },
        "preempt_probe": {
            "victims": probe_preempted,
            "preempt_resumes_total": probe_stats["preempt_resumes"],
            "bit_identical": probe_identical,
        },
    }
    if args.replicas > 1:
        result["fleet"] = {
            "fleet_replicas": st["fleet_replicas"],
            "routed_requests": st["routed_requests"],
            "affinity_hits": st["affinity_hits"],
            "affinity_rate": st["affinity_rate"],
            "spilled_admissions": st["spilled_admissions"],
            "migrated_sessions": st["migrated_sessions"],
        }
    checks = {
        # the server stayed live: every submitted request reached a typed
        # terminal status, nothing stranded in a queue or slot
        "all_requests_terminal": (not inflight
                                  and all(s in terminal for s in statuses)),
        "nothing_live_after_drain": (probe_stats["queued_requests"] == 0
                                     and probe_stats["live_requests"] == 0),
        # overload control engaged and sheds carry typed errors
        "overload_exercised": overload_events > 0,
        "sheds_typed": sheds_typed,
        # interactive class protected while batch degrades
        "hi_p99_ttft_bounded": hi_m["ttft_p99_s"] <= args.hi_ttft_gate,
        # the directed probe preempted and resumed bit-identically
        "probe_preempted": probe_preempted >= 1,
        "preempt_resume_bit_identical": probe_identical,
    }
    if args.chaos:
        result["chaos"] = {
            "fault_rate": args.fault_rate,
            "faults_injected": sum(injector.injected.values()),
            "faults_by_site": dict(injector.injected),
            "dispatch_retries": st["dispatch_retries"],
            "dead_lettered": st["dead_lettered"],
        }
        checks["faults_injected_gt_0"] = sum(injector.injected.values()) > 0
    result["checks"] = checks
    server.close()

    write_artifact(args.out, result, seed=args.seed)
    print(json.dumps(result, indent=2))
    if not all(checks.values()):
        raise SystemExit("load_bench: robustness checks FAILED")
    print(f"load_bench: OK ({overload_events} overload events, hi p99 TTFT "
          f"{hi_m['ttft_p99_s']:.3f}s, probe bit-identical) -> {args.out}")


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
