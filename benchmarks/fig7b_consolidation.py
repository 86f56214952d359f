"""Fig. 7b — Singleton vs consolidated MCP deployment under a 1-RPS synthetic
workload (§5.3.2): per-request total MCP latency timeline, cold starts, cost.

Mimics the paper's methodology: a Step-Function-like driver fires the
applications' MCP call sequence (each server invoked twice — two ReAct
iterations) at 1 RPS for 120 s, without spending agent LLM tokens.

``--llm jax`` adds the serving-side consolidation story (fame/fusion.py):
three concurrent workflow chains run either serialized (singleton — each
agent invocation drains the engine alone) or co-batched (consolidated — all
invocations share engine steps via ``CoBatchDriver``), and the gate asserts
the consolidated run actually co-batches (``active_slots_per_step > 1``)."""
from __future__ import annotations

import argparse
import os
import sys
import time

try:
    from benchmarks import fame_common as fc
except ModuleNotFoundError:
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from benchmarks import fame_common as fc

from repro.apps import log_analytics as la
from repro.apps import research_summary as rs
from repro.core.config import CONFIGS
from repro.core.mcp import rpc_call
from repro.core.runtime import FameRuntime
from repro.core.telemetry import Trace, use_trace

SEQUENCES = {
    "RS": [("download_paper", {"title": rs.data.title_of("P1")}),
           ("summarize_text", {"query": "Summarize Introduction",
                               "text": "$inline"})] * 2,
    "LA": [("filter_by_keyword", {"file": "/logs/apache.log", "keyword": "AH01630"}),
           ("mean", {"values": "[1.0, 2.0, 3.0]"}),
           ("line_plot", {"data": "[1.0, 2.0, 3.0]", "title": "t"})] * 2,
}


def run_workload(app_key: str, fusion: str, *, rps: float = 1.0,
                 duration_s: float = 120.0):
    app = {"RS": rs, "LA": la}[app_key]
    rt = FameRuntime(config=CONFIGS["E"], fusion_mode=fusion)
    rt.deploy_mcp(app.APP.servers, app.APP.sources)
    seq = SEQUENCES[app_key]
    points = []
    n = int(duration_s * rps)
    for i in range(n):
        t_arrival = i / rps
        trace = Trace()
        with use_trace(trace):
            t = t_arrival
            for tool, args in seq:
                fn = rt.resolve_tool_function(tool)
                if args.get("text") == "$inline":
                    args = dict(args, text=rs.data.paper_content("P1")[:2000])
                _, t = rt.platform.invoke(fn, {"body": rpc_call(tool, args)}, t)
        points.append((t_arrival, t - t_arrival))
    stats = rt.platform.stats
    cold = sum(s["cold_starts"] for k, s in stats.items() if k.startswith("mcp"))
    cost = sum(s["cost_cents"] for k, s in stats.items() if k.startswith("mcp"))
    calls = sum(s["invocations"] for k, s in stats.items() if k.startswith("mcp"))
    return points, cold, cost / max(calls, 1)


def run_serving_chains(arch: str, mode: str, smoke: bool) -> dict:
    """Three concurrent RS workflow chains (config M+C) on one real server:
    singleton serializes agent invocations, consolidated co-batches them."""
    harness = fc.make_harness(arch, cobatch=(mode == "consolidated"))
    app = rs

    def chain_thunk(inp):
        def run():
            rt, meter = fc._build_serving_runtime(app, "M+C", mode, harness)
            queries = app.APP.queries(inp)
            res = rt.run_session(f"RS-{inp}-{mode}",
                                 queries[:1] if smoke else queries)
            return res.statuses, meter
        return run

    before = dict(harness.server.stats())
    t0 = time.perf_counter()
    results = harness.driver.run([chain_thunk(i) for i in app.APP.inputs])
    makespan = time.perf_counter() - t0
    after = harness.server.stats()
    statuses = [s for st, _ in results for s in st]
    meters = [m for _, m in results]
    return {
        "mode": mode,
        "chains": len(results),
        "statuses": statuses,
        "makespan_s": makespan,
        "active_slots_per_step": after["active_slots_per_step"],
        "engine_steps": after["engine_steps"] - before["engine_steps"],
        "turns": sum(len(m.records) for m in meters),
        "all_terminal": all(m.all_terminal() for m in meters),
    }


def main(argv=None):
    args = None
    if argv is not None:
        ap = fc.add_common_args(argparse.ArgumentParser(description=__doc__),
                                default_out="results/fame_fig7b.json")
        args = ap.parse_args(argv)
    print("fig7b,app,mode,t_arrival_s,total_mcp_latency_s")
    out = {}
    for app in ("RS", "LA"):
        for mode in ("singleton", "consolidated"):
            pts, cold, cents_per_call = run_workload(app, mode)
            for t, lat in pts[:10] + pts[30:40:3]:     # head + stable sample
                print(f"fig7b,{app},{mode},{t:.0f},{lat:.2f}")
            stable = [l for t, l in pts if t >= 40]
            avg_stable = sum(stable) / len(stable)
            print(f"fig7b_summary,{app},{mode},cold_starts={cold},"
                  f"stable_latency_s={avg_stable:.2f},"
                  f"cents_per_call={cents_per_call:.4f}")
            out[(app, mode)] = (cold, avg_stable, cents_per_call)
    for app in ("RS", "LA"):
        s, c = out[(app, "singleton")], out[(app, "consolidated")]
        print(f"fig7b_derived,{app},cold_start_reduction,{s[0]}->{c[0]},"
              f"stable_speedup,{s[1] / c[1]:.2f}x")

    if args is not None and args.llm == "jax":
        from _artifact import write_artifact
        serving = {m: run_serving_chains(args.arch, m, args.smoke)
                   for m in ("singleton", "consolidated")}
        for m, r in serving.items():
            print(f"fig7b_serving,{m},chains={r['chains']},"
                  f"makespan_s={r['makespan_s']:.1f},"
                  f"active_slots_per_step={r['active_slots_per_step']:.2f},"
                  f"all_terminal={int(r['all_terminal'])}")
        failures = []
        cons = serving["consolidated"]
        if cons["active_slots_per_step"] <= 1.05:
            failures.append("consolidated chains did not co-batch "
                            f"(active_slots_per_step="
                            f"{cons['active_slots_per_step']:.2f})")
        if not all(s == "SUCCEEDED" for r in serving.values()
                   for s in r["statuses"]):
            failures.append("a serving chain DNF'd")
        if not all(r["all_terminal"] for r in serving.values()):
            failures.append("non-terminal handles after chain drain")
        write_artifact(args.out, {
            "oracle": {f"{a}/{m}": v for (a, m), v in out.items()},
            "serving": serving, "gate_failures": failures})
        for f in failures:
            print(f"GATE FAIL: {f}")
        print(f"fig7b_gates,{'FAIL' if failures else 'PASS'}")
        if failures:
            sys.exit(1)
    elif args is not None:
        from _artifact import write_artifact
        write_artifact(args.out,
                       {"oracle": {f"{a}/{m}": v for (a, m), v in out.items()}})
    return out


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main(argv=sys.argv[1:])
