"""Fig. 4 — End-to-end workflow execution latencies per (app, input, query,
config), with per-agent splits, tool-call counts and DNF tags.

``--llm jax`` runs the matrix on the real ``LLMServer`` (fame/ subsystem) and
asserts the serving invariants — M+C beats baseline E on latency and input
tokens, memory configs reuse session tails instead of re-prefilling history,
cache-hit tool injections radix-hit, and per-state retries route through the
PR-6 fault taxonomy with every handle terminal. This is the CI smoke gate
(``--smoke --llm jax``)."""
from __future__ import annotations

import argparse
import os
import sys

try:
    from benchmarks import fame_common as fc
except ModuleNotFoundError:                      # `python benchmarks/fig4_latency.py`
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from benchmarks import fame_common as fc


def main(matrix=None, argv=None):
    args = harness = None
    if matrix is None:
        ap = fc.add_common_args(argparse.ArgumentParser(description=__doc__),
                                default_out="results/fame_fig4.json")
        args = ap.parse_args(argv if argv is not None else [])
        matrix, harness = fc.matrix_from_args(args)
    print("fig4,app,input,query,config,e2e_s,planner_s,actor_s,evaluator_s,"
          "tool_calls,dnf")
    for (app, config, inp), cell in sorted(matrix.items()):
        for qi in range(3):
            sp = cell.agent_split_s[qi]
            print(f"fig4,{app},{inp},Q{qi + 1},{config},"
                  f"{cell.e2e_s[qi]:.1f},{sp['planner']:.1f},{sp['actor']:.1f},"
                  f"{sp['evaluator']:.1f},{cell.tool_calls[qi]},"
                  f"{int(cell.dnf[qi])}")
    # headline: max speedup of M+C vs worst baseline on completed queries
    best = 0.0
    for (app, config, inp), cell in matrix.items():
        if config != "M+C":
            continue
        for qi in range(3):
            for base in ("E", "N"):
                b = matrix[(app, base, inp)]
                if not b.dnf[qi] and cell.e2e_s[qi] > 0:
                    best = max(best, b.e2e_s[qi] / cell.e2e_s[qi])
    print(f"fig4_derived,max_speedup_MC_vs_baseline,{best:.1f}x")
    out = {"max_speedup": best}

    if args is not None and args.llm == "jax":
        from _artifact import write_artifact
        failures = fc.check_jax_gates(matrix, harness)
        fault_report = fc.check_fault_path(harness)
        if not fault_report["ok"]:
            failures.append(f"fault-path check failed: {fault_report}")
        out.update(fault_report=fault_report, gate_failures=failures,
                   server_stats=harness.server.stats())
        write_artifact(args.out, dict(out, matrix=fc.matrix_to_dict(matrix)))
        for f in failures:
            print(f"GATE FAIL: {f}")
        print(f"fig4_gates,{'FAIL' if failures else 'PASS'},"
              f"fault_path={'PASS' if fault_report['ok'] else 'FAIL'}")
        if failures:
            sys.exit(1)
    elif args is not None:
        from _artifact import write_artifact
        write_artifact(args.out, dict(out, matrix=fc.matrix_to_dict(matrix)))
    return out


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main(argv=sys.argv[1:])
