"""Fig. 6 — Monetary cost decomposition: LLM vs agent-FaaS vs MCP-FaaS.

Under ``--llm jax`` the LLM component is priced from billed serving tokens
and the FaaS components meter real wall seconds charged into the simulated
clock (EXPERIMENTS.md §Billing)."""
from __future__ import annotations

import argparse
import os
import sys

try:
    from benchmarks import fame_common as fc
except ModuleNotFoundError:
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from benchmarks import fame_common as fc


def main(matrix=None, argv=None):
    args = None
    if matrix is None:
        ap = fc.add_common_args(argparse.ArgumentParser(description=__doc__),
                                default_out="results/fame_fig6.json")
        args = ap.parse_args(argv if argv is not None else [])
        matrix, _ = fc.matrix_from_args(args)
    print("fig6,app,input,config,llm_cents,agent_faas_cents,mcp_faas_cents,"
          "total_cents,llm_share")
    totals = {}
    for (app, config, inp), cell in sorted(matrix.items()):
        llm = sum(cell.llm_cents)
        ag = sum(cell.faas_agent_cents)
        mcp = sum(cell.faas_mcp_cents)
        tot = llm + ag + mcp
        totals[(app, config, inp)] = tot
        share = llm / tot if tot else 0
        print(f"fig6,{app},{inp},{config},{llm:.3f},{ag:.3f},{mcp:.3f},"
              f"{tot:.3f},{share:.2f}")
    best = 0.0
    for app in ("RS", "LA"):
        for inp in {k[2] for k in totals if k[0] == app}:
            base = max(totals[(app, c, inp)] for c in ("E", "N"))
            ours = min(totals[(app, c, inp)] for c in ("C", "M", "M+C"))
            if base:
                best = max(best, (base - ours) / base)
    print(f"fig6_derived,max_cost_reduction,{best * 100:.0f}%")
    out = {"max_cost_reduction": best}
    if args is not None:
        from _artifact import write_artifact
        write_artifact(args.out, dict(out, matrix=fc.matrix_to_dict(matrix)))
    return out


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main(argv=sys.argv[1:])
