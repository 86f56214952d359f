"""Fig. 7a — MCP caching effect: Actor latency breakdown, N vs C.

Comparing N (no cache, no agent memory) against C (cache + S3 file handling,
no agent memory) isolates the MCP-level optimizations, per §5.3.1. Under
``--llm jax`` the C cells additionally exercise the cache × radix composition
(fame/toolflow.py): warm tool results re-enter the token stream as radix
prefix hits."""
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
    args = harness = None
    if argv is not None or matrix is None:
        ap = fc.add_common_args(argparse.ArgumentParser(description=__doc__),
                                default_out="results/fame_fig7a.json")
        args = ap.parse_args(argv if argv is not None else [])
        if args.llm == "jax":
            harness = fc.make_harness(args.arch)
    print("fig7a,app,input,query,config,actor_s,llm_s,mcp_s,cache_hits")
    reductions = []
    cells_by_app = {}
    for app in ("RS", "LA"):
        inp = {"RS": "P1", "LA": "L1"}[app]
        llm = args.llm if args is not None else "oracle"
        cells = {c: fc.run_cell(app, c, inp, llm=llm, harness=harness)
                 for c in ("N", "C")}
        cells_by_app[app] = cells
        for qi in range(3):
            for cname, cell in cells.items():
                sp = cell.agent_split_s[qi]
                print(f"fig7a,{app},{inp},Q{qi + 1},{cname},"
                      f"{sp['actor']:.2f},{sp['llm_s']:.2f},{sp['mcp_s']:.2f},"
                      f"{cells['C'].cache_hits if cname == 'C' else 0}")
            n_mcp = cells["N"].agent_split_s[qi]["mcp_s"]
            c_mcp = cells["C"].agent_split_s[qi]["mcp_s"]
            if qi > 0 and n_mcp > 0:          # warm-cache queries only
                reductions.append((n_mcp - c_mcp) / n_mcp)
    avg = sum(reductions) / len(reductions) if reductions else 0.0
    print(f"fig7a_derived,avg_warm_mcp_latency_reduction,{avg * 100:.0f}%")
    out = {"mcp_latency_reduction": avg}
    if args is not None:
        import dataclasses
        from _artifact import write_artifact
        write_artifact(args.out, dict(
            out, cells={f"{a}/{c}": dataclasses.asdict(cell)
                        for a, cells in cells_by_app.items()
                        for c, cell in cells.items()}))
    return out


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main(argv=sys.argv[1:])
