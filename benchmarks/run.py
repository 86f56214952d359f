"""Benchmark runner — one function per paper table/figure.

Prints ``name,...`` CSV rows per figure plus derived headline numbers, and a
final validation block comparing against the paper's claims (13× latency,
88% input-token reduction, 66% cost reduction, DNF pattern).
"""
from __future__ import annotations

import sys


def main() -> None:
    from benchmarks import (fig4_latency, fig5_tokens, fig6_cost,
                            fig7a_caching, fig7b_consolidation)
    from benchmarks.fame_common import run_matrix

    matrix = run_matrix()
    d4 = fig4_latency.main(matrix)
    d5 = fig5_tokens.main(matrix)
    d6 = fig6_cost.main(matrix)
    d7a = fig7a_caching.main()
    fig7b_consolidation.main()

    # ---- validation vs the paper's claims --------------------------------
    print("\n=== validation vs paper claims ===")
    checks = [
        ("latency speedup M+C vs baseline (paper: up to 13x)",
         d4["max_speedup"], 5.0),
        ("input-token reduction (paper: up to 88%)",
         d5["max_token_reduction"] * 100, 60.0),
        ("cost reduction (paper: up to 66%)",
         d6["max_cost_reduction"] * 100, 50.0),
        ("warm MCP latency reduction from caching (paper: ~28-33%)",
         d7a["mcp_latency_reduction"] * 100, 15.0),
    ]
    ok = True
    for name, value, floor in checks:
        status = "PASS" if value >= floor else "FAIL"
        ok &= value >= floor
        print(f"{status}: {name}: {value:.1f}")
    sys.exit(0 if ok else 1)


if __name__ == '__main__':
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
