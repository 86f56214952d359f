"""Fig. 5 — Input/output LLM tokens per workflow invocation + LLM cost.

Under ``--llm jax`` the token columns are *billed* tokens from the real
serving stack: session continuations bill only their delta, cache-hit tool
injections bill zero (EXPERIMENTS.md §Billing)."""
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
                                default_out="results/fame_fig5.json")
        args = ap.parse_args(argv if argv is not None else [])
        matrix, _ = fc.matrix_from_args(args)
    print("fig5,app,input,query,config,in_tokens,out_tokens,llm_cents")
    for (app, config, inp), cell in sorted(matrix.items()):
        for qi in range(3):
            print(f"fig5,{app},{inp},Q{qi + 1},{config},{cell.in_tokens[qi]},"
                  f"{cell.out_tokens[qi]},{cell.llm_cents[qi]:.4f}")
    # headline: input-token reduction, session totals N -> best of {C,M,M+C}
    best = 0.0
    for app in ("RS", "LA"):
        for inp in {k[2] for k in matrix if k[0] == app}:
            n = sum(matrix[(app, "N", inp)].in_tokens)
            for c in ("C", "M", "M+C"):
                m = sum(matrix[(app, c, inp)].in_tokens)
                if n:
                    best = max(best, (n - m) / n)
    print(f"fig5_derived,max_input_token_reduction,{best * 100:.0f}%")
    out = {"max_token_reduction": best}
    if args is not None:
        from _artifact import write_artifact
        write_artifact(args.out, dict(out, matrix=fc.matrix_to_dict(matrix)))
    return out


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main(argv=sys.argv[1:])
