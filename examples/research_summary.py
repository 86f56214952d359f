"""Research-paper-summarization app (§4.1) across all five memory configs —
reproduces the paper's Fig. 3/4 behaviour interactively.

    PYTHONPATH=src python examples/research_summary.py [--paper P1]
"""
import argparse

from repro.apps import research_summary as rs
from repro.core.config import CONFIGS
from repro.core.runtime import FameRuntime


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--paper", default="P1", choices=["P1", "P2", "P3"])
    args = ap.parse_args()

    print(f"paper: {rs.data.title_of(args.paper)!r}")
    print(f"{'config':6s} {'Q1':>14s} {'Q2':>14s} {'Q3':>14s} "
          f"{'in_tok':>8s} {'e2e_s':>7s}")
    for cname in ["E", "N", "C", "M", "M+C"]:
        rt = FameRuntime(config=CONFIGS[cname])
        for role, o in rs.build_oracles().items():
            rt.set_llm(role, o)
        rt.deploy_mcp(rs.APP.servers, rs.APP.sources)
        res = rt.run_session(f"s-{args.paper}", rs.queries(args.paper))
        cells = []
        for st, tr in zip(res.statuses, res.traces):
            faas = [s for s in tr.spans if s.kind == "faas"]
            dur = (max(s.t_end for s in faas) - min(s.t_start for s in faas)
                   if faas else 0)
            cells.append(f"{'OK' if st == 'SUCCEEDED' else 'DNF'}/{dur:5.1f}s")
        tok = sum(t.llm_tokens()[0] for t in res.traces)
        tot = sum(max((s.t_end for s in t.spans if s.kind == 'faas'), default=0)
                  - min((s.t_start for s in t.spans if s.kind == 'faas'), default=0)
                  for t in res.traces)
        print(f"{cname:6s} {cells[0]:>14s} {cells[1]:>14s} {cells[2]:>14s} "
              f"{tok:8d} {tot:7.1f}")


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
