#!/usr/bin/env python3
"""The knee of an open-loop cell: the highest offered rate it sustains.

    python3 chipbench/sweep.py --workload <cell> --seconds <s> --seed <n> \
        --rates <r> [<r> ...]

One process serves the cell once per rate (``run.serve``, the cell's own
engine and mix with ``load.rate_per_s`` replaced), lowest rate first, and
prints one JSON line per rate. A rate is sustained when every turn due in
the window finished, the pump was not stalled, and the median time to the
first token of the turns due in the window's second half is within twice
that of its first half (no backlog that grows through the run). The last
line names the knee, the highest sustained rate below the first one that
is not. The cell's rate is set to about four fifths of it by hand; the
benchmark's own runs never sweep.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys

import manifest
import metrics_run
import run
from generator import percentile


def reading(wl, rate: float, seed: int, seconds: float, out_dir, *,
            require_tpu=True) -> dict:
    """One run of the cell at ``rate``, and whether it was sustained."""
    wl["cell"]["load"]["rate_per_s"] = rate
    got = run.serve(wl, seed=seed, seconds=seconds, trace=False,
                    require_tpu=require_tpu, out_dir=out_dir)
    if got is None:
        return None
    ctx = metrics_run.Run(records=got["records"], t0=got["t0"],
                          seconds=seconds, setup_s=got["setup_s"],
                          spec=wl["spec"], chips=wl["entry"]["chips"],
                          stats=got["stats"], kind=got["device"]["kind"],
                          ref=got["ref"])
    measured = ctx.measured
    half = ctx.t0 + seconds / 2

    def p50(recs):
        return percentile([r.first - r.due for r in recs if r.ok], 50)

    early = p50([r for r in measured if r.due < half])
    late = p50([r for r in measured if r.due >= half])
    unfinished = sum(not r.ok for r in measured)
    stalls = got["stats"]["end"].get("pump_stall_notices") or 0
    out = {"rate": rate, "measured": len(measured),
           "unfinished": unfinished, "stalls": stalls,
           "ttft_p50_early_s": early, "ttft_p50_late_s": late,
           "slots_busy_per_step":
               manifest.reader("slots_busy_per_step").read(ctx),
           **{k: f(ctx) for k, f in metrics_run.END_TO_END.items()}}
    out["sustained"] = bool(
        measured and unfinished == 0 and not stalls
        and early is not None and late is not None and late <= 2 * early)
    del got, ctx
    gc.collect()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    bench = manifest.benchmark()
    wl = manifest.workload(bench, args.workload)
    wl["bench"] = bench
    if not run.use_program():
        return 2
    out_dir = run.ROOT / "chipbench_out" / args.workload
    out_dir.mkdir(parents=True, exist_ok=True)
    knee = None
    for rate in sorted(args.rates):
        r = reading(wl, rate, args.seed, args.seconds, out_dir)
        if r is None:
            return 2
        print(json.dumps(r, default=float), flush=True)
        if not r["sustained"]:
            break
        knee = rate
    print(json.dumps({"workload": args.workload, "knee": knee}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
