#!/usr/bin/env python3
"""The readings a cell's limit is set from: the program's and the
control's widest logit gap, seed by seed.

    python3 chipbench/control.py --workload <cell> --seconds <s> \
        --seeds <n> [<n> ...] [--control-seeds <k>]

For each seed, one process serves a short window of the cell at its own
load and size (``run.serve``), then reads, on the sample the benchmark's
check takes, the widest gap by which a served token's float32-reference
logit lies below the reference's best (the program's reading). On the
first ``--control-seeds`` seeds it also reads the control: the reference
itself in 8-bit floats put in the program's place, the gap of the token
it puts first at each of the same positions. The lower reading of a limit
is the largest program reading, the upper the smallest control reading.
One JSON line per seed, then a summary line. The benchmark's own runs
never run the control.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time

import run
import manifest


def readings(wl, seed: int, seconds: float, control: bool, *,
             require_tpu=True, out_dir=None):
    """The program's (and with ``control`` the control's) widest gap on
    the check's sample of one run, with the gaps turn by turn."""
    import check
    got = run.serve(wl, seed=seed, seconds=seconds, trace=False,
                    require_tpu=require_tpu, out_dir=out_dir)
    if got is None:
        return None
    cell = wl["cell"]
    records = got["records"]
    picked = check.sample(records, seed, cell["check"]["served_tokens"])
    rows = max(r.max_new for r in records)
    out = {"seed": seed, "turns": len(picked),
           "served_tokens": int(sum(len(r.output) for r in picked)),
           "unfinished": sum(1 for r in records if r.measured and not r.ok)}
    t = time.perf_counter()
    prog = check.gap_readings(picked, got["params"], wl["spec"],
                              wl["reference"], "float32", rows)
    out["reference_s"] = time.perf_counter() - t
    out["program_gap"] = float(prog.max())
    out["program_turn_gaps"] = prog.tolist()
    if control:
        ctl = check.gap_readings(picked, got["params"], wl["spec"],
                                 wl["reference"], "fp8", rows)
        out["control_gap"] = float(ctl.max())
        out["control_turn_gaps"] = ctl.tolist()
    del got
    gc.collect()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, default=3)
    args = ap.parse_args(argv)
    bench = manifest.benchmark()
    wl = manifest.workload(bench, args.workload)
    wl["bench"] = bench
    if not run.use_program():
        return 2
    out_dir = run.ROOT / "chipbench_out" / args.workload
    out_dir.mkdir(parents=True, exist_ok=True)
    rows = []
    for i, seed in enumerate(args.seeds):
        r = readings(wl, seed, args.seconds, i < args.control_seeds,
                     out_dir=out_dir)
        if r is None:
            return 2
        rows.append(r)
        print(json.dumps(r), flush=True)
    prog = [r["program_gap"] for r in rows]
    ctl = [r["control_gap"] for r in rows if "control_gap" in r]
    print(json.dumps({"workload": args.workload, "seeds": len(rows),
                      "lower_reading": max(prog),
                      "upper_reading": min(ctl) if ctl else None,
                      "program_gaps": prog, "control_gaps": ctl}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
