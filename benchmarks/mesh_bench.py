"""Mesh-sharded serving A/B: single-device vs a 2×4 host mesh.

Runs the same greedy request stream through two servers sharing one set of
weights — the default single-device engine and one with
``EngineConfig(mesh=make_test_mesh((2, 4)))`` — and reports decode
throughput for both plus the **bit_identical** flag the CI ``mesh`` job
gates on (the outputs must match string-for-string; the serve layout never
splits a float contraction, see distributed/sharding.py).

On CPU the eight "devices" are XLA host threads carved from one socket, so
mesh throughput is a *correctness-under-partitioning* artifact, not a
speedup claim — the JSON records the ratio so regressions in partitioned
compile output are visible across PRs, and the same harness run on a real
8-chip slice measures true tensor-parallel scaling.

    PYTHONPATH=src python benchmarks/mesh_bench.py [--smoke] [--arch A]
                                                   [--mode dense|paged]

The script forces ``--xla_force_host_platform_device_count=8`` itself
(before importing jax) when the environment doesn't already provide enough
devices, so it runs identically under CI and bare invocation.

Exit status is non-zero when outputs diverge: the artifact is the evidence,
the exit code is the gate.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

from _artifact import write_artifact

# must happen before `import jax` anywhere in this process
if "--xla_force_host_platform_device_count" not in os.environ.get(
        "XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                               " --xla_force_host_platform_device_count=8"
                               ).strip()

import jax  # noqa: E402


def make_prompts(n):
    seeds = ["the quick brown fox jumps over the lazy dog",
             "err 429 err 429 err 429. retry with backoff. go",
             "a b c a b c a b c d e f",
             "summarize: the meeting moved to tuesday at noon"]
    return [seeds[i % len(seeds)] + f" [req {i}]" for i in range(n)]


def run_server(cfg, ecfg, prompts, max_new, *, slots, capacity, params=None):
    from repro.serving.server import LLMServer, SamplingParams
    srv = LLMServer(cfg, num_slots=slots, capacity=capacity, seed=7,
                    params=params, engine_cfg=ecfg)
    # warm the jits (prefill buckets + decode + extend) outside the timer
    w = srv.submit(prompts[0], SamplingParams(max_new_tokens=4))
    srv.run_until_idle()
    w.result()
    tok0 = srv.stats()["decode_tokens"]
    t0 = time.perf_counter()
    handles = [srv.submit(p, SamplingParams(max_new_tokens=max_new))
               for p in prompts]
    srv.run_until_idle()
    wall = time.perf_counter() - t0
    outs = [h.result() for h in handles]
    stats = srv.stats()
    toks = stats["decode_tokens"] - tok0
    params = srv.params
    srv.close()
    return {
        "wall_s": round(wall, 4),
        "decode_tokens": int(toks),
        "decode_tok_s": round(toks / max(wall, 1e-9), 2),
        "sharded": stats["sharded"],
        "mesh_devices": stats["mesh_devices"],
        "mesh_shape": stats["mesh_shape"],
    }, outs, params


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b")
    ap.add_argument("--mode", default="paged", choices=["dense", "paged"])
    ap.add_argument("--mesh-shape", default="2x4")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--capacity", type=int, default=96)
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--spec-len", type=int, default=0)
    ap.add_argument("--out", default="results/mesh_bench.json")
    ap.add_argument("--smoke", action="store_true",
                    help="small fast run for CI gating")
    args = ap.parse_args()
    if args.smoke:
        args.requests, args.max_new, args.slots = 6, 16, 2

    from repro.configs.registry import ARCHS
    from repro.launch.mesh import make_test_mesh
    from repro.serving.scheduler import EngineConfig

    shape = tuple(int(x) for x in args.mesh_shape.split("x"))
    mesh = make_test_mesh(shape)
    cfg = ARCHS[args.arch].reduced(dtype="float32", param_dtype="float32",
                                   vocab_size=512, num_kv_heads=4)
    prompts = make_prompts(args.requests)
    kw = dict(cache_mode=args.mode, page_size=8, spec_len=args.spec_len)

    single_r, single_out, params = run_server(
        cfg, EngineConfig(**kw), prompts, args.max_new,
        slots=args.slots, capacity=args.capacity)
    mesh_r, mesh_out, _ = run_server(
        cfg, EngineConfig(mesh=mesh, **kw), prompts, args.max_new,
        slots=args.slots, capacity=args.capacity,
        params=jax.device_get(params))

    bit_identical = single_out == mesh_out
    result = {
        "bench": "mesh_serving",
        "arch": args.arch,
        "cache_mode": args.mode,
        "mesh_shape": {"data": shape[0], "model": shape[1]},
        "device_count": jax.device_count(),
        "requests": args.requests,
        "max_new_tokens": args.max_new,
        "spec_len": args.spec_len,
        "single_device": single_r,
        "mesh": mesh_r,
        "mesh_over_single_tok_s": round(
            mesh_r["decode_tok_s"] / max(single_r["decode_tok_s"], 1e-9), 3),
        "bit_identical": bit_identical,
        "smoke": args.smoke,
    }
    write_artifact(args.out, result, seed=7)
    print(json.dumps(result, indent=2))
    if not bit_identical:
        print("FAIL: mesh output diverged from single-device", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    sys.exit(main())
