#!/usr/bin/env python3
"""Chip benchmark of the serving stack: one cell, one run.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Run from the root of a checkout. The cell's entry in ``BENCHMARK.json``
names its configuration and traffic mix; ``manifest.py`` finds their
files, the cell's engine settings and load, the plain reference and the
per-layer metric readers by those names.

One process: make the weights on the device from the seed, build the
server the cell describes (``LLMServer`` with its background pump, or a
``FleetServer`` of one replica per chip), warm up the cell's programs and
fill the shared prefixes its traffic uses (all of that is ``setup_s``),
then play the traffic for ``--seconds``, wait for every turn due in the
window, and check the served tokens against the float32 reference.

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics), ``device`` and, traced, a
``breakdown``; its last key, ``compared``, holds each number the check
compared with its limit, which also end stderr. Without a TPU, or with
fewer chips than the cell asks for, it exits 2 and prints no result.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import pathlib
import sys
import threading
import time

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path.insert(0, str(HERE))

import manifest  # noqa: E402

DRAIN_S = 150.0        # longest wait for turns due in the window
TRACE_MAX_S = 8.0      # the traced span: at most this long, mid-window


def log(**kw):
    print(json.dumps(kw, default=float), flush=True)


class CompileCounter:
    """Traces and compilations JAX starts, counted while ``armed``."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax.monitoring as mon
        self.armed = False
        self.count = 0
        self.seconds = 0.0
        mon.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if event in self.EVENTS:
            self.seconds += secs
            if self.armed:
                self.count += 1


def program_config(spec):
    """The served model's configuration: the registry architecture the
    configuration file names, with every size taken from the file."""
    import dataclasses
    from repro.configs.registry import ARCHS
    D = spec["hidden_size"]
    return dataclasses.replace(
        ARCHS[spec["registry"]],
        num_layers=spec["num_hidden_layers"], d_model=D,
        num_heads=spec["num_attention_heads"],
        num_kv_heads=spec["num_key_value_heads"],
        head_dim=spec.get("head_dim") or D // spec["num_attention_heads"],
        d_ff=spec["intermediate_size"], vocab_size=spec["vocab_size"],
        rope_theta=float(spec["rope_theta"]),
        norm_eps=float(spec["rms_norm_eps"]),
        tie_embeddings=spec["tie_word_embeddings"],
        attn_bias=spec["attention_bias"],
        dtype=spec["torch_dtype"], param_dtype=spec["torch_dtype"])


def check_layout(params, cfg):
    """The benchmark's weights must have the served model's layout."""
    import jax
    from repro.models import Model
    want = Model(cfg).param_specs()
    got = jax.tree.map(lambda a: (a.shape, str(a.dtype)), params)
    exp = jax.tree.map(lambda s: (s.shape, str(s.dtype)), want)
    if got != exp:
        raise SystemExit(f"weights layout differs from the served model's: "
                         f"{got} vs {exp}")


def placement(cfg, cell, devices):
    """Where the cell's weights are made, and the serve mesh if it has one.

    A cell with ``mesh`` (``[data, model]``) serves one program over that
    many chips, with its weights made straight into the program's serve
    layout; any other cell makes them on the first chip (a fleet's
    replicas copy them to their own)."""
    import jax
    if "mesh" not in cell:
        return None, jax.sharding.SingleDeviceSharding(devices[0])
    from repro.distributed import sharding
    from repro.launch.mesh import make_test_mesh
    from repro.models import Model
    mesh = make_test_mesh(tuple(cell["mesh"]))
    model = Model(cfg)
    return mesh, sharding.put_shardings(
        model.param_specs(),
        sharding.param_pspecs(model.param_axes(),
                              sharding.rules_for(mesh, "serve")), mesh)


def build_server(cfg, params, cell, devices, mesh=None):
    """The server the cell file describes: an ``LLMServer`` with its
    background pump, on one chip or over the cell's serve ``mesh``, or a
    ``FleetServer`` of ``replicas`` one-chip replicas."""
    from repro.serving.scheduler import EngineConfig
    e = cell["engine"]
    ecfg = EngineConfig(cache_mode="paged", page_size=e["page_size"],
                        num_pages=e["num_pages"],
                        prefill_buckets=tuple(e["prefill_buckets"]),
                        spec_len=e["spec_len"], mesh=mesh)
    common = dict(num_slots=e["num_slots"], capacity=e["capacity"],
                  params=params, engine_cfg=ecfg)
    replicas = cell.get("replicas", 1)
    if replicas > 1:
        from repro.launch.mesh import make_host_mesh
        from repro.serving.fleet import FleetServer
        return FleetServer(cfg, num_replicas=replicas, pump=True,
                           meshes=[make_host_mesh(d)
                                   for d in devices[:replicas]], **common)
    from repro.serving.server import LLMServer
    return LLMServer(cfg, pump=True, **common)


def _wait(handles, limit_s=1800.0):
    t = time.perf_counter()
    for h in handles:
        while not h.done:
            if time.perf_counter() - t > limit_s:
                raise SystemExit("warm-up request did not finish")
            time.sleep(0.005)
        if h.status().value != "completed":
            raise SystemExit(f"warm-up request ended {h.status().value}: "
                             f"{h.exception()!r}")


def warm_up(server, cell, vocab, prefixes, sampling, marks, t_start):
    """Run every program shape the cell's traffic uses, through the
    server's own path, on every replica: one request per prefill bucket as
    a prompt's last chunk, and one prompt longer than the largest bucket
    (a chunk that samples nothing), all at once so that they share their
    decode chunks. Then fill the shared prefixes through the server's
    front, as traffic would."""
    import numpy as np
    from generator import token_ids
    e = cell["engine"]
    rng = np.random.default_rng(12345)
    buckets = sorted(e["prefill_buckets"])
    lengths = buckets + [buckets[-1] + 1]
    targets = ([r.server for r in server.replicas]
               if hasattr(server, "replicas") else [server])
    prompts = [token_ids(rng, n, vocab).tolist() for n in lengths]
    _wait([t.submit("", sampling(2), token_ids=ids)
           for ids in prompts for t in targets])
    marks["programs"] = time.perf_counter() - t_start
    _wait([server.submit("", sampling(2), token_ids=p.tolist())
           for p in prefixes])
    marks["prefixes"] = time.perf_counter() - t_start


def device_info(devices, chips):
    d = devices[0]
    peak = max((x.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for x in devices[:chips])
    return {"platform": d.platform, "kind": d.device_kind, "count": chips,
            "memory_peak_bytes": peak}


class Tracer(threading.Thread):
    """Starts the profiler at ``start``, stops it ``length`` seconds later;
    records the traced span on the host clock. Runs off the client
    thread, so starting and stopping the profiler delays no submit."""

    def __init__(self, out_dir, start, length):
        super().__init__(name="bench-tracer", daemon=True)
        self.out_dir, self.start_at, self.length = out_dir, start, length
        self.span = None
        self.error = None

    def run(self):
        import jax
        try:
            time.sleep(max(0.0, self.start_at - time.perf_counter()))
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(str(self.out_dir),
                                     profiler_options=opts)
            with jax.profiler.TraceAnnotation("chipbench_span"):
                a = time.perf_counter()
                time.sleep(max(0.0, a + self.length - time.perf_counter()))
                b = time.perf_counter()
            jax.profiler.stop_trace()
            self.span = (a, b)
        except Exception as e:                        # reported, not hidden
            self.error = repr(e)


def serve(wl, *, seed, seconds, trace, require_tpu=True, fault=None,
          out_dir=None, t_start=None):
    """Set up and play one run of workload ``wl`` (``manifest.workload``),
    and shut the server down. Returns what the run measured, with the
    weights for the check, or None when there is no TPU or too few chips.
    ``fault(server)`` breaks the timed path underneath (tests)."""
    t_start = time.perf_counter() if t_start is None else t_start
    import jax
    import generator
    from client import LoadClient
    name = wl["entry"]["name"]
    chips = wl["entry"]["chips"]
    devices = jax.devices()
    if require_tpu and (devices[0].platform != "tpu"
                        or len(devices) < chips):
        print(f"run: {name} needs {chips} TPU chip(s); JAX sees "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return None
    from repro.serving.scheduler import SamplingParams
    counter = CompileCounter()
    spec, cell, mix = wl["spec"], wl["cell"], wl["mix"]
    ref = manifest.module(wl["reference"])
    cfg = program_config(spec)

    def sampling(n):                                    # greedy
        return SamplingParams(max_new_tokens=n)

    marks = {"jax": time.perf_counter() - t_start}
    mesh, placed = placement(cfg, cell, devices)
    params = ref.init(spec, seed, sharding=placed)
    jax.block_until_ready(params)
    check_layout(params, cfg)
    marks["weights"] = time.perf_counter() - t_start
    server = build_server(cfg, params, cell, devices, mesh)
    marks["server"] = time.perf_counter() - t_start
    plan = generator.plan(mix, cell["load"], cell["engine"], seconds)
    client = LoadClient(server, plan, vocab=spec["vocab_size"], seed=seed,
                        sampling=sampling)
    warm_up(server, cell, spec["vocab_size"], client.prefixes, sampling,
            marks, t_start)
    if fault is not None:
        fault(server)
    lead = cell["load"].get("lead_in_s", 0.0)
    t0 = time.perf_counter() + lead
    setup_s = t0 - t_start
    log(phase="setup", workload=name, seed=seed, setup_s=setup_s,
        compile_s=counter.seconds, marks_s=marks,
        **generator.offered(plan, seconds))
    stats = {}
    tracer = None
    if trace:
        length = min(TRACE_MAX_S, seconds / 2)
        tracer = Tracer(out_dir / "trace", t0 + (seconds - length) / 2,
                        length)
        tracer.start()
    armed = []

    def on_tick(now):
        if not armed and now >= t0:
            armed.append(now)
            counter.armed = True
            stats["start"] = client.pool.submit(server.stats)
        if len(armed) == 1 and now >= t0 + seconds:
            armed.append(now)
            counter.armed = False
            stats["end"] = client.pool.submit(server.stats)

    records = client.run(t0, seconds, DRAIN_S, on_tick=on_tick)
    stats = {k: f.result() for k, f in stats.items()}
    client.close()
    if tracer is not None:
        tracer.join()
    dev = device_info(devices, chips)
    log(phase="window", compiles_in_window=counter.count,
        memory_peak_bytes=dev["memory_peak_bytes"],
        turns=len(records), measured=sum(r.measured for r in records),
        **{k: stats["end"].get(k) for k in (
            "pages_peak_in_use", "pump_stall_notices", "admission_retries",
            "dead_lettered", "decode_chunks", "extend_chunks")})
    server.close()
    del client, server
    gc.collect()
    return {"records": records, "params": params, "ref": ref, "t0": t0,
            "setup_s": setup_s, "stats": stats, "device": dev,
            "tracer": tracer}


def run_cell(wl, *, seed, seconds, trace, require_tpu=True, fault=None,
             out_dir=None, t_start=None):
    """One run of workload ``wl``: ``serve``, then the cell's metrics and
    the check. Returns the result object, or None without the chips."""
    import check
    import metrics_run
    got = serve(wl, seed=seed, seconds=seconds, trace=trace,
                require_tpu=require_tpu, fault=fault, out_dir=out_dir,
                t_start=t_start)
    if got is None:
        return None
    name = wl["entry"]["name"]
    records, dev = got["records"], got["device"]
    measured = [r for r in records if r.measured]
    ctx = metrics_run.Run(records=records, t0=got["t0"], seconds=seconds,
                          setup_s=got["setup_s"], spec=wl["spec"],
                          chips=wl["entry"]["chips"], stats=got["stats"],
                          kind=dev["kind"], ref=got["ref"])
    result = {"correct": False, "attempted": len(measured),
              "failed": sum(not r.ok for r in measured)}
    if trace:
        ctx.attach_trace(out_dir / "trace", got["tracer"])
        steps, contexts = ctx.decode_steps_in_span()
        log(phase="trace", programs_s=ctx.trace.programs(),
            custom_calls_s=ctx.trace.kernel_time("tpu_custom_call"),
            decode_seq_steps=steps, decode_contexts=contexts,
            admissions=len(ctx.admissions_in_span()))
        result["metrics"] = {
            m["name"]: {"value": v, "unit": m["unit"]}
            for m in manifest.per_layer(wl["bench"], name)
            if (v := manifest.reader(m["name"]).read(ctx)) is not None}
        dev["busy_s"], dev["window_s"] = ctx.busy_s, ctx.window_s
        result["breakdown"] = ctx.breakdown()
    else:
        result["metrics"] = {
            m["name"]: {"value": metrics_run.END_TO_END[m["name"]](ctx),
                        "unit": m["unit"]}
            for m in manifest.end_to_end(wl["bench"], name)}
    result["device"] = dev
    compared, checked = check.compare(records, got["params"], wl["spec"],
                                      wl["reference"], wl["cell"], seed)
    log(phase="check", **checked)
    result["correct"] = check.holds(compared)
    result["compared"] = compared
    for k, c in compared.items():
        print(f"compared {k} = {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    return result


def use_program() -> bool:
    """Put the serving stack on the path, with JAX's persistent compilation
    cache at its fixed place inside this checkout: the program's
    ``use_compile_cache`` takes the directory ``JAX_COMPILATION_CACHE_DIR``
    names, and the benchmark names its own, so that two checkouts never
    share compiled programs. False when the checkout holds no serving
    stack."""
    if not (ROOT / "src" / "repro").is_dir():
        print("run: the serving stack (src/repro) is not in this checkout",
              file=sys.stderr)
        return False
    sys.path.insert(0, str(ROOT / "src"))
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    import jax
    # every program, however quick to compile, is kept: a set-up that
    # compiles nothing on its second run is a steady one
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return True


def main(argv=None) -> int:
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = manifest.benchmark()
    wl = manifest.workload(bench, args.workload)
    wl["bench"] = bench
    if not use_program():
        return 2
    out_dir = ROOT / "chipbench_out" / args.workload
    out_dir.mkdir(parents=True, exist_ok=True)
    result = run_cell(wl, seed=args.seed, seconds=args.seconds,
                      trace=bool(args.trace), out_dir=out_dir,
                      t_start=t_start)
    if result is None:
        return 2
    print(json.dumps(result, default=float), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
