#!/usr/bin/env python3
"""Chip smoke test: the serving stack's main path on TPU, at full width.

    python3 chip_smoke.py [--seed N]           # one chip
    python3 chip_smoke.py --chips 4 [--seed N]  # the multi-chip paths only

One chip: serves qwen2.5-3b at its published width and depth (36 layers,
d_model 2048, 16 query / 2 KV heads, d_ff 11008, vocab 151936, bf16;
random weights from ``--seed``) through ``LLMServer`` with the paged KV
cache and n-gram speculation (``spec_len=4``). Four sessions share a
~1k-token system prompt and take two greedy turns each, so bucketed
prefill, paged extend over radix-shared pages, paged decode, speculative
verify and session-tail reuse all run on the compiled Pallas kernels.
Every handle must reach COMPLETED. Two checks against the float32
reference forward (``repro.models.reference``) on the same weights:
  * the served greedy tokens of session 0, both turns, must equal the
    reference's argmax given the same served context at ``TOP1_MIN`` of
    their positions;
  * at the model level (bf16, the kernels), the logits of a prefill
    right-padded to its bucket and of a paged extend followed by 32 paged
    decode steps must agree with the reference over 256 positions of the
    prompt: relative L2 error at most ``REL_L2_MAX`` and top-1 agreement
    at least ``TOP1_MIN``.

Four chips (``--chips 4``) runs only what needs several chips, each beside
what it is compared with:
  (a) ``FleetServer`` with four qwen2.5-3b replicas, one per chip: every
      replica's weights and pools must sit on its own device, and greedy
      outputs must equal one replica serving the same requests;
  (b) mistral-nemo-12b on the bit-exact (1, 4) "serve" mesh at full depth,
      then a depth cut that also fits one chip, served on one chip and on
      the mesh: greedy outputs must be string-identical.

Bring-up readings (set-up and compile seconds, first-token and decode wall
times, compile and prefix-hit counters, host syncs per token, peak device
bytes) print as JSON lines; the last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Without a TPU, or when any check fails, it exits non-zero and prints no
such line. The compile cache goes where ``JAX_COMPILATION_CACHE_DIR`` says,
else to ``.jax_cache/`` in this checkout (``repro.launch.compile_cache``).
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import random
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

# Served bf16 logits vs the float32 reference. bf16 rounds each operation
# to 2^-8 relative; through 36 layers that gave a relative L2 error of 0.011
# at d_model 256 on the CPU, so 0.05 leaves a 4x margin, while a path that
# dropped to 8-bit floats (2^-4) would land far outside it.
REL_L2_MAX = 0.05
# share of positions whose argmax agrees: random weights make near-ties, so
# a few positions may flip on rounding, not a tenth of them
TOP1_MIN = 0.90
DECODE_CHECK = 32      # trailing positions decoded through the paged cache
PREFILL_PAD = 23       # positions the prefill check leaves to bucket padding
SYSTEM_TOKENS = 960    # shared system prompt length (tokens)
NEW_TOKENS = 32        # greedy tokens per turn

_WORDS = ("tool", "invoke", "function", "cold", "start", "cache", "agent",
          "plan", "evaluate", "result", "latency", "region", "payload",
          "schema", "retry", "budget", "trace", "summary", "query", "log")


class Check(Exception):
    """A failed bring-up check."""


def emit(phase: str, **readings):
    print(json.dumps({"phase": phase, **readings}), flush=True)


def expect(cond: bool, what: str):
    if not cond:
        raise Check(what)


class CompileClock:
    """Seconds JAX spent compiling (or loading from the persistent cache)
    and cache hits, from JAX's monitoring events."""

    def __init__(self):
        import jax.monitoring as mon
        self.seconds = 0.0
        self.cache_hits = 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def peak_bytes(devices) -> list:
    return [(d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices]


def system_prompt(tokenizer, seed: int, n_tokens: int) -> str:
    rng = random.Random(seed)
    text = "System: you plan serverless tool calls for an agent.\n"
    i = 0
    while len(tokenizer.encode(text)) < n_tokens:
        text += f"tool_{i}: " + " ".join(rng.choice(_WORDS)
                                         for _ in range(8)) + "\n"
        i += 1
    return text


def _wave(handles, t0):
    """Wait for a wave of handles; return its readings."""
    outs = [h.result() for h in handles]
    wall = time.perf_counter() - t0
    for h in handles:
        expect(h.status().value == "completed",
               f"rid {h.request.rid} ended {h.status().value}")
        expect(1 <= h.request.output_tokens <= NEW_TOKENS,
               f"rid {h.request.rid}: {h.request.output_tokens} tokens")
    reqs = [h.request for h in handles]
    return outs, {
        "wall_s": wall,
        "first_token_s": [r.first_token_s for r in reqs],
        "decode_s": [r.decode_s for r in reqs],
        "output_tokens": [r.output_tokens for r in reqs],
        "prefix_hit_tokens": [r.prefix_hit_tokens for r in reqs],
    }


def serve_sessions(server, system: str, n_sessions: int):
    """Three waves on one server: session 0's first turn alone (cold, it
    fills the radix trie), the other sessions' first turns (system prompt
    served from shared pages), then every session's second turn (served
    from its retained tail). Returns (readings, session 0's two turns'
    requests)."""
    from repro.serving.scheduler import SamplingParams
    sp = SamplingParams(max_new_tokens=NEW_TOKENS)
    sessions = [server.open_session() for _ in range(n_sessions)]
    prompts = [system + f"\nUser {i}: which tool first for task {i}?\n"
               "Assistant:" for i in range(n_sessions)]
    readings, outs = {}, [None] * n_sessions
    t0 = time.perf_counter()
    first = sessions[0].submit(prompts[0], sp)
    o, readings["turn1_cold"] = _wave([first], t0)
    outs[0] = o[0]
    t0 = time.perf_counter()
    o, readings["turn1_shared"] = _wave(
        [s.submit(p, sp) for s, p in zip(sessions[1:], prompts[1:])], t0)
    outs[1:] = o
    turn2 = [p + out + f"\nUser {i}: and after that?\nAssistant:"
             for i, (p, out) in enumerate(zip(prompts, outs))]
    t0 = time.perf_counter()
    second = [s.submit(p, sp) for s, p in zip(sessions, turn2)]
    _, readings["turn2"] = _wave(second, t0)
    expect(min(readings["turn1_shared"]["prefix_hit_tokens"]) > 0,
           "first turns did not share the system prompt's pages")
    expect(min(readings["turn2"]["prefix_hit_tokens"]) > 0,
           "second turns reused nothing")
    return readings, (first.request, second[0].request)


def served_tokens_agreement(cfg, params, turns):
    """Session 0's served greedy tokens, both turns, vs the float32
    reference's argmax given the same served context (teacher forcing: one
    bf16 near-tie flip would otherwise send a free-running greedy decode
    elsewhere for every later token). The second turn's context holds the
    first turn's prompt and output, so one reference forward over it,
    right-padded (attention is causal), covers both turns. Returns
    readings."""
    import jax
    import jax.numpy as jnp
    from repro.models.reference import reference_logits
    r1, r2 = turns
    n1, n2 = len(r1._ids), len(r2._ids)
    seq = list(r2._ids) + list(r2.output_ids)
    expect(seq[:n1] == list(r1._ids) and
           seq[n1:n1 + len(r1.output_ids)] == list(r1.output_ids),
           "second turn's context does not extend the first turn's")
    length = -(-len(seq) // 256) * 256
    tokens = jnp.asarray([seq + [0] * (length - len(seq))], jnp.int32)
    top = jax.jit(lambda p, t: jnp.argmax(
        reference_logits(p, t, cfg)[..., :cfg.vocab_size], -1))(params,
                                                                tokens)
    top = [int(t) for t in top[0]]
    out = {}
    for name, n, ids in (("turn1", n1, r1.output_ids),
                         ("turn2", n2, r2.output_ids)):
        want = top[n - 1:n - 1 + len(ids)]
        out[name] = {"tokens": len(ids),
                     "agree": sum(a == b for a, b in zip(ids, want))
                     / len(ids)}
    return out


def _agreement(got, ref, vocab):
    import jax.numpy as jnp
    got, ref = got[..., :vocab], ref[..., :vocab]
    return {"positions": int(got.shape[1]),
            "finite": bool(jnp.all(jnp.isfinite(got))),
            "rel_l2": float(jnp.linalg.norm(got - ref) / jnp.linalg.norm(ref)),
            "top1_agree": float(jnp.mean(jnp.argmax(got, -1)
                                         == jnp.argmax(ref, -1))),
            "max_abs": float(jnp.max(jnp.abs(got - ref))),
            "ref_std": float(jnp.std(ref))}


def logits_agreement(cfg, params, tokens, bucket, pad_id,
                     n_decode=DECODE_CHECK, n_pad=PREFILL_PAD):
    """The model's kernel paths (bf16, the platform's kernels) vs the
    float32 reference forward on the same weights: prefill of the first
    ``S - n_pad`` tokens right-padded to the prefill ``bucket`` with
    ``length`` set, as the engine pads a prompt (the flash kernel), and
    paged extend of all but the last ``n_decode`` tokens followed by paged
    decode of those (the paged decode kernel). Returns {path: readings}."""
    import jax
    import jax.numpy as jnp
    from repro.models import Model
    from repro.models.reference import reference_logits
    from repro.serving import kvpool
    model = Model(cfg)
    S = tokens.shape[1]
    S0 = S - n_decode
    P = S - n_pad
    expect(P <= bucket, f"prefill bucket {bucket} < {P} tokens")
    ps = 16

    @jax.jit
    def prefill(p, t):
        padded = jnp.full((1, bucket), pad_id, jnp.int32).at[:, :P].set(
            t[:, :P])
        logits, _ = model.prefill(p, model.make_batch(padded),
                                  model.init_cache(1, bucket),
                                  length=jnp.int32(P))
        return logits[:, :P].astype(jnp.float32)

    @jax.jit
    def paged(p, t):
        pool = kvpool.init_paged_cache(cfg, 1 + S // ps, ps)
        bt = jnp.arange(1, 1 + S // ps, dtype=jnp.int32)[None]
        head, pool = model.extend(p, model.make_batch(t[:, :S0]), pool,
                                  jnp.int32(0), block_tables=bt)

        def step(pool, pos):
            batch = {"tokens": jax.lax.dynamic_slice_in_dim(t, pos, 1, 1),
                     "positions": jnp.reshape(pos, (1, 1))}
            logits, pool = model.decode_step(p, batch, pool,
                                             jnp.reshape(pos, (1,)),
                                             block_tables=bt)
            return pool, logits[0, 0]

        _, tail = jax.lax.scan(step, pool, jnp.arange(S0, S,
                                                      dtype=jnp.int32))
        return jnp.concatenate([head[0], tail], axis=0)[None].astype(
            jnp.float32)

    ref = jax.jit(lambda p, t: reference_logits(p, t, cfg))(params, tokens)
    out = {"prefill": _agreement(prefill(params, tokens), ref[:, :P],
                                 cfg.vocab_size)}
    got = paged(params, tokens)
    out["paged_extend"] = _agreement(got[:, :S0], ref[:, :S0],
                                     cfg.vocab_size)
    out["paged_decode"] = _agreement(got[:, S0:], ref[:, S0:],
                                     cfg.vocab_size)
    return out


def one_chip(cfg, seed: int, clock, *, num_slots=4, capacity=2048,
             n_sessions=4, system_tokens=SYSTEM_TOKENS, check_positions=256):
    """The one-chip phase: LLMServer sessions, then logits agreement."""
    import jax
    import jax.numpy as jnp
    from repro.models import Model
    from repro.serving.scheduler import EngineConfig
    from repro.serving.server import LLMServer

    t0 = time.perf_counter()
    params = Model(cfg).init(jax.random.PRNGKey(seed))
    jax.block_until_ready(params)
    init_s = time.perf_counter() - t0
    server = LLMServer(cfg, num_slots=num_slots, capacity=capacity,
                       params=params,
                       engine_cfg=EngineConfig(cache_mode="paged", spec_len=4))
    jax.block_until_ready(server.engine.cache)
    setup_s = time.perf_counter() - t0
    emit("setup", arch=cfg.name, layers=cfg.num_layers,
         d_model=cfg.d_model, params=cfg.param_count(),
         init_params_s=init_s, setup_s=setup_s,
         compile_s_so_far=clock.seconds)
    system = system_prompt(server.tokenizer, seed, system_tokens)
    c0 = clock.seconds
    readings, turns = serve_sessions(server, system, n_sessions)
    st = server.stats()
    expect(st["turn_prefix_hits"] == n_sessions,
           f"turn_prefix_hits {st['turn_prefix_hits']} != {n_sessions}")
    expect(st["live_requests"] == 0 and st["queued_requests"] == 0,
           "requests left in the server")
    emit("serve", **readings, compile_s=clock.seconds - c0,
         persistent_cache_hits=clock.cache_hits,
         prefill_compiles=st["prefill_compiles"],
         extend_compiles=st["extend_compiles"],
         prefix_hit_tokens=st["prefix_hit_tokens"],
         turn_prefix_hits=st["turn_prefix_hits"],
         host_syncs_per_token=st["host_syncs_per_token"],
         decode_tokens=st["decode_tokens"], verify_steps=st["verify_steps"],
         acceptance_rate=st["acceptance_rate"],
         peak_bytes_in_use=peak_bytes(jax.devices()[:1]))
    served = served_tokens_agreement(cfg, params, turns)
    emit("served_tokens", top1_min=TOP1_MIN, **served)
    for turn, a in served.items():
        expect(a["agree"] >= TOP1_MIN,
               f"{turn}: served tokens agree {a['agree']} < {TOP1_MIN}")
    ids = server.tokenizer.encode(system)[:check_positions]
    bucket = min(b for b in server.engine.buckets
                 if b >= check_positions - PREFILL_PAD)
    agree = logits_agreement(cfg, params, jnp.asarray([ids], jnp.int32),
                             bucket, server.tokenizer.pad_id)
    emit("logits", rel_l2_max=REL_L2_MAX, top1_min=TOP1_MIN, bucket=bucket,
         **agree)
    for path, a in agree.items():
        expect(a["finite"], f"{path}: non-finite logits")
        expect(a["rel_l2"] <= REL_L2_MAX,
               f"{path}: rel_l2 {a['rel_l2']} > {REL_L2_MAX}")
        expect(a["top1_agree"] >= TOP1_MIN,
               f"{path}: top1 agreement {a['top1_agree']} < {TOP1_MIN}")


def _greedy(server, prompts):
    """Greedy outputs of ``prompts`` submitted together to an LLMServer or
    a FleetServer."""
    from repro.serving.scheduler import SamplingParams
    sp = SamplingParams(max_new_tokens=NEW_TOKENS)
    handles = [server.submit(p, sp) for p in prompts]
    outs = [h.result() for h in handles]
    for h in handles:
        expect(h.status().value == "completed",
               f"rid {h.request.rid} ended {h.status().value}")
    return outs


def _devices_of(tree) -> set:
    import jax
    return {d for leaf in jax.tree.leaves(tree) for d in leaf.devices()}


def fleet_phase(cfg, seed: int, devices, *, capacity=1024, n_requests=8):
    """(a) one replica per device vs one replica serving the same
    requests."""
    import jax
    from repro.launch.mesh import make_host_mesh
    from repro.serving.fleet import FleetServer
    from repro.serving.scheduler import EngineConfig
    from repro.serving.server import LLMServer
    ecfg = EngineConfig(cache_mode="paged", spec_len=4)
    t0 = time.perf_counter()
    fleet = FleetServer(cfg, num_replicas=len(devices), num_slots=4,
                        capacity=capacity, seed=seed, engine_cfg=ecfg,
                        meshes=[make_host_mesh(d) for d in devices])
    try:
        for i, r in enumerate(fleet.replicas):
            eng = r.server.engine
            placed = _devices_of((eng.params, eng.cache))
            expect(placed == {devices[i]},
                   f"replica {i} holds arrays on {placed}")
        setup_s = time.perf_counter() - t0
        rng = random.Random(seed)
        prompts = [f"Task {i}: " + " ".join(rng.choice(_WORDS)
                                            for _ in range(40))
                   for i in range(n_requests)]
        t0 = time.perf_counter()
        got = _greedy(fleet, prompts)
        fleet_s = time.perf_counter() - t0
        stats = fleet.stats()
        per_replica = [p["decode_tokens"] if p else 0
                       for p in stats["per_replica"]]
        compile_s = [p["pump_longest_compile_s"] if p else None
                     for p in stats["per_replica"]]
        single = LLMServer(cfg, num_slots=4, capacity=capacity,
                           params=fleet.params, engine_cfg=ecfg)
        t0 = time.perf_counter()
        want = _greedy(single, prompts)
        single_s = time.perf_counter() - t0
    finally:
        fleet.close()
    same = [a == b for a, b in zip(got, want)]
    emit("fleet", replicas=len(devices), setup_s=setup_s,
         fleet_wall_s=fleet_s, one_replica_wall_s=single_s,
         decode_tokens_per_replica=per_replica, identical=same,
         pump_longest_compile_s=compile_s,
         pump_stall_notices=stats["pump_stall_notices"],
         peak_bytes_in_use=peak_bytes(devices))
    expect(all(n > 0 for n in per_replica), "a replica served nothing")
    expect(all(same), "fleet output differs from one replica")


def mesh_phase(cfg, cut_layers: int, seed: int, devices, *, capacity=1024):
    """(b) the full configuration on a (1, n) serve mesh, then a depth cut
    on one chip and on the mesh, compared string for string."""
    import dataclasses
    import jax
    from repro.launch.mesh import make_test_mesh
    from repro.serving.scheduler import EngineConfig
    from repro.serving.server import LLMServer
    mesh = make_test_mesh((1, len(devices)))
    ecfg = EngineConfig(cache_mode="paged", spec_len=4, mesh=mesh)
    rng = random.Random(seed + 1)
    prompts = [f"Query {i}: " + " ".join(rng.choice(_WORDS)
                                         for _ in range(30))
               for i in range(4)]
    t0 = time.perf_counter()
    full = LLMServer(cfg, num_slots=4, capacity=capacity, seed=seed,
                     engine_cfg=ecfg)
    jax.block_until_ready(full.engine.params)
    setup_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    _greedy(full, prompts)
    full_s = time.perf_counter() - t0
    emit("mesh_full", arch=cfg.name, layers=cfg.num_layers,
         mesh=dict(mesh.shape), setup_s=setup_s, wall_s=full_s,
         stats={k: full.stats()[k] for k in ("decode_tokens",
                                             "host_syncs_per_token",
                                             "prefill_compiles")},
         peak_bytes_in_use=peak_bytes(devices))
    full.close()
    del full
    gc.collect()

    cut = dataclasses.replace(cfg, num_layers=cut_layers)
    sharded = LLMServer(cut, num_slots=4, capacity=capacity, seed=seed,
                        engine_cfg=ecfg)
    got = _greedy(sharded, prompts)
    single = LLMServer(cut, num_slots=4, capacity=capacity,
                       params=sharded.engine.params,
                       engine_cfg=dataclasses.replace(ecfg, mesh=None))
    want = _greedy(single, prompts)
    same = [a == b for a, b in zip(got, want)]
    emit("mesh_cut", arch=cut.name, layers=cut_layers, identical=same)
    expect(all(same), "mesh output differs from one chip")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    try:
        import jax
        from repro.configs.registry import ARCHS
        from repro.launch.compile_cache import use_compile_cache
    except ImportError as e:
        print(f"chip_smoke: cannot import the serving stack: {e}",
              file=sys.stderr)
        return 2
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform "
              f"{devices[0].platform!r})", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX sees {len(devices)}", file=sys.stderr)
        return 2
    cache_dir = use_compile_cache()
    clock = CompileClock()
    emit("start", chips=args.chips, seed=args.seed, jax=jax.__version__,
         compile_cache=cache_dir)
    t0 = time.perf_counter()
    try:
        if args.chips == 1:
            one_chip(ARCHS["qwen2.5-3b"], args.seed, clock)
        else:
            fleet_phase(ARCHS["qwen2.5-3b"], args.seed, devices[:4])
            gc.collect()
            emit("between", bytes_in_use=[
                (d.memory_stats() or {}).get("bytes_in_use")
                for d in devices[:4]])
            mesh_phase(ARCHS["mistral-nemo-12b"], 8, args.seed, devices[:4])
    except Check as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    emit("done", total_s=time.perf_counter() - t0, compile_s=clock.seconds,
         persistent_cache_hits=clock.cache_hits)
    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
