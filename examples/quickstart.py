"""Quickstart: deploy a FAME stack and run one multi-turn agentic session.

    PYTHONPATH=src python examples/quickstart.py [--config M+C] [--app RS]

``--llm oracle`` (default) drives the workflow with the deterministic
scripted oracle; ``--llm jax`` hosts the agents on the real serving stack —
an ``repro.serving.server.LLMServer`` session per agent role (tokenize →
prefill → decode on a reduced architecture; untrained weights, so workflow
outcomes DNF — the point is the serving path).
"""
import argparse

from repro.apps import log_analytics as la
from repro.apps import research_summary as rs
from repro.core.config import CONFIGS
from repro.core.runtime import FameRuntime


def build_jax_backend(arch: str):
    """FAME agents on the session-oriented serving API (LLMServer)."""
    from repro.configs.registry import ARCHS
    from repro.core.llm import JaxLLM, rates_for_arch
    from repro.serving.server import EngineConfig, LLMServer

    cfg = ARCHS[arch].reduced(dtype="float32", param_dtype="float32",
                              vocab_size=512)
    server = LLMServer(cfg, num_slots=4, capacity=512,
                       engine_cfg=EngineConfig(cache_mode="paged"))
    return server, JaxLLM(server, max_new_tokens=8,
                          latency=rates_for_arch(arch))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", default="M+C", choices=sorted(CONFIGS))
    ap.add_argument("--app", default="RS", choices=["RS", "LA"])
    ap.add_argument("--fusion", default="singleton",
                    choices=["singleton", "consolidated"])
    ap.add_argument("--llm", default="oracle", choices=["oracle", "jax"],
                    help="oracle: scripted deterministic LLM; jax: the real "
                         "serving stack behind an LLMServer session per role")
    ap.add_argument("--arch", default="qwen2.5-3b",
                    help="architecture for --llm jax")
    args = ap.parse_args()

    app = {"RS": rs, "LA": la}[args.app]
    rt = FameRuntime(config=CONFIGS[args.config], fusion_mode=args.fusion)
    server = None
    if args.llm == "jax":
        server, backend = build_jax_backend(args.arch)
        for role in app.build_oracles():
            rt.set_llm(role, backend)
    else:
        for role, oracle in app.build_oracles().items():
            rt.set_llm(role, oracle)
    rt.deploy_mcp(app.APP.servers, app.APP.sources)

    print(f"=== FAME quickstart: app={args.app} config={args.config} "
          f"fusion={args.fusion} llm={args.llm} ===")
    print(f"deployed functions: {sorted(rt.platform.functions)}")
    for w in rt._wrapped:
        print(f"--- generated wrapper for MCP server {w.server.name!r} ---")
        print(w.wrapper_source.splitlines()[2])

    inp = app.APP.inputs[0]
    res = rt.run_session(f"quickstart-{inp}", app.APP.queries(inp))
    for qi, (q, resp, status) in enumerate(
            zip(app.APP.queries(inp), res.responses, res.statuses)):
        tr = res.traces[qi]
        i_tok, o_tok = tr.llm_tokens()
        print(f"\nQ{qi + 1}: {q[:78]}")
        print(f"  status={status} in_tokens={i_tok} out_tokens={o_tok} "
              f"tool_calls={tr.count('mcp')}")
        print(f"  answer: {resp[:120]}...")
    print(f"\ncache hits: {rt.cache.hits}  "
          f"memory entries: {len(rt.memory.recall(f'quickstart-{inp}'))}")
    print("cost breakdown (cents):",
          {k: round(sum(t.cost_breakdown()[k] for t in res.traces), 3)
           for k in ("llm_cents", "faas_agent_cents", "faas_mcp_cents")})
    if server is not None:
        st = server.stats()
        print("serving stats:",
              {k: st[k] for k in ("sessions_opened", "session_turns",
                                  "turn_prefix_hits", "decode_tokens",
                                  "host_syncs_per_token",
                                  "active_slots_per_step") if k in st})


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    main()
