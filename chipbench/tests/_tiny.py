"""A tiny workload for running the harness on the CPU: the qwen2.5-3b
architecture at toy sizes, with each traffic kind at toy lengths."""
import copy
import pathlib

HERE = pathlib.Path(__file__).resolve().parents[1]

SPEC = {
    "source": "tiny", "registry": "qwen2.5-3b", "reference": "dense_gqa",
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "rms_norm_eps": 1e-06, "rope_theta": 1000000.0,
    "tie_word_embeddings": True, "torch_dtype": "bfloat16",
    "vocab_size": 512, "attention_bias": True,
    "layout": {"vocab_pad_multiple": 128},
}

ENGINE = {"num_slots": 4, "capacity": 512, "page_size": 16,
          "num_pages": 400, "prefill_buckets": [32, 64, 128],
          "spec_len": 0}

MIXES = {
    "agent": {
        "kind": "agent", "apps": 2,
        "roles": ["planner", "actor", "evaluator"], "system_tokens": 96,
        "user_requests": {"dist": "uniform", "min": 1, "max": 2},
        "iterations": {"dist": "uniform", "min": 1, "max": 2},
        "suffix_tokens": {"dist": "uniform", "min": 8, "max": 24},
        "output_tokens": {"dist": "uniform", "min": 4, "max": 16},
        "tool_output_to": "evaluator",
        "tool_output_tokens": {"dist": "lognormal", "median": 16,
                               "sigma": 0.8, "min": 8, "max": 40},
        "tool_seconds": {"dist": "exponential", "mean": 0.05}},
    "open": {
        "kind": "open",
        "classes": [
            {"name": "short", "share": 0.8,
             "prompt_tokens": {"dist": "lognormal", "median": 24,
                               "sigma": 0.5, "min": 8, "max": 64},
             "output_tokens": {"dist": "uniform", "min": 4, "max": 12}},
            {"name": "long", "share": 0.2,
             "prompt_tokens": {"dist": "lognormal", "median": 200,
                               "sigma": 0.4, "min": 130, "max": 300},
             "output_tokens": {"dist": "uniform", "min": 8, "max": 16}}]},
}

LOADS = {"agent": {"rate_per_s": 2.0, "lead_in_s": 0.5},
         "open": {"rate_per_s": 6.0, "lead_in_s": 0.5},
         "closed": {"clients": 3, "conversations_per_client": 3,
                    "lead_in_s": 0.5}}


def workload(kind="agent", *, max_logit_gap=1.0, load=None, **layout):
    """A resolved workload (``manifest.workload``'s shape) of traffic kind
    ``kind`` at toy sizes, under its open-loop load or the one ``load``
    names (``"closed"``); ``layout`` adds ``replicas`` or ``mesh`` to the
    cell."""
    cell = {"engine": dict(ENGINE), "load": dict(LOADS[load or kind]),
            "check": {"served_tokens": 120, "max_logit_gap": max_logit_gap},
            **layout}
    chips = layout.get("replicas", 1)
    if "mesh" in layout:
        chips = layout["mesh"][0] * layout["mesh"][1]
    return {
        "entry": {"name": f"tiny.{kind}", "config": "tiny",
                  "traffic": kind, "chips": chips, "why": "test"},
        "spec": copy.deepcopy(SPEC), "cell": cell,
        "mix": copy.deepcopy(MIXES[kind]),
        "reference": HERE / "references" / "dense_gqa.py",
        "bench": {"end_to_end": [
            {"name": n, "unit": u, "better": "lower", "bound": 0.1,
             "source": "host_clock"}
            for n, u in (("ttft_p95_s", "s"), ("tpot_p95_ms", "ms"),
                         ("output_tok_s", "tokens/s"), ("setup_s", "s"))],
            "per_layer": []},
    }
