"""KV cache: share of the window's admitted prompt tokens served from the
prefix cache (radix-shared pages and retained session tails), in %."""


def read(run):
    prompt = run.delta("prompt_tokens")
    if prompt <= 0 or "prefix_hit_tokens" not in run.stats["end"]:
        return None
    return 100.0 * run.delta("prefix_hit_tokens") / prompt
