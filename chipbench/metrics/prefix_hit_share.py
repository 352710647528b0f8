"""Share of the prompt tokens admitted in the window that the prefix
cache served (engine counters: prefix hit tokens over prompt tokens)."""


def read(run):
    c = run["counters"]
    if not c["prompt_tokens"]:
        return None
    return 100.0 * c["prefix_hit_tokens"] / c["prompt_tokens"]
