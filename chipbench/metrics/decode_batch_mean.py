"""Rows per decode step in the window (engine counters: decode tokens
over decode dispatches)."""


def read(run):
    c = run["counters"]
    if not c["decode_steps"]:
        return None
    return c["decode_tokens"] / c["decode_steps"]
