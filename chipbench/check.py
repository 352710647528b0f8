"""Whether what the window served is correct.

After the window has closed and the engine is freed, a sample of the
requests the server finished, drawn from the seed and holding the longest
of them, is run through the plain reference (``reference.py``) with the
tokens the client received, and each served token is judged by its gap:
how far its reference logit lies below the reference's best at that
position.  Greedy serving in bfloat16 puts that gap near zero; a token
altered or a cache written wrong puts it far above the limit.  The number
compared is the widest gap over the sample; its limit is the cell's
``check.max_logit_gap`` (set from the readings in PERF.md).

Besides, every streamed completion must equal the server's final
``done`` event and run to its ``max_tokens``.
"""
from __future__ import annotations

import time

import numpy as np

from . import plan as planlib
from .reference import Reference


def served(result: dict) -> tuple:
    """(attempted, failed, finished records) of the window: every request
    due in it counts, and one that did not finish within the grace, or
    ended in an error, failed."""
    recs = [r for r in result["records"] if r["due"] < result["t_end"]]
    done = [r for r in recs if r["done"] is not None and "error" not in r]
    return len(recs), len(recs) - len(done), done


def _served_tokens(done: list) -> dict:
    return {r["id"]: r["choices"][0]["tokens"] for r in done}


def sample(cell: dict, plan: dict, done: list, seed: int) -> list:
    """(record, choice) pairs to check: the longest, then others in an
    order drawn from the seed until ``sample_tokens`` served tokens; with
    ``same_document`` only requests about the longest one's document."""
    reqs = plan["requests"]
    toks = _served_tokens(done)
    pairs = [(r, c) for r in done for c in range(len(r["choices"]))]
    size = lambda p: len(planlib.prompt_ids(plan, reqs[p[0]["id"]], toks)) \
        + len(p[0]["choices"][p[1]]["tokens"])
    first = max(pairs, key=size)
    pool = [p for p in pairs if p is not first]
    if cell["check"]["same_document"]:
        doc = reqs[first[0]["id"]]["doc"]
        pool = [p for p in pool if reqs[p[0]["id"]]["doc"] == doc]
    rng = np.random.default_rng([seed, 3])
    out, n = [first], len(first[0]["choices"][first[1]]["tokens"])
    for i in rng.permutation(len(pool)):
        if n >= cell["check"]["sample_tokens"]:
            break
        out.append(pool[i])
        n += len(pool[i][0]["choices"][pool[i][1]]["tokens"])
    return out


def reference_logits(ref: Reference, plan: dict, pairs: list, toks: dict):
    """Per (record, choice), the reference's logits at the positions that
    chose each served token, given the prompt and the tokens served
    before.  A shared document is computed once."""
    reqs = plan["requests"]
    states = {}
    out = []
    for r, c in pairs:
        q = reqs[r["id"]]
        served_ = np.asarray(r["choices"][c]["tokens"], np.int32)
        ids = np.asarray(planlib.prompt_ids(plan, q, toks), np.int32)
        state, skip = None, 0
        if q["doc"] >= 0 and not q["context"]:
            if q["doc"] not in states:
                states[q["doc"]] = ref.forward(plan["documents"][q["doc"]])[0]
            state, skip = states[q["doc"]], len(plan["documents"][q["doc"]])
        rest = ids[skip:]
        _, hid = ref.forward(np.concatenate([rest, served_[:-1]]), state)
        out.append(ref.logits(hid, len(rest) - 1, len(served_)))
    return out


def gaps(logits: np.ndarray, tokens) -> np.ndarray:
    """Reference best minus the reference logit of each chosen token."""
    idx = np.arange(len(tokens))
    return logits.max(-1) - logits[idx, np.asarray(tokens)]


def check(cell: dict, spec: dict, plan: dict, result: dict, params,
          seed: int, *, log=print, control: bool = False) -> dict:
    """The verdict: {"correct", "attempted", "failed", "numbers":
    {name: (value, limit)}, ...}.  ``control`` also reads, at the same
    positions, the gap of the token the float8 control puts first."""
    attempted, failed, done = served(result)
    reqs = plan["requests"]
    bad_stream = [r["id"] for r in done for c in r["choices"]
                  if c["tokens"] != c.get("output")
                  or len(c["tokens"]) != reqs[r["id"]]["max_tokens"]]
    out = {"attempted": attempted, "failed": failed,
           "stream_mismatch": len(bad_stream), "numbers": {}}
    if not done:
        out["correct"] = False
        return out
    toks = _served_tokens(done)
    pairs = sample(cell, plan, done, seed)
    t = time.perf_counter()
    ref = Reference(spec, params)
    l32 = reference_logits(ref, plan, pairs, toks)
    g = [gaps(lg, r["choices"][c]["tokens"]) for lg, (r, c) in zip(l32, pairs)]
    widest = float(max(x.max() for x in g))
    out["sampled_requests"] = len(pairs)
    out["sampled_tokens"] = int(sum(len(x) for x in g))
    out["reference_s"] = time.perf_counter() - t
    log(f"reference: {len(pairs)} completions, {out['sampled_tokens']} "
        f"served tokens, {out['reference_s']:.1f} s")
    limit = cell["check"]["max_logit_gap"]
    out["numbers"]["max_logit_gap"] = (widest, limit)
    out["numbers"]["stream_mismatch"] = (len(bad_stream), 0)
    if control:
        l8 = reference_logits(Reference(spec, params, quant="fp8"), plan,
                              pairs, toks)
        out["control_max_logit_gap"] = float(max(
            gaps(a, b.argmax(-1)).max() for a, b in zip(l32, l8)))
    out["correct"] = (limit is not None and widest <= limit
                      and not bad_stream and failed == 0)
    for name, (v, lim) in out["numbers"].items():
        log(f"check: {name} {v!r} limit {lim!r}")
    return out
