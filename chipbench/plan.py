"""The plan: the one form in which any traffic kind hands its requests to
the client, the check and the harness.  Standard library only, so the
load client (which never imports JAX or numpy) reads it too.

A plan is ``{"documents": [[ids], ...], "requests": [request, ...]}``.
A request is::

    {"id":         its index in ``requests``,
     "doc":        index of a shared document its prompt starts with, or -1,
     "prompt":     [ids] it sends after that document (and after its
                   context, below),
     "max_tokens": tokens it asks for (greedy, to the end),
     "n":          completions it asks for (one prefill, n forks),
     "after":      id of the request it waits for, or None,
     "context":    true: its prompt starts with that request's whole
                   prompt and its first completion (a later turn),
     "delay":      seconds after the window opens (no ``after``) or after
                   the request it waits for finished, that it is due}

A request is timed from when it is due.  One that is due at or after the
window's close, or that waits for a request that failed or was never
sent, is not sent.  Shared documents are sent once during set-up, so that
their prefill fills the prefix cache.
"""
from __future__ import annotations

FIELDS = ("id", "doc", "prompt", "max_tokens", "n", "after", "context",
          "delay")


def request(id, prompt, max_tokens, *, doc=-1, n=1, after=None,
            context=False, delay=0.0) -> dict:
    if context and after is None:
        raise ValueError("a request with context has to wait for one")
    return {"id": int(id), "doc": int(doc), "prompt": [int(t) for t in prompt],
            "max_tokens": int(max_tokens), "n": int(n),
            "after": None if after is None else int(after),
            "context": bool(context), "delay": float(delay)}


def validate(plan: dict) -> None:
    """Raises where a plan is not in the form above."""
    reqs = plan["requests"]
    for i, r in enumerate(reqs):
        if tuple(sorted(r)) != tuple(sorted(FIELDS)):
            raise ValueError(f"request {i} has keys {sorted(r)}")
        if r["id"] != i:
            raise ValueError(f"request {i} has id {r['id']}")
        if not (r["prompt"] or r["doc"] >= 0 or r["context"]):
            raise ValueError(f"request {i} sends an empty prompt")
        if r["max_tokens"] < 1 or r["n"] < 1 or r["delay"] < 0:
            raise ValueError(f"request {i}: {r}")
        if r["after"] is not None and not 0 <= r["after"] < i:
            raise ValueError(f"request {i} waits for {r['after']}")
        if not -1 <= r["doc"] < len(plan["documents"]):
            raise ValueError(f"request {i} names document {r['doc']}")


def prompt_ids(plan: dict, req: dict, served: dict) -> list:
    """The token ids a request sends: its context (the prompt and first
    completion of the request it follows, ``served[id]``), its document,
    then its own tokens."""
    head = []
    if req["context"]:
        prev = plan["requests"][req["after"]]
        head = prompt_ids(plan, prev, served) + list(served[prev["id"]])
    if req["doc"] >= 0:
        head = head + list(plan["documents"][req["doc"]])
    return head + list(req["prompt"])


def longest(plan: dict, documents: bool = True) -> int:
    """Tokens of the longest request, prompt and completion together
    (without ``documents``: leaving out the shared documents' tokens)."""
    size = {}
    for r in plan["requests"]:
        n = len(r["prompt"]) + r["max_tokens"]
        if r["doc"] >= 0 and documents:
            n += len(plan["documents"][r["doc"]])
        if r["context"]:
            n += size[r["after"]]
        size[r["id"]] = n
    return max(size.values())


def shortest_prompt(plan: dict) -> int:
    """Tokens of the shortest prompt a request sends."""
    return min(len(r["prompt"]) + (len(plan["documents"][r["doc"]])
                                   if r["doc"] >= 0 else 0)
               for r in plan["requests"])
