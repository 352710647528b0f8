"""Multi-turn sessions: ``sessions`` conversations run side by side.  Each
turn is sent ``think_s`` after the answer to the last one and carries the
whole conversation so far (every earlier prompt and first completion)
and then its own new tokens, so the prefix cache holds all but the new
part.  After ``turns`` turns a session starts a new conversation, up to
``conversations`` of them.  A conversation's first turn starts with a
shared document where the mix has them.

Parameters besides the shared ones (``traffic.py``): ``sessions``,
``turns``, ``conversations``, ``think_s``.  Request i is session
i mod sessions's turn i div sessions; each such turn is one group.
"""
from chipbench import plan


def make(mix: dict, draw) -> dict:
    s, turns = mix["sessions"], mix["turns"]
    n = s * turns * mix["conversations"]
    plens = draw.lengths(mix["prompt"], n, s)
    outs = draw.lengths(mix["output"], n, s)
    docs = draw.documents()
    doc_of = draw.document_of(n, s, len(docs))
    reqs = []
    for i in range(n):
        k = i // s
        first = k % turns == 0
        reqs.append(plan.request(
            i, draw.tokens(plens[i]), outs[i],
            doc=doc_of[i] if first else -1,
            after=i - s if k else None, context=not first,
            delay=mix["think_s"] if k else 0.0))
    return {"documents": docs, "requests": reqs}


def smoke(mix: dict) -> dict:
    mix.update({"sessions": 2, "turns": 3, "conversations": 3,
                "think_s": 0.05})
    return mix
