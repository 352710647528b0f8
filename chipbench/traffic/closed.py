"""Closed loop: ``clients`` callers, each sending its next request as soon
as its last one finished, ``per_client`` requests each at most.  The
callers start one after another, evenly over the window's first
``ramp_s`` seconds: callers that all start at once would measure their
own synchronised start, a burst that a server running for hours does not
see.

Parameters besides the shared ones (``traffic.py``): ``clients``,
``per_client``, ``ramp_s``.  Request i is caller i mod clients's turn
i div clients; each turn of the callers is one group, so the requests in
flight at any time have the same sizes under every seed.
"""
from chipbench import plan


def make(mix: dict, draw) -> dict:
    c = mix["clients"]
    n = c * mix["per_client"]
    plens = draw.lengths(mix["prompt"], n, c)
    outs = draw.lengths(mix["output"], n, c)
    docs = draw.documents()
    doc_of = draw.document_of(n, c, len(docs))
    return {"documents": docs, "requests": [
        plan.request(i, draw.tokens(plens[i]), outs[i], doc=doc_of[i],
                     after=i - c if i >= c else None,
                     delay=0.0 if i >= c else i * mix["ramp_s"] / c)
        for i in range(n)]}


def smoke(mix: dict) -> dict:
    mix.update({"clients": 3, "per_client": 16, "ramp_s": 0.3})
    return mix
