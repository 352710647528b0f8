"""Open loop: ``requests`` requests due on a Poisson schedule at ``rate``
per second, sent whether or not earlier ones finished, and timed from
when they were due, so a server that falls behind pays for it.

Parameters besides the shared ones (``traffic.py``): ``rate``,
``requests``, ``group`` (it divides ``requests``), and optional
``bursts`` {"on_s", "off_s"}: arrivals come only in the on periods, at
``rate`` x (on_s + off_s) / on_s, so the mean rate stays ``rate``.
"""
from chipbench import plan


def make(mix: dict, draw) -> dict:
    n, g = mix["requests"], mix["group"]
    plens = draw.lengths(mix["prompt"], n, g)
    outs = draw.lengths(mix["output"], n, g)
    docs = draw.documents()
    doc_of = draw.document_of(n, g, len(docs))
    bursts = mix.get("bursts")
    rate = mix["rate"]
    if bursts:
        on, off = bursts["on_s"], bursts["off_s"]
        rate = rate * (on + off) / on
    gaps = draw.gaps(rate, n, g)
    t, reqs = 0.0, []
    for i in range(n):
        due = t
        if bursts:
            due = (t // on) * (on + off) + t % on
        reqs.append(plan.request(i, draw.tokens(plens[i]), outs[i],
                                 doc=doc_of[i], delay=due))
        t += gaps[i]
    return {"documents": docs, "requests": reqs}


def smoke(mix: dict) -> dict:
    mix.update({"requests": 16, "group": 4, "rate": 8.0})
    if "bursts" in mix:
        mix["bursts"] = {"on_s": 0.5, "off_s": 0.5}
    return mix
