"""The traffic generator and the plan form: every kind gives each seed the
same sizes in another order, and plans say what the client sends."""
import collections
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from chipbench import plan as planlib  # noqa: E402
from chipbench import traffic  # noqa: E402

SEEDS = (2 ** 31 + 11, 2 ** 32 + 3)
VOCAB = 102400


def mixes():
    out = {}
    for d in (os.path.join(ROOT, "chipbench", "traffic"),
              os.path.join(HERE, "data")):
        for f in sorted(os.listdir(d)):
            if f.endswith(".json") and (d.endswith("traffic")
                                        or f.startswith("mix-")):
                with open(os.path.join(d, f)) as fh:
                    out[f[:-5]] = json.load(fh)
    return out


MIXES = mixes()


def sizes(plan):
    """Each size as a multiset: a seed pairs them in another order."""
    reqs = plan["requests"]
    return [collections.Counter(f(r) for r in reqs) for f in (
        lambda r: len(r["prompt"]), lambda r: r["max_tokens"],
        lambda r: (r["doc"] >= 0, r["context"], r["after"]))]


@pytest.mark.parametrize("name", sorted(MIXES))
def test_every_seed_gets_the_same_work(name):
    a, b = (traffic.make(MIXES[name], VOCAB, s) for s in SEEDS)
    planlib.validate(a)
    assert sizes(a) == sizes(b)
    assert sorted(map(len, a["documents"])) == sorted(map(len, b["documents"]))
    assert a["requests"][0]["prompt"] != b["requests"][0]["prompt"]
    assert traffic.make(MIXES[name], VOCAB, SEEDS[0]) == a
    ids = {t for r in a["requests"] for t in r["prompt"]}
    assert ids <= set(range(VOCAB))
    share = MIXES[name].get("vocab_share")
    if share:
        assert len(ids) <= share * VOCAB


def test_closed_loop_callers_are_chains():
    mix = dict(MIXES["docqa-closed16"], per_client=3)
    p = traffic.make(mix, VOCAB, SEEDS[0])
    c = mix["clients"]
    assert [r["after"] for r in p["requests"]] == (
        [None] * c + list(range(2 * c)))
    assert not any(r["context"] for r in p["requests"])
    # the callers start evenly over the ramp, then follow their answers
    assert [r["delay"] for r in p["requests"]] == (
        [i * mix["ramp_s"] / c for i in range(c)] + [0.0] * 2 * c)
    # each turn of the callers asks about every document equally
    turn = collections.Counter(r["doc"] for r in p["requests"][:c])
    assert set(turn.values()) == {c // mix["documents"]["count"]}


def test_open_loop_bursts_keep_arrivals_in_the_on_periods():
    mix = MIXES["mix-open-bursts-n2"]
    p = traffic.make(mix, VOCAB, SEEDS[0])
    on, off = mix["bursts"]["on_s"], mix["bursts"]["off_s"]
    due = [r["delay"] for r in p["requests"]]
    assert due == sorted(due)
    assert all(d % (on + off) < on for d in due)
    assert all(r["n"] == 2 and r["after"] is None for r in p["requests"])
    rate = len(due) / (due[-1] + off)
    assert 0.5 * mix["rate"] < rate < 2 * mix["rate"]
    # each group of arrivals spans the same time under every seed
    other = traffic.make(mix, VOCAB, SEEDS[1])["requests"]
    g = mix["group"]
    assert [round(d, 9) for d in due[::g]] == [
        round(r["delay"], 9) for r in other[::g]]


def test_sessions_carry_their_conversation():
    mix = MIXES["mix-sessions-docs"]
    p = traffic.make(mix, VOCAB, SEEDS[0])
    s, turns = mix["sessions"], mix["turns"]
    reqs = p["requests"]
    served = {r["id"]: [7] * r["max_tokens"] for r in reqs}
    second = reqs[s]
    assert second["context"] and second["after"] == 0 and second["doc"] == -1
    want = (p["documents"][reqs[0]["doc"]] + reqs[0]["prompt"]
            + served[0] + second["prompt"])
    assert planlib.prompt_ids(p, second, served) == want
    # a new conversation starts after ``turns`` turns, without context
    fresh = reqs[s * turns]
    assert fresh["after"] == s * (turns - 1) and not fresh["context"]
    assert fresh["doc"] >= 0
    longest = max(len(planlib.prompt_ids(p, r, served)) + r["max_tokens"]
                  for r in reqs)
    assert planlib.longest(p) == longest


def test_a_plan_out_of_form_is_refused():
    p = {"documents": [], "requests": [planlib.request(0, [1, 2], 4)]}
    planlib.validate(p)
    for bad in ({"max_tokens": 0}, {"after": 0}, {"id": 3}, {"doc": 1},
                {"extra": 1}):
        q = {"documents": [], "requests": [dict(p["requests"][0], **bad)]}
        with pytest.raises(ValueError):
            planlib.validate(q)
    with pytest.raises(ValueError):
        planlib.request(1, [1], 2, context=True)
    with pytest.raises(ValueError, match="unknown traffic kind"):
        traffic.make({"kind": "nonesuch"}, VOCAB, 1)
