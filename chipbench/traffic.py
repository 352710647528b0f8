"""The one traffic generator: turns a traffic mix (``traffic/<mix>.json``)
and a seed into a plan (``plan.py``) that the client sends as it is.

A mix is data only.  Its ``kind`` names a module ``traffic/<kind>.py``,
found by that name, which reads the mix's other parameters; each kind's
docstring lists them.  Parameters every kind shares, read here:

  prompt, output  {"dist": "uniform" | "lognormal", "min", "max", and for
                  lognormal "median", "sigma"}: the new prompt tokens a
                  request carries and the tokens it asks for;
  documents       optional {"count", "min", "max"}: shared documents,
                  prefilled during set-up, that requests start with;
  n               optional: completions per request (one prefill, forks);
  vocab_share     optional: token ids are drawn from this share of the
                  vocabulary (a seeded subset), to skew routing.

Every seed gets the same sizes: a kind draws lengths and gaps in groups,
each group the distribution's quantiles at evenly spaced points, and the
seed only shuffles them within the group and draws the token ids.  So a
group of requests is the same work under every seed, in another order.
"""
from __future__ import annotations

import importlib.util
import json
import os
from statistics import NormalDist

import numpy as np

from . import plan as planlib

HERE = os.path.dirname(os.path.abspath(__file__))
KINDS = os.path.join(HERE, "traffic")


def load(name: str) -> dict:
    with open(os.path.join(KINDS, f"{name}.json")) as f:
        return json.load(f)


def kind(name: str):
    """The module of a traffic kind, ``traffic/<name>.py``."""
    path = os.path.join(KINDS, f"{name}.py")
    if not os.path.exists(path):
        raise ValueError(f"unknown traffic kind {name!r}")
    spec = importlib.util.spec_from_file_location(f"chipbench_traffic_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def quantiles(dist: dict, n: int) -> np.ndarray:
    """``n`` integer lengths at the quantiles (i + 0.5) / n of ``dist``."""
    p = (np.arange(n) + 0.5) / n
    lo, hi = dist["min"], dist["max"]
    if dist["dist"] == "uniform":
        v = lo + p * (hi - lo)
    elif dist["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in p])
        v = dist["median"] * np.exp(dist["sigma"] * z)
    else:
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    return np.clip(np.rint(v), lo, hi).astype(np.int64)


class Draw:
    """What a kind draws from the seed: grouped lengths and gaps, token
    ids and the shared documents."""

    def __init__(self, mix: dict, vocab: int, seed: int):
        self.mix = mix
        self.rng = np.random.default_rng([seed, 1])
        share = mix.get("vocab_share", 1.0)
        self.ids = (self.rng.permutation(vocab)[:max(1, int(share * vocab))]
                    if share < 1.0 else None)
        self.vocab = vocab

    def grouped(self, values, n: int) -> np.ndarray:
        """``n`` values: ``values`` (one group) shuffled, group by group."""
        m = len(values)
        if n % m:
            raise ValueError(f"a group of {m} does not divide {n} requests")
        return np.concatenate([self.rng.permutation(values)
                               for _ in range(n // m)])

    def lengths(self, dist: dict, n: int, group: int) -> list:
        return [int(v) for v in self.grouped(quantiles(dist, group), n)]

    def gaps(self, rate: float, n: int, group: int) -> list:
        """Poisson inter-arrival gaps at ``rate`` per second."""
        p = (np.arange(group) + 0.5) / group
        return [float(v) for v in self.grouped(-np.log1p(-p) / rate, n)]

    def tokens(self, k: int) -> list:
        if self.ids is None:
            return self.rng.integers(0, self.vocab, k).tolist()
        return self.ids[self.rng.integers(0, len(self.ids), k)].tolist()

    def documents(self) -> list:
        d = self.mix.get("documents")
        if not d:
            return []
        sizes = quantiles({"dist": "uniform", "min": d["min"],
                           "max": d["max"]}, d["count"])
        return [self.tokens(int(s)) for s in self.rng.permutation(sizes)]

    def document_of(self, n: int, group: int, count: int) -> list:
        """Which document each of ``n`` requests asks about: each group
        spreads its requests evenly over the documents."""
        if not count:
            return [-1] * n
        return [int(v) for v in self.grouped(np.arange(group) % count, n)]


def make(mix: dict, vocab: int, seed: int) -> dict:
    """The plan of a mix under a seed."""
    draw = Draw(mix, vocab, seed)
    plan = kind(mix["kind"]).make(mix, draw)
    for r in plan["requests"]:
        r["n"] = int(mix.get("n", 1))
    planlib.validate(plan)
    return plan


def smoke(mix: dict) -> dict:
    """A mix cut to tiny lengths and counts for CPU rehearsals."""
    mix = json.loads(json.dumps(mix))
    if "documents" in mix:
        mix["documents"].update({"count": 2, "min": 40, "max": 60})
    mix["prompt"].update({"min": 4, "max": 12, "median": 8})
    mix["output"].update({"min": 3, "max": 6, "median": 4})
    return kind(mix["kind"]).smoke(mix)
