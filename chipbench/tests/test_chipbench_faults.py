"""The check catches a broken timed path: the rest of a run is driven at
smoke widths on the CPU with a fault planted in the program's decode step,
and ``correct`` must come out false."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [HERE, ROOT, os.path.join(ROOT, "src")]

from test_chipbench_rehearsal import rehearse  # noqa: E402


def state_unchanged(engine):
    """Every decode step hands back the pool it was given: no latent of a
    generated token is ever written."""
    import jax
    import jax.numpy as jnp
    make = engine._sample_step

    def broken(scheme):
        fn = make(scheme)

        def step(params, tok, pool, *rest):
            kept = jax.tree.map(jnp.copy, pool)
            out, _ = fn(params, tok, pool, *rest)
            return out, kept
        return step
    engine._sample_step = broken


def token_altered(engine):
    """Every token the decode step produces is replaced by its neighbour."""
    vocab = engine.cfg.vocab
    make = engine._sample_step

    def broken(scheme):
        fn = make(scheme)

        def step(*args):
            out, pool = fn(*args)
            return (out + 1) % vocab, pool
        return step
    engine._sample_step = broken


@pytest.mark.parametrize("fault", [state_unchanged, token_altered],
                         ids=["state_unchanged", "token_altered"])
def test_fault_is_not_correct(fault, monkeypatch):
    rec, line = rehearse("v2-docqa-closed16", monkeypatch, fault=fault)
    assert line["correct"] is False
    gap, limit = line["check"]["max_logit_gap"].values()
    assert gap > limit
