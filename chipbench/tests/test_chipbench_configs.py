"""Configuration files: every source key accounted for, widths kept, and
the chip's share of the experts tied to the uncut layer."""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from chipbench import costs, model, weights  # noqa: E402

NAMES = ["deepseek-v2-ep8", "deepseek-v3-ep32"]


@pytest.mark.parametrize("name", NAMES)
def test_every_key_is_mapped_reduced_or_a_departure(name):
    spec = model.load(name)
    keys = set(model.source_keys(spec))
    mapped = (set(model.FIELDS) | set(model.CHECKED) | set(model.HONOURED)
              | {model.EXPERTS, "max_position_embeddings"})
    assert keys <= mapped | set(spec["reduced"]) | set(spec["departures"])
    for k, r in spec["reduced"].items():
        assert set(r) == {"published", "here", "why"}
        assert spec[k] == r["here"] != r["published"]
    bad = dict(spec, unknown_key=1)
    with pytest.raises(ValueError, match="unknown_key"):
        model.check(bad)


@pytest.mark.parametrize("name", NAMES)
def test_no_width_is_reduced(name):
    from repro.models.common import ModelConfig
    spec = model.load(name)
    assert not set(spec["reduced"]) & set(model.WIDTHS)
    cfg = model.model_config(spec, ModelConfig, max_seq=4096)
    for key, field in model.FIELDS.items():
        assert getattr(cfg, field) == spec[key]
    assert cfg.n_experts == spec["reduced"]["n_routed_experts"]["published"]
    bad = dict(spec, reduced=dict(spec["reduced"],
                                  hidden_size={"published": 1, "here": 1,
                                               "why": ""}))
    with pytest.raises(ValueError, match="width"):
        model.check(bad)


@pytest.mark.parametrize("name", NAMES)
def test_expert_shares_add_up_to_the_uncut_layer(name):
    """Each EP rank routes over the full router and computes its own
    experts (the program's ``moe_apply`` with no mesh holds experts
    0..held-1; rank r is that with the router's columns rotated so its
    experts come first).  The ranks' outputs, with the shared experts
    counted once, equal the layer with every expert held."""
    from repro.models import moe
    from repro.models.common import ModelConfig
    spec = model.smoke_spec(model.load(name))
    cfg = model.model_config(spec, ModelConfig, max_seq=64)
    E, held = cfg.n_experts, model.held_experts(spec)
    ranks = E // held
    D, F = cfg.d_model, cfg.moe_d_ff
    ks = jax.random.split(jax.random.key(0), 6)
    full = {"router": jax.random.normal(ks[0], (D, E)) * 0.5,
            "gate_up": jax.random.normal(ks[1], (E, D, 2, F)) * D ** -0.5,
            "down": jax.random.normal(ks[2], (E, F, D)) * F ** -0.5,
            "shared": {"wi": jax.random.normal(ks[3], (D, 2, 2 * F)) * 0.1,
                       "wo": jax.random.normal(ks[4], (2 * F, D)) * 0.1}}
    x = jax.random.normal(ks[5], (24, D))
    with jax.default_matmul_precision("highest"):
        want, _ = moe.moe_apply(full, cfg, x)
        shared = moe.moe_apply(dict(full, gate_up=full["gate_up"][:0],
                                    down=full["down"][:0]), cfg, x)[0]
        total = shared
        for r in range(ranks):
            part = dict(full,
                        router=jnp.roll(full["router"], -r * held, axis=1),
                        gate_up=full["gate_up"][r * held:(r + 1) * held],
                        down=full["down"][r * held:(r + 1) * held])
            total = total + moe.moe_apply(part, cfg, x)[0] - shared
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("name", NAMES)
def test_weight_layout_matches_the_program(name):
    from repro import models
    from repro.models.common import ModelConfig
    from repro.nn import module as nnm
    spec = model.load(name)
    cfg = model.model_config(spec, ModelConfig, max_seq=4096)
    want = jax.eval_shape(lambda: nnm.init_params(
        jax.random.key(0), models.model_defs(cfg), jnp.bfloat16))
    have = weights.shapes(spec)
    held = model.held_experts(spec)

    def expect(path, w):
        keys = [getattr(p, "key", None) for p in path]
        shape = list(w.shape)
        if keys[-1] in ("gate_up", "down"):     # the held experts only
            shape[1 if "period" in keys else 0] = held
        return tuple(shape)

    assert jax.tree.map(lambda a: a.shape, have) == \
        jax.tree_util.tree_map_with_path(expect, want)


@pytest.mark.parametrize("name", NAMES)
def test_token_flops_count_the_parameters_a_token_uses(name):
    """At zero context, a token's operations are twice the parameters it
    multiplies by: the weight tree's matrices, with the routed experts at
    the share this chip holds and the output head for logits."""
    spec = model.load(name)
    shapes = weights.shapes(spec)
    leaves = jax.tree_util.tree_flatten_with_path(shapes)[0]
    width = model.router_width(spec)
    macs = 0.0
    for path, s in leaves:
        keys = [getattr(p, "key", None) for p in path]
        n = float(np.prod(s.shape))
        if keys[-1] == "scale":
            continue                            # norm scales
        if keys[-1] in ("gate_up", "down"):
            n *= spec["num_experts_per_tok"] / width
        if keys[-1] == "table":
            continue
        macs += n
    head = spec["vocab_size"] * spec["hidden_size"]
    got = costs.token_flops(spec, 0, True)
    assert got == pytest.approx(2 * (macs + head), rel=1e-9)


def test_peaks_refuse_an_unknown_device():
    assert costs.peaks("TPU v5 lite")["flops"] == 197e12
    with pytest.raises(KeyError):
        costs.peaks("TPU v99")


def test_smoke_spec_keeps_the_held_share():
    spec = model.load("deepseek-v2-ep8")
    s = model.smoke_spec(spec)
    model.check(s)
    assert model.held_experts(s) / model.router_width(s) == \
        pytest.approx(model.held_experts(spec) / model.router_width(spec))
    from repro.models.common import ModelConfig
    cfg = model.model_config(s, ModelConfig, max_seq=64)
    assert cfg.n_experts == model.router_width(s)
