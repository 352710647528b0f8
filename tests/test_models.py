"""Per-architecture smoke tests: instantiate the REDUCED config of each
assigned arch, run one forward + one train step on CPU, assert output
shapes and absence of NaNs; check prefill/decode consistency for one arch
per family."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as C
import repro.models as models
from repro.nn import module as nnm
from repro.optim import AdamWConfig, adamw_init
from repro.runtime.steps import make_train_step, TrainStepConfig

ARCHS = C.ARCHS


def _batch(cfg, B=2, L=24, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    b = {"tokens": jax.random.randint(ks[0], (B, L), 0, cfg.vocab),
         "labels": jax.random.randint(ks[1], (B, L), 0, cfg.vocab)}
    if cfg.family in ("vlm", "encdec"):
        P = cfg.n_patches if cfg.family == "vlm" else cfg.n_frames
        b["embeds"] = jax.random.normal(ks[2], (B, P, cfg.d_model),
                                        jnp.float32) * 0.1
    return b


@pytest.mark.parametrize("arch", ARCHS + ["deepseek-v3-671b"])
def test_smoke_forward_shapes_no_nan(arch):
    cfg = C.smoke(arch)
    params = nnm.init_params(jax.random.PRNGKey(0), models.model_defs(cfg),
                             jnp.float32)
    b = _batch(cfg)
    logits, aux = models.forward(params, cfg, b["tokens"],
                                 embeds=b.get("embeds"),
                                 compute_dtype=jnp.float32)
    P = 0
    if cfg.family == "vlm":
        P = cfg.n_patches
    assert logits.shape == (2, b["tokens"].shape[1] + P, cfg.vocab)
    assert not bool(jnp.isnan(logits).any())
    assert set(aux) == {"balance", "z_loss", "dropped_frac"}


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_train_step(arch):
    cfg = C.smoke(arch)
    params = nnm.init_params(jax.random.PRNGKey(0), models.model_defs(cfg),
                             jnp.float32)
    opt_cfg = AdamWConfig(lr=1e-3)
    opt = adamw_init(params, opt_cfg)
    step, _ = make_train_step(cfg, None, opt_cfg,
                              TrainStepConfig(compute_dtype=jnp.float32))
    b = _batch(cfg)
    before = [np.asarray(x) for x in jax.tree.leaves(params)]  # pre-donation
    p1, o1, m1 = step(params, opt, b)
    assert np.isfinite(float(m1["loss"]))
    p2, o2, m2 = step(p1, o1, _batch(cfg, seed=1))
    assert np.isfinite(float(m2["loss"]))
    # params actually changed
    delta = max(float(np.max(np.abs(np.asarray(a) - b2)))
                for a, b2 in zip(jax.tree.leaves(p2), before))
    assert delta > 0


@pytest.mark.parametrize("arch", [
    "gemma3-1b",              # dense, local:global windows
    "deepseek-v2-236b",       # MLA + MoE (the paper's arch)
    "jamba-1.5-large-398b",   # hybrid mamba/attn/MoE
    "xlstm-350m",             # pure SSM
    "whisper-medium",         # encoder-decoder
    "internvl2-26b",          # VLM with patch prefix
])
def test_prefill_decode_match_forward(arch):
    """prefill(tokens) + decode(t) logits == teacher-forced forward.

    MoE capacity is made non-binding (capacity_factor=64): token-drop
    patterns legitimately differ between a 26-token forward and a 1-token
    decode, which is capacity semantics, not an equivalence bug."""
    import dataclasses
    cfg = dataclasses.replace(C.smoke(arch), capacity_factor=64.0)
    params = nnm.init_params(jax.random.PRNGKey(1), models.model_defs(cfg),
                             jnp.float32)
    b = _batch(cfg, B=2, L=12, seed=2)
    toks = b["tokens"]
    logits, _ = models.forward(params, cfg, toks, embeds=b.get("embeds"),
                               compute_dtype=jnp.float32)
    last, cache = models.prefill(params, cfg, toks, embeds=b.get("embeds"),
                                 capacity=32, compute_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(last), np.asarray(logits[:, -1]),
                               atol=3e-4, rtol=1e-4)
    # one decode step == forward over L+1 tokens
    nxt = jnp.argmax(last, -1).astype(jnp.int32)
    # decode index counts CACHED positions: VLM caches patches + text
    index = toks.shape[1] + (cfg.n_patches if cfg.family == "vlm" else 0)
    step_logits, cache = models.decode_step(
        params, cfg, nxt, cache, index, compute_dtype=jnp.float32)
    toks2 = jnp.concatenate([toks, nxt[:, None]], axis=1)
    logits2, _ = models.forward(params, cfg, toks2, embeds=b.get("embeds"),
                                compute_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(step_logits),
                               np.asarray(logits2[:, -1]), atol=3e-4,
                               rtol=1e-4)


def test_serving_logits_are_f32_under_bf16():
    """Tokens are sampled from f32 logits whatever the compute dtype: in
    bf16 the top two of a large vocabulary often tie, and a step that
    fuses the argmax may see them unrounded while one that returns the
    logits rounds them."""
    cfg = C.smoke("deepseek-v2-236b")
    bf = jnp.bfloat16
    params = nnm.init_params(jax.random.PRNGKey(1), models.model_defs(cfg), bf)
    toks = _batch(cfg, B=2, L=8, seed=2)["tokens"]
    last, cache = models.prefill(params, cfg, toks, capacity=16,
                                 compute_dtype=bf)
    step, _ = models.decode_step(params, cfg,
                                 jnp.argmax(last, -1).astype(jnp.int32),
                                 cache, toks.shape[1], compute_dtype=bf)
    train, _ = models.forward(params, cfg, toks, compute_dtype=bf)
    assert last.dtype == step.dtype == jnp.float32
    assert train.dtype == bf          # the training head is unchanged


def test_full_configs_param_counts():
    """FULL configs match the published model sizes (±10%)."""
    expect = {
        "jamba-1.5-large-398b": 398e9, "gemma3-1b": 1.0e9,
        "granite-34b": 34e9, "phi3-mini-3.8b": 3.8e9,
        "starcoder2-7b": 7e9, "deepseek-v2-236b": 236e9,
        "granite-moe-1b-a400m": 1.3e9, "whisper-medium": 0.77e9,
        "internvl2-26b": 19.3e9,  # backbone only; ViT is stubbed
        "deepseek-v3-671b": 671e9,
    }
    for arch, want in expect.items():
        got = models.param_count(C.full(arch))
        assert abs(got - want) / want < 0.4, (arch, got, want)


def test_xlstm_param_count_soft():
    got = models.param_count(C.full("xlstm-350m"))
    assert 3e8 < got < 6e8


def test_layer_plans_cover_all_layers():
    for arch in ARCHS:
        cfg = C.full(arch)
        if cfg.family == "encdec":
            continue
        prefix, period, n, suffix = cfg.layer_plan()
        assert len(prefix) + len(period) * n + len(suffix) == cfg.n_layers


def test_gemma3_local_global_pattern():
    cfg = C.full("gemma3-1b")
    prefix, period, n, suffix = cfg.layer_plan()
    assert len(period) == 6 and n == 4
    wins = [s.window for s in period]
    assert wins[:5] == [512] * 5 and wins[5] is None


def test_jamba_interleave_pattern():
    cfg = C.full("jamba-1.5-large-398b")
    _, period, n, _ = cfg.layer_plan()
    assert len(period) == 8 and n == 9
    mixers = [s.mixer for s in period]
    assert mixers.count("attn") == 1 and mixers[3] == "attn"
    assert [s.ffn for s in period].count("moe") == 4
