"""Pallas kernels vs pure-jnp oracles (interpret=True on CPU).

Sweeps shapes, dtypes, GQA group counts and window sizes per the kernel
contract; asserts allclose against ref.py.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.mla_decode import mla_decode_kernel

pytestmark = pytest.mark.kernel

TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


def rand(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


@pytest.mark.parametrize("B,H,Hkv,Lq,Lk,D,Dv", [
    (1, 4, 4, 64, 64, 32, 32),      # MHA square
    (2, 4, 2, 48, 48, 16, 16),      # GQA 2:1
    (1, 8, 1, 33, 70, 16, 24),      # MQA, ragged, Dv != Dqk
    (2, 6, 3, 128, 128, 64, 64),    # larger, MXU-aligned
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_fwd_shapes(B, H, Hkv, Lq, Lk, D, Dv, dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = rand(ks[0], (B, H, Lq, D), dtype)
    k = rand(ks[1], (B, Hkv, Lk, D), dtype)
    v = rand(ks[2], (B, Hkv, Lk, Dv), dtype)
    out = flash_attention(q, k, v, True, None, 0, None, 32, 32, True)
    want = ref.flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


@pytest.mark.parametrize("window", [None, 8, 32])
def test_flash_window(window):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = rand(ks[0], (1, 4, 96, 32), jnp.float32)
    k = rand(ks[1], (1, 2, 96, 32), jnp.float32)
    v = rand(ks[2], (1, 2, 96, 32), jnp.float32)
    out = flash_attention(q, k, v, True, window, 0, None, 32, 32, True)
    want = ref.flash_attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


def test_flash_q_offset_chunked_prefill():
    """Chunked prefill: q block at absolute offset must equal full run."""
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = rand(ks[0], (1, 2, 64, 16), jnp.float32)
    k = rand(ks[1], (1, 2, 64, 16), jnp.float32)
    v = rand(ks[2], (1, 2, 64, 16), jnp.float32)
    full = flash_attention(q, k, v, True, None, 0, None, 16, 16, True)
    part = flash_attention(q[:, :, 32:], k, v, True, None, 32, None, 16, 16, True)
    np.testing.assert_allclose(np.asarray(part), np.asarray(full[:, :, 32:]),
                               atol=2e-5)


def test_flash_backward():
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = rand(ks[0], (2, 4, 48, 32), jnp.float32)
    k = rand(ks[1], (2, 2, 48, 32), jnp.float32)
    v = rand(ks[2], (2, 2, 48, 32), jnp.float32)

    def loss_kernel(q, k, v):
        return (flash_attention(q, k, v, True, None, 0, None, 16, 16, True)
                ** 2).sum()

    def loss_ref(q, k, v):
        return (ref.flash_attention_ref(q, k, v, causal=True) ** 2).sum()

    g = jax.grad(loss_kernel, (0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, (0, 1, 2))(q, k, v)
    for a, b in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=5e-4, rtol=5e-4)


@pytest.mark.parametrize("B,H,S,Dl,Dr,index,block", [
    (1, 4, 64, 32, 8, 0, 32),       # first token
    (2, 8, 100, 32, 8, 57, 32),     # mid-cache, ragged S
    (1, 16, 256, 64, 16, 255, 64),  # full cache
    (2, 128, 128, 512, 64, 100, 64),  # deepseek-v2 head/latent dims
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_mla_decode_kernel(B, H, S, Dl, Dr, index, block, dtype):
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q = rand(ks[0], (B, H, Dl + Dr), dtype)
    ckv = rand(ks[1], (B, S, Dl), dtype)
    krope = rand(ks[2], (B, S, Dr), dtype)
    out = mla_decode_kernel(q, ckv, krope, index, block_k=block,
                            interpret=True)
    want = ref.mla_decode_ref(q, ckv, krope, index)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


def test_mla_decode_kernel_masks_beyond_index():
    """Entries past ``index`` must not influence the result."""
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q = rand(ks[0], (1, 4, 40), jnp.float32)
    ckv = rand(ks[1], (1, 64, 32), jnp.float32)
    krope = rand(ks[2], (1, 64, 8), jnp.float32)
    out = mla_decode_kernel(q, ckv, krope, 19, block_k=16, interpret=True)
    out_p = mla_decode_kernel(q, ckv.at[:, 20:].set(1e4),
                              krope.at[:, 20:].set(1e4), 19, block_k=16,
                              interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_p), atol=1e-6)


def test_interpret_mode_is_explicit_and_never_on_a_tpu(monkeypatch):
    from repro.kernels import interpret
    monkeypatch.delenv(interpret.ENV, raising=False)
    assert interpret.resolve() is False          # default: compile (Mosaic)
    monkeypatch.setenv(interpret.ENV, "0")
    assert interpret.resolve() is False
    assert interpret.resolve(True) is True
    monkeypatch.setenv(interpret.ENV, "1")
    assert interpret.resolve() is True
    assert interpret.resolve(False) is False
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(RuntimeError, match="TPU"):
        interpret.resolve()
    with pytest.raises(RuntimeError, match="TPU"):
        interpret.resolve(True)
    assert interpret.resolve(False) is False
