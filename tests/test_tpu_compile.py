"""Compile-only rehearsals of the serving kernels for a described TPU v5e.

The paged decode and prefill kernels are compiled with Mosaic at
DeepSeek-V2's MLA widths (128 heads, 512 latent + 64 rope dims) and the
engine's pool block of 16 tokens, for a chip that is described, not
attached.  The TPU compiler refuses here what interpret mode accepts: a
tile that overflows the 16 MiB scoped VMEM, a block not aligned to the
tiling.  Nothing runs, so no result or time is checked.

The topology is described inside a fixture (never at import) so that only
the worker given this file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.mla_decode import mla_decode_paged_kernel
from repro.kernels.mla_prefill import mla_prefill_paged_kernel

H, DL, DR, BS = 128, 512, 64, 16      # MLA widths, engine block size
B, NB, N = 8, 128, 1024               # batch, table width, pool blocks
PREFILL_CHUNK = 32                    # PagedMLAEngine's default chunk
SPEC_K = 4                            # verify chunk = SPEC_K + 1


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """The first chip of the described topology, with the persistent
    compilation cache off: a compile for a described chip is written to
    it but cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _pool(one_chip, pool_dtype):
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    pool = [s((N, BS, DL), pool_dtype), s((N, BS, DR), pool_dtype)]
    scales = []
    if pool_dtype == jnp.int8:
        scales = [s((N, BS, 1), jnp.float32), s((N, BS, 1), jnp.float32)]
    return s, pool, scales


def _assert_mosaic(fn, args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("pool_dtype", [jnp.bfloat16, jnp.int8],
                         ids=["bf16", "int8"])
def test_decode_kernel_compiles_for_v5e(one_chip, pool_dtype):
    s, pool, scales = _pool(one_chip, pool_dtype)
    args = [s((B, H, DL + DR), jnp.bfloat16), *pool,
            s((B, NB), jnp.int32), s((B,), jnp.int32), *scales]

    def fn(q, ckv, krope, tables, idx, *sc):
        kw = dict(ckv_scales=sc[0], krope_scales=sc[1]) if sc else {}
        return mla_decode_paged_kernel(q, ckv, krope, tables, idx,
                                       interpret=False, **kw)

    _assert_mosaic(fn, args)


@pytest.mark.parametrize("chunk", [PREFILL_CHUNK, SPEC_K + 1],
                         ids=["prefill_chunk", "verify_chunk"])
@pytest.mark.parametrize("pool_dtype", [jnp.bfloat16, jnp.int8],
                         ids=["bf16", "int8"])
def test_prefill_kernel_compiles_for_v5e(one_chip, chunk, pool_dtype):
    s, pool, scales = _pool(one_chip, pool_dtype)
    args = [s((B, chunk, H, DL + DR), jnp.bfloat16), *pool,
            s((B, NB), jnp.int32), s((B,), jnp.int32), s((B,), jnp.int32),
            *scales]

    def fn(q, ckv, krope, tables, lengths, n_valid, *sc):
        kw = dict(ckv_scales=sc[0], krope_scales=sc[1]) if sc else {}
        return mla_prefill_paged_kernel(q, ckv, krope, tables, lengths,
                                        n_valid, interpret=False, **kw)

    _assert_mosaic(fn, args)
