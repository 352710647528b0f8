"""Each cell's serving steps compile for a described TPU v5e at the cell's
published widths and engine settings, with no chip attached.  The paged
prefill kernel's scoped VMEM grows with batch x chunk, so a cell whose
settings the chip's compiler refuses fails here first."""
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from chipbench import harness, model, traffic, weights  # noqa: E402
from chipbench import plan as planlib  # noqa: E402

CELLS = sorted(f[:-5] for f in os.listdir(os.path.join(
    ROOT, "chipbench", "workloads")) if f.endswith(".json"))


@pytest.fixture(scope="module")
def one_chip():
    """The first chip of a described v5e, with the persistent compilation
    cache off: a compile for a described chip cannot be read back."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.mark.parametrize("cell", CELLS)
def test_cell_steps_compile_for_v5e(cell, one_chip, monkeypatch):
    # the kernels compile with Mosaic, as on the chip, even where the CPU
    # suite asks for the Pallas interpreter
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "0")
    from repro import models
    from repro.core import mla as mlalib
    from repro.models.common import ModelConfig
    from repro.runtime.steps import (make_chunked_prefill_step,
                                     make_paged_sample_step)
    c = harness.load_cell(cell)
    spec = model.load(c["config"])
    plan = traffic.make(traffic.load(c["traffic"]), spec["vocab_size"], 1)
    cfg = model.model_config(spec, ModelConfig,
                             max_seq=planlib.longest(plan) + 1)
    B, C = c["engine"]["max_batch"], c["engine"]["prefill_chunk"]
    num_blocks, nb = harness.geometry(spec, c, plan)
    s = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)
    params = jax.eval_shape(lambda p: mlalib.attach_absorbed_tree(
        p, cfg.mla_config()), weights.shapes(spec))
    params = jax.tree.map(s, params)
    pool = jax.tree.map(s, jax.eval_shape(lambda: models.init_paged_cache(
        cfg, num_blocks, spec["settings"]["block_size"], jnp.bfloat16,
        cache_dtype="bf16")))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32,
                                              sharding=one_chip)
    u32 = jax.ShapeDtypeStruct((B,), jnp.uint32, sharding=one_chip)
    kw = dict(compute_dtype=jnp.bfloat16, impl="kernel", scheme="seq",
              cache_dtype="bf16")
    prefill = make_chunked_prefill_step(cfg, None, **kw).lower(
        params, i32(B, C), pool, i32(B, nb), i32(B), i32(B)).compile()
    decode = make_paged_sample_step(cfg, None, **kw).lower(
        params, i32(B), pool, i32(B, nb), i32(B), u32, u32).compile()
    for compiled in (prefill, decode):
        assert 'custom_call_target="tpu_custom_call"' in compiled.as_text()
