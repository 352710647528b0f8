"""Public jit'd wrappers for the Pallas kernels + shard_map builders.

Models call attention through these so the implementation is swappable:
  impl='ref'    pure-jnp dense reference (GSPMD partitions it freely)
  impl='kernel' Pallas kernel (compiled with Mosaic; interpreted only where
                kernels.interpret says so), wrapped in shard_map when a
                mesh is active so each device runs the kernel on its local
                shard (batch over DP axes, heads over 'model').
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
from jax.sharding import Mesh, PartitionSpec as PS

from . import ref
from .flash_attention import flash_attention
from .mla_decode import mla_decode_kernel, mla_decode_paged_kernel
from .mla_prefill import mla_prefill_paged_kernel


def attention(q, k, v, *, impl: str = "ref", causal: bool = True,
              window: Optional[int] = None, q_offset: int = 0,
              softmax_scale: Optional[float] = None,
              mesh: Optional[Mesh] = None, dp_axes=None):
    """q: (B, H, Lq, Dqk); k, v: (B, Hkv, Lk, D). Returns (B, H, Lq, Dv)."""
    if impl == "ref":
        return ref.flash_attention_ref(q, k, v, causal=causal, window=window,
                                       q_offset=q_offset, softmax_scale=softmax_scale)
    fn = functools.partial(flash_attention, causal=causal, window=window,
                           q_offset=q_offset, softmax_scale=softmax_scale)
    if mesh is None:
        return fn(q, k, v)
    dp = dp_axes if dp_axes is not None else tuple(
        a for a in ("pod", "data") if a in mesh.axis_names)
    qs = PS(dp, "model", None, None)
    return jax.shard_map(fn, mesh=mesh, in_specs=(qs, qs, qs),
                            out_specs=qs, check_vma=False)(q, k, v)


def mla_decode_attention(q_full, ckv, krope, index, *, impl: str = "ref",
                         softmax_scale: Optional[float] = None,
                         mesh: Optional[Mesh] = None, dp_axes=None,
                         block_k: int = 512):
    """Absorbed-MLA decode: q_full (B,H,Dl+Dr), ckv (B,S,Dl), krope
    (B,S,Dr) -> (B,H,Dl).

    Under shard_map: batch over DP axes, heads over 'model'; the latent
    cache is head-shared so it is REPLICATED over 'model' (the MQA
    structure of absorbed MLA — each model shard re-reads the same cache,
    which is the paper's bandwidth win: the cache is ~16x smaller than a
    dense KV cache, so n_model re-reads still move less data)."""
    if impl == "ref":
        return ref.mla_decode_ref(q_full, ckv, krope, index,
                                  softmax_scale=softmax_scale)
    fn = functools.partial(mla_decode_kernel,
                           softmax_scale=softmax_scale, block_k=block_k)
    if mesh is None:
        return fn(q_full, ckv, krope, index)
    dp = dp_axes if dp_axes is not None else tuple(
        a for a in ("pod", "data") if a in mesh.axis_names)
    return jax.shard_map(
        lambda q, c, r, i: fn(q, c, r, i), mesh=mesh,
        in_specs=(PS(dp, "model", None), PS(dp, None, None),
                  PS(dp, None, None), PS()),
        out_specs=PS(dp, "model", None), check_vma=False,
    )(q_full, ckv, krope, index)


def mla_decode_paged_attention(q_full, ckv_pages, krope_pages, block_tables,
                               indices, *, impl: str = "ref",
                               softmax_scale: Optional[float] = None,
                               ckv_scales=None, krope_scales=None,
                               rescale: str = "exp_add",
                               mesh: Optional[Mesh] = None, dp_axes=None):
    """Paged absorbed-MLA decode: q_full (B,H,Dl+Dr), pool pages
    (N,bs,Dl)/(N,bs,Dr), block_tables (B,nb), per-request ``indices``
    (B,) -> (B,H,Dl).

    Quantized pools pass per-token-slot ``ckv_scales``/``krope_scales``
    (N,bs,1) f32: the kernel dequantizes in-register, the ref oracle on
    the gathered f32 view.  ``rescale`` picks the kernel's online-softmax
    correction (AMLA 'exp_add' or classic 'mul'); the oracle's exact
    softmax ignores it.

    Under shard_map the batch (and with it the block tables / indices)
    shards over the DP axes and heads over 'model'; the block POOL (data
    and scale leaves alike) is replicated over 'model' exactly like the
    contiguous latent cache (the MQA structure of absorbed MLA: head
    shards re-read the same compact pool)."""
    if impl == "ref":
        return ref.mla_decode_paged_ref(q_full, ckv_pages, krope_pages,
                                        block_tables, indices,
                                        softmax_scale=softmax_scale,
                                        ckv_scales=ckv_scales,
                                        krope_scales=krope_scales)
    quantized = ckv_scales is not None
    if mesh is None:
        return mla_decode_paged_kernel(
            q_full, ckv_pages, krope_pages, block_tables, indices,
            softmax_scale=softmax_scale, ckv_scales=ckv_scales,
            krope_scales=krope_scales, rescale=rescale)
    dp = dp_axes if dp_axes is not None else tuple(
        a for a in ("pod", "data") if a in mesh.axis_names)
    in_specs = [PS(dp, "model", None), PS(None, None, None),
                PS(None, None, None), PS(dp, None), PS(dp)]
    operands = [q_full, ckv_pages, krope_pages, block_tables, indices]
    if quantized:
        in_specs += [PS(None, None, None), PS(None, None, None)]
        operands += [ckv_scales, krope_scales]

        def fn(q, c, r, t, i, cs, rs):
            return mla_decode_paged_kernel(
                q, c, r, t, i, softmax_scale=softmax_scale,
                ckv_scales=cs, krope_scales=rs, rescale=rescale)
    else:
        def fn(q, c, r, t, i):
            return mla_decode_paged_kernel(
                q, c, r, t, i, softmax_scale=softmax_scale, rescale=rescale)
    return jax.shard_map(
        fn, mesh=mesh, in_specs=tuple(in_specs),
        out_specs=PS(dp, "model", None), check_vma=False,
    )(*operands)


def mla_prefill_paged_attention(q_full, ckv_pages, krope_pages, block_tables,
                                lengths, n_valid, *, impl: str = "ref",
                                softmax_scale: Optional[float] = None,
                                ckv_scales=None, krope_scales=None,
                                rescale: str = "exp_add",
                                mesh: Optional[Mesh] = None, dp_axes=None,
                                block_q: int = 0):
    """Paged chunked-prefill MLA attention: q_full (B,C,H,Dl+Dr), pool
    pages (N,bs,Dl)/(N,bs,Dr), block_tables (B,nb), per-request
    ``lengths``/``n_valid`` (B,) -> (B,C,H,Dl).

    Quantized pools pass ``ckv_scales``/``krope_scales`` (N,bs,1) f32 and
    ``rescale`` picks the kernel's online-softmax correction — see
    :func:`mla_decode_paged_attention`.

    The multi-query sibling of :func:`mla_decode_paged_attention`: under
    shard_map the batch (and with it the block tables / lengths /
    n_valid) shards over the DP axes and heads over 'model'; the block
    POOL is replicated over 'model' (the MQA structure of absorbed MLA —
    head shards re-read the same compact pool, which is the paper's
    bandwidth win: the latent pool is ~16x smaller than dense KV)."""
    if impl == "ref":
        return ref.mla_prefill_paged_ref(q_full, ckv_pages, krope_pages,
                                         block_tables, lengths, n_valid,
                                         softmax_scale=softmax_scale,
                                         ckv_scales=ckv_scales,
                                         krope_scales=krope_scales)
    quantized = ckv_scales is not None
    kfn = functools.partial(mla_prefill_paged_kernel,
                            softmax_scale=softmax_scale, block_q=block_q,
                            rescale=rescale)
    if mesh is None:
        return kfn(q_full, ckv_pages, krope_pages, block_tables, lengths,
                   n_valid, ckv_scales=ckv_scales, krope_scales=krope_scales)
    dp = dp_axes if dp_axes is not None else tuple(
        a for a in ("pod", "data") if a in mesh.axis_names)
    in_specs = [PS(dp, None, "model", None), PS(None, None, None),
                PS(None, None, None), PS(dp, None), PS(dp), PS(dp)]
    operands = [q_full, ckv_pages, krope_pages, block_tables, lengths,
                n_valid]
    if quantized:
        in_specs += [PS(None, None, None), PS(None, None, None)]
        operands += [ckv_scales, krope_scales]

        def fn(q, c, r, t, ln, nv, cs, rs):
            return kfn(q, c, r, t, ln, nv, ckv_scales=cs, krope_scales=rs)
    else:
        def fn(q, c, r, t, ln, nv):
            return kfn(q, c, r, t, ln, nv)
    return jax.shard_map(
        fn, mesh=mesh, in_specs=tuple(in_specs),
        out_specs=PS(dp, None, "model", None), check_vma=False,
    )(*operands)
