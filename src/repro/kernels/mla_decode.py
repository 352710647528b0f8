"""Pallas TPU kernel for absorbed-MLA decode attention (the paper's object
of study): MQA-style flash-decoding over the *latent* KV cache.

After weight absorption (any of the seq/rc/ru schemes), each head's query
lives in the joint latent space  q_full = [q_latent(D_kvl) ; q_rope(D_r)]
and K = V = the shared latent cache  [ckv ; k_rope]  — a single "KV head"
shared by all n_h query heads.  This kernel fuses score, online softmax and
value reduction so the cache streams HBM->VMEM exactly once and no
(B, H, S) score tensor ever exists in HBM — the fused execution the paper
assumes ("it is crucial that the resulting, larger weight matrix remains
on-chip"; here the analogous requirement is that scores/softmax state stay
in VMEM).

TPU mapping:
  grid (B, nk) — kv-blocks innermost (sequential), online-softmax state in
  VMEM scratch.  Per-instance VMEM at H=128, D=576, block_k=512:
  q 128x576x4 = 295 KB, cache block 512x576x4 = 1.2 MB, scores 128x512x4
  = 262 KB, acc 128x512x4 = 262 KB  => ~2 MB.
  The cache-length ``index`` is a runtime scalar (scalar-prefetch operand);
  kv-blocks entirely beyond ``index`` skip their compute via pl.when.

Two variants share the kernel structure:
  * ``mla_decode_kernel``       — contiguous (B, S, .) cache, one shared
    scalar ``index``.
  * ``mla_decode_paged_kernel`` — paged block pool + per-request block
    tables and ragged ``indices`` (continuous batching); the block table
    rides the scalar-prefetch operand so the BlockSpec index_map gathers
    pool blocks directly (vLLM-style paged attention).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .interpret import resolve as resolve_interpret

NEG_INF = -2.0 ** 30
INV_LN2 = 1.4426950408889634        # log2(e): folds exp into exp2
RESCALES = ("exp_add", "mul")


def exp_add_rescale(x, d_i):
    """x * 2**d_i for f32 ``x`` and int32 ``d_i <= 0`` via IEEE-754 exponent
    ADDITION (AMLA, arxiv 2509.25224): bitcast to int32, add d_i into the
    exponent field, bitcast back.  Zero inputs and exponent underflow
    (biased exponent reaching 0) flush to 0.0; d_i <= 0 never touches the
    sign bit."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    exp_field = (bits >> 23) & 0xFF
    shifted = jax.lax.bitcast_convert_type(bits + (d_i << 23), jnp.float32)
    ok = (x != 0.0) & (exp_field + d_i > 0)
    return jnp.where(ok, shifted, 0.0)


def softmax_tile_update(s, mask, ckv, acc, m_sc, l_sc, *, rescale):
    """One online-softmax + PV tile update on VMEM scratch state, shared by
    the decode and prefill kernels.  ``s`` is the scaled score tile with
    masked lanes already at NEG_INF; ``ckv`` the (already dequantized) f32
    value tile.

    rescale='mul'     — classic FlashAttention: real-valued running max,
      state rescaled by corr = exp(m_prev - m_new) multiplies.
    rescale='exp_add' — AMLA-style: base-2 softmax with the running max
      quantized up to an integer, so the correction 2**d
      (d = m_prev - m_new, an integer <= 0) is applied by adding d to the
      exponent bits of the f32 state — the per-tile rescale multiplies on
      acc/l disappear from the inner loop.
    """
    m_prev = m_sc[...]
    if rescale == "mul":
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_sc[...] = l_sc[...] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc[...] = acc[...] * corr + p @ ckv
    elif rescale == "exp_add":
        s2 = s * INV_LN2
        m_new = jnp.ceil(
            jnp.maximum(m_prev, jnp.max(s2, axis=1, keepdims=True)))
        p = jnp.where(mask, jnp.exp2(s2 - m_new), 0.0)
        # d <= 0 by construction; anything below -254 zeroes every f32
        # anyway, and the clip keeps d << 23 inside int32.
        d_i = jnp.clip(m_prev - m_new, -254.0, 0.0).astype(jnp.int32)
        l_sc[...] = (exp_add_rescale(l_sc[...], d_i)
                     + jnp.sum(p, axis=1, keepdims=True))
        acc[...] = exp_add_rescale(acc[...], d_i) + p @ ckv
    else:
        raise ValueError(f"unknown rescale {rescale!r}; expected {RESCALES}")
    m_sc[...] = m_new


def _kernel(idx_ref, q_ref, ckv_ref, krope_ref, o_ref, acc, m_sc, l_sc, *,
            scale, v_dim, block_k, nk, rescale):
    ik = pl.program_id(1)
    index = idx_ref[0]

    @pl.when(ik == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)

    @pl.when(ik * block_k <= index)  # skip blocks fully beyond the cache end
    def _compute():
        q = q_ref[0].astype(jnp.float32)            # (H, Dl+Dr)
        ckv = ckv_ref[0].astype(jnp.float32)        # (Bk, Dl)
        krope = krope_ref[0].astype(jnp.float32)    # (Bk, Dr)
        # two-term scores on the split cache (no fused [ckv|krope] copy)
        s = (jax.lax.dot_general(q[:, :v_dim], ckv, (((1,), (1,)), ((), ())))
             + jax.lax.dot_general(q[:, v_dim:], krope,
                                   (((1,), (1,)), ((), ())))) * scale
        k_pos = ik * block_k + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        mask = k_pos <= index
        s = jnp.where(mask, s, NEG_INF)
        softmax_tile_update(s, mask, ckv, acc, m_sc, l_sc, rescale=rescale)

    @pl.when(ik == nk - 1)
    def _done():
        l = l_sc[...]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc[...] / l_safe).astype(o_ref.dtype)


def _paged_kernel(bt_ref, idx_ref, q_ref, ckv_ref, krope_ref, *rest,
                  scale, v_dim, bs, nb, rescale, quantized):
    if quantized:
        ckv_s_ref, krope_s_ref, o_ref, acc, m_sc, l_sc = rest
    else:
        o_ref, acc, m_sc, l_sc = rest
    b = pl.program_id(0)
    j = pl.program_id(1)
    index = idx_ref[b]                      # newest valid position, or -1

    @pl.when(j == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)

    @pl.when(j * bs <= index)   # skip request-local blocks beyond the end
    def _compute():
        q = q_ref[0].astype(jnp.float32)          # (H, Dl+Dr)
        ckv = ckv_ref[0].astype(jnp.float32)      # (bs, Dl) — pool block
        krope = krope_ref[0].astype(jnp.float32)  # (bs, Dr)
        if quantized:
            # dequant in-register: one f32 scale per token slot, the block's
            # scales DMA'd alongside it through the same block-table
            # index_map
            ckv = ckv * ckv_s_ref[0]              # (bs, 1) broadcast
            krope = krope * krope_s_ref[0]
        s = (jax.lax.dot_general(q[:, :v_dim], ckv, (((1,), (1,)), ((), ())))
             + jax.lax.dot_general(q[:, v_dim:], krope,
                                   (((1,), (1,)), ((), ())))) * scale
        k_pos = j * bs + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = k_pos <= index
        s = jnp.where(mask, s, NEG_INF)
        softmax_tile_update(s, mask, ckv, acc, m_sc, l_sc, rescale=rescale)

    @pl.when(j == nb - 1)
    def _done():
        l = l_sc[...]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc[...] / l_safe).astype(o_ref.dtype)


def mla_decode_paged_kernel(q_full, ckv_pages, krope_pages, block_tables,
                            indices, *, softmax_scale: Optional[float] = None,
                            ckv_scales=None, krope_scales=None,
                            rescale: str = "exp_add",
                            interpret: Optional[bool] = None):
    """Paged flash-decode over the latent block pool.

    q_full (B, H, Dl+Dr); ckv_pages (N, bs, Dl); krope_pages (N, bs, Dr);
    block_tables (B, nb) int32; indices (B,) int32 — newest valid position
    per request (ragged; -1 = inactive slot -> zero output).
    Returns (B, H, Dl).

    Both the block table and the per-request indices ride the scalar-
    prefetch operand: the BlockSpec index_map dereferences
    ``block_tables[b, j]`` so each grid step DMAs exactly one pool block
    HBM->VMEM — the single-stream property of the contiguous kernel is
    preserved under paging, and blocks past ``indices[b]`` skip their
    compute (the DMA'd null/stale block is never read by the math).

    For a QUANTIZED pool pass ``ckv_scales``/``krope_scales`` (N, bs, 1)
    f32: each grid step DMAs the block's scales through the same
    block-table index_map and the kernel dequantizes in-register — the
    cache never exists at full precision in HBM.  ``rescale`` selects the
    online-softmax correction: 'exp_add' (AMLA exponent addition, default)
    or 'mul' (classic FlashAttention).
    """
    B, H, D = q_full.shape
    v_dim, dr = ckv_pages.shape[-1], krope_pages.shape[-1]
    bs = ckv_pages.shape[1]
    nb = block_tables.shape[1]
    scale = softmax_scale if softmax_scale is not None else D ** -0.5
    quantized = ckv_scales is not None
    if quantized != (krope_scales is not None):
        raise ValueError("pass both ckv_scales and krope_scales or neither")
    kernel = functools.partial(_paged_kernel, scale=scale, v_dim=v_dim,
                               bs=bs, nb=nb, rescale=rescale,
                               quantized=quantized)
    block_tables = jnp.asarray(block_tables, jnp.int32)
    indices = jnp.asarray(indices, jnp.int32)
    in_specs = [
        pl.BlockSpec((1, H, D), lambda b, j, bt, idx: (b, 0, 0)),
        pl.BlockSpec((1, bs, v_dim),
                     lambda b, j, bt, idx: (bt[b, j], 0, 0)),
        pl.BlockSpec((1, bs, dr),
                     lambda b, j, bt, idx: (bt[b, j], 0, 0)),
    ]
    operands = [block_tables, indices, q_full, ckv_pages, krope_pages]
    if quantized:
        in_specs += [
            pl.BlockSpec((1, bs, 1), lambda b, j, bt, idx: (bt[b, j], 0, 0)),
            pl.BlockSpec((1, bs, 1), lambda b, j, bt, idx: (bt[b, j], 0, 0)),
        ]
        operands += [ckv_scales, krope_scales]
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, nb),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, H, v_dim),
                                   lambda b, j, bt, idx: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((H, v_dim), jnp.float32),
                pltpu.VMEM((H, 1), jnp.float32),
                pltpu.VMEM((H, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, v_dim), q_full.dtype),
        interpret=resolve_interpret(interpret),
    )(*operands)
    return out


def mla_decode_kernel(q_full, ckv, krope, index, *,
                      softmax_scale: Optional[float] = None,
                      block_k: int = 512, rescale: str = "exp_add",
                      interpret: Optional[bool] = None):
    """q_full: (B, H, Dl+Dr) = [q_latent ; q_rope]; ckv: (B, S, Dl);
    krope: (B, S, Dr); index: scalar int32 (newest valid position).
    Returns (B, H, Dl) — attention-weighted latent values."""
    B, H, D = q_full.shape
    S, v_dim = ckv.shape[1], ckv.shape[2]
    scale = softmax_scale if softmax_scale is not None else D ** -0.5
    bk = min(block_k, S)
    pad = -S % bk
    if pad:
        ckv = jnp.pad(ckv, ((0, 0), (0, pad), (0, 0)))
        krope = jnp.pad(krope, ((0, 0), (0, pad), (0, 0)))
    nk = ckv.shape[1] // bk
    dr = krope.shape[-1]
    kernel = functools.partial(_kernel, scale=scale, v_dim=v_dim,
                               block_k=bk, nk=nk, rescale=rescale)
    index = jnp.asarray(index, jnp.int32).reshape((1,))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, nk),
            in_specs=[
                pl.BlockSpec((1, H, D), lambda b, j, idx: (b, 0, 0)),
                pl.BlockSpec((1, bk, v_dim), lambda b, j, idx: (b, j, 0)),
                pl.BlockSpec((1, bk, dr), lambda b, j, idx: (b, j, 0)),
            ],
            out_specs=pl.BlockSpec((1, H, v_dim), lambda b, j, idx: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((H, v_dim), jnp.float32),
                pltpu.VMEM((H, 1), jnp.float32),
                pltpu.VMEM((H, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, v_dim), q_full.dtype),
        interpret=resolve_interpret(interpret),
    )(index, q_full, ckv, krope)
    return out
