"""Pallas TPU flash attention (forward + backward), GQA + sliding window.

TPU mapping (see DESIGN.md §9):
  grid (B, H, nq, nk) — nk innermost; TPU executes the grid sequentially, so
  the online-softmax state (acc/m/l) lives in VMEM scratch across kv blocks.
  Block shapes are MXU-aligned (q/k/v blocks 128 x D); with D<=576 the
  per-instance VMEM footprint is ~1.2 MB, far under the ~128 MB/core budget.

Numerics: fp32 accumulation, finite -2^30 mask value + explicit p=0 on
masked lanes (avoids inf-inf NaNs for fully-masked rows).

Validated on CPU with ``interpret=True`` against ``ref.flash_attention_ref``
(tests/test_kernels.py sweeps shapes, dtypes, GQA groups, windows).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .interpret import resolve as resolve_interpret

NEG_INF = -2.0 ** 30


def _mask(iq, ik, *, block_q, block_k, q_offset, lk_valid, causal, window):
    q_pos = q_offset + iq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_pos = ik * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    m = k_pos < lk_valid
    if causal:
        m &= k_pos <= q_pos
    if window is not None:
        m &= k_pos > (q_pos - window)
    return m


# ------------------------------------------------------------- forward -----


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc, m_sc, l_sc, *,
                scale, causal, window, q_offset, block_q, block_k, nk, lk_valid):
    iq, ik = pl.program_id(2), pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)

    q = q_ref[0, 0].astype(jnp.float32)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
    mask = _mask(iq, ik, block_q=block_q, block_k=block_k, q_offset=q_offset,
                 lk_valid=lk_valid, causal=causal, window=window)
    s = jnp.where(mask, s, NEG_INF)
    m_prev = m_sc[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m_prev - m_new)
    l_sc[...] = l_sc[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    acc[...] = acc[...] * corr + p @ v
    m_sc[...] = m_new

    @pl.when(ik == nk - 1)
    def _done():
        l = l_sc[...]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc[...] / l_safe).astype(o_ref.dtype)
        lse_ref[0, 0] = (m_sc[...] + jnp.log(l_safe))[:, 0]


def _fwd(q, k, v, *, causal, window, q_offset, scale, block_q, block_k, interpret):
    B, H, Lq, Dqk = q.shape
    Hkv, Lk, Dv = k.shape[1], k.shape[2], v.shape[-1]
    G = H // Hkv
    bq, bk = min(block_q, Lq), min(block_k, Lk)
    lq_pad = -Lq % bq
    lk_pad = -Lk % bk
    if lq_pad:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, lq_pad), (0, 0)))
    if lk_pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, lk_pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, lk_pad), (0, 0)))
    nq, nk = q.shape[2] // bq, k.shape[2] // bk
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, window=window,
        q_offset=q_offset, block_q=bq, block_k=bk, nk=nk, lk_valid=Lk)
    out, lse = pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, Dqk), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, Dqk), lambda b, h, i, j: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, bk, Dv), lambda b, h, i, j: (b, h // G, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, Dv), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq), lambda b, h, i, j: (b, h, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, q.shape[2], Dv), q.dtype),
            jax.ShapeDtypeStruct((B, H, q.shape[2]), jnp.float32),
        ],
        scratch_shapes=[pl_scratch((bq, Dv)), pl_scratch((bq, 1)), pl_scratch((bq, 1))],
        interpret=interpret,
    )(q, k, v)
    if lq_pad:
        out, lse = out[:, :, :Lq], lse[:, :, :Lq]
    return out, lse


def pl_scratch(shape):
    from jax.experimental.pallas import tpu as pltpu
    return pltpu.VMEM(shape, jnp.float32)


# ------------------------------------------------------------ backward -----


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, dq_ref, dq_acc, *,
               scale, causal, window, q_offset, block_q, block_k, nk, lk_valid):
    iq, ik = pl.program_id(2), pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    q = q_ref[0, 0].astype(jnp.float32)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    do = do_ref[0, 0].astype(jnp.float32)
    lse = lse_ref[0, 0].astype(jnp.float32)[:, None]
    delta = dl_ref[0, 0].astype(jnp.float32)[:, None]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
    mask = _mask(iq, ik, block_q=block_q, block_k=block_k, q_offset=q_offset,
                 lk_valid=lk_valid, causal=causal, window=window)
    p = jnp.where(mask, jnp.exp(s - lse), 0.0)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())))
    ds = p * (dp - delta) * scale
    dq_acc[...] += ds @ k

    @pl.when(ik == nk - 1)
    def _done():
        dq_ref[0, 0] = dq_acc[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, dk_ref, dv_ref,
                dk_acc, dv_acc, *, scale, causal, window, q_offset, block_q,
                block_k, nq, G, lk_valid):
    ik, g, iq = pl.program_id(2), pl.program_id(3), pl.program_id(4)

    @pl.when((g == 0) & (iq == 0))
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q = q_ref[0, 0].astype(jnp.float32)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    do = do_ref[0, 0].astype(jnp.float32)
    lse = lse_ref[0, 0].astype(jnp.float32)[:, None]
    delta = dl_ref[0, 0].astype(jnp.float32)[:, None]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
    mask = _mask(iq, ik, block_q=block_q, block_k=block_k, q_offset=q_offset,
                 lk_valid=lk_valid, causal=causal, window=window)
    p = jnp.where(mask, jnp.exp(s - lse), 0.0)
    dv_acc[...] += jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())))
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())))
    ds = p * (dp - delta) * scale
    dk_acc[...] += jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())))

    @pl.when((g == G - 1) & (iq == nq - 1))
    def _done():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


# ----------------------------------------------------------- public API ----


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def flash_attention(q, k, v, causal: bool = True, window: Optional[int] = None,
                    q_offset: int = 0, softmax_scale: Optional[float] = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: Optional[bool] = None):
    """q: (B, H, Lq, Dqk); k, v: (B, Hkv, Lk, Dqk/Dv) -> (B, H, Lq, Dv)."""
    out, _ = _flash_fwd(q, k, v, causal, window, q_offset, softmax_scale,
                        block_q, block_k, interpret)
    return out


def _resolve(softmax_scale, Dqk, interpret):
    scale = softmax_scale if softmax_scale is not None else Dqk ** -0.5
    return scale, resolve_interpret(interpret)


def _flash_fwd(q, k, v, causal, window, q_offset, softmax_scale, block_q,
               block_k, interpret):
    scale, interpret = _resolve(softmax_scale, q.shape[-1], interpret)
    out, lse = _fwd(q, k, v, causal=causal, window=window, q_offset=q_offset,
                    scale=scale, block_q=block_q, block_k=block_k,
                    interpret=interpret)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, window, q_offset, softmax_scale, block_q, block_k,
               interpret, res, dout):
    q, k, v, out, lse = res
    B, H, Lq, Dqk = q.shape
    Hkv, Lk, Dv = k.shape[1], k.shape[2], v.shape[-1]
    G = H // Hkv
    scale, interpret = _resolve(softmax_scale, Dqk, interpret)
    delta = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)

    bq, bk = min(block_q, Lq), min(block_k, Lk)
    lq_pad, lk_pad = -Lq % bq, -Lk % bk
    pad4 = lambda x, n: jnp.pad(x, ((0, 0), (0, 0), (0, n), (0, 0))) if n else x
    pad3 = lambda x, n: jnp.pad(x, ((0, 0), (0, 0), (0, n))) if n else x
    qp, kp, vp = pad4(q, lq_pad), pad4(k, lk_pad), pad4(v, lk_pad)
    dop, lsep, dlp = pad4(dout, lq_pad), pad3(lse, lq_pad), pad3(delta, lq_pad)
    # padded lse rows are 0 -> p = exp(-2^30 - 0) = 0: padded q rows are inert
    nq, nk = qp.shape[2] // bq, kp.shape[2] // bk

    dq_kernel = functools.partial(
        _dq_kernel, scale=scale, causal=causal, window=window,
        q_offset=q_offset, block_q=bq, block_k=bk, nk=nk, lk_valid=Lk)
    dq = pl.pallas_call(
        dq_kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, Dqk), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, Dqk), lambda b, h, i, j: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, bk, Dv), lambda b, h, i, j: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, bq, Dv), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq), lambda b, h, i, j: (b, h, i)),
            pl.BlockSpec((1, 1, bq), lambda b, h, i, j: (b, h, i)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, Dqk), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct(qp.shape, q.dtype),
        scratch_shapes=[pl_scratch((bq, Dqk))],
        interpret=interpret,
    )(qp, kp, vp, dop, lsep, dlp)

    dkv_kernel = functools.partial(
        _dkv_kernel, scale=scale, causal=causal, window=window,
        q_offset=q_offset, block_q=bq, block_k=bk, nq=nq, G=G, lk_valid=Lk)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(B, Hkv, nk, G, nq),
        in_specs=[
            pl.BlockSpec((1, 1, bq, Dqk), lambda b, hk, j, g, i: (b, hk * G + g, i, 0)),
            pl.BlockSpec((1, 1, bk, Dqk), lambda b, hk, j, g, i: (b, hk, j, 0)),
            pl.BlockSpec((1, 1, bk, Dv), lambda b, hk, j, g, i: (b, hk, j, 0)),
            pl.BlockSpec((1, 1, bq, Dv), lambda b, hk, j, g, i: (b, hk * G + g, i, 0)),
            pl.BlockSpec((1, 1, bq), lambda b, hk, j, g, i: (b, hk * G + g, i)),
            pl.BlockSpec((1, 1, bq), lambda b, hk, j, g, i: (b, hk * G + g, i)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bk, Dqk), lambda b, hk, j, g, i: (b, hk, j, 0)),
            pl.BlockSpec((1, 1, bk, Dv), lambda b, hk, j, g, i: (b, hk, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(kp.shape, k.dtype),
            jax.ShapeDtypeStruct(vp.shape, v.dtype),
        ],
        scratch_shapes=[pl_scratch((bk, Dqk)), pl_scratch((bk, Dv))],
        interpret=interpret,
    )(qp, kp, vp, dop, lsep, dlp)

    if lq_pad:
        dq = dq[:, :, :Lq]
    if lk_pad:
        dk, dv = dk[:, :, :Lk], dv[:, :, :Lk]
    return dq, dk, dv


flash_attention.defvjp(_flash_fwd, _flash_bwd)
