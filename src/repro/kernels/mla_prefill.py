"""Pallas TPU kernel for paged chunked-prefill MLA attention: the
multi-query sibling of ``kernels/mla_decode.mla_decode_paged_kernel``.

One CHUNK of batched prefill feeds C query tokens per request, already
mapped into the joint latent space (q_full = [q_eff(D_kvl) ; q_rope(D_r)]
— any of the seq/rc/ru absorption schemes; they differ only in how q_eff
was produced).  K = V = the shared paged latent pool.  The kernel walks
each request's block table via scalar prefetch and runs fused
score/online-softmax/PV per pool block, so the resident prefix streams
HBM->VMEM exactly once per chunk and NO contiguous (B, S) gather of the
block-table view is ever materialized in HBM — the reference gather path
(core.mla gather branch) writes + re-reads that view every chunk, which
is exactly the bandwidth the paper's roofline says the compute-bound
prefill phase cannot afford (see hwmodel.attention_costs
.mla_prefill_chunk_cost for the closed-form delta).

TPU mapping:
  grid (B, nq, nb) — kv-blocks innermost (sequential), query tiles of
  ``block_q`` chunk rows next, batch outermost.  Online-softmax state
  lives in VMEM scratch shaped (block_q*H, D_kvl).  A tile holds
  block_q*H score rows, and each row costs ~5 KB of VMEM at D=576 (the
  double-buffered bf16 q and output blocks, the f32 q and acc, the
  m/l/score lanes).  The default tile keeps block_q*H <= MAX_TILE_ROWS =
  2048 (~10 MB), inside the 16 MiB scoped VMEM limit of a v5e: at H=128
  that is block_q=16, and the whole chunk (block_q=32, 4096 rows, 20 MB)
  is refused by the compiler.  Each query tile re-streams the prefix
  blocks it attends, so a chunk of C rows reads them ceil(C/block_q)
  times.

Ragged semantics (shared with core.cache / runtime.scheduler):
  * ``lengths[b]`` — absolute position of row b's FIRST chunk token
    (tokens already resident: prefix-cache hits + earlier chunks).
  * ``n_valid[b]`` — real tokens in row b's chunk; rows past it are
    padding and produce EXACT ZEROS (their l stays 0), as do idle batch
    rows (n_valid == 0) — the engine discards them either way, but zeros
    keep kernel/oracle parity assertable everywhere.
  * causal over absolute positions: chunk token c attends pool positions
    <= lengths[b] + c.  The chunk's own latents are scattered into the
    pool BEFORE the kernel runs (update_latent_paged_chunk), so the
    in-chunk causal triangle rides the same block-table walk.
  * unassigned block-table entries point at the null block 0; blocks
    fully beyond the last valid position skip their compute via pl.when
    (the DMA'd null/stale block is never read by the math).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .interpret import resolve as resolve_interpret
from .mla_decode import softmax_tile_update

NEG_INF = -2.0 ** 30
MAX_TILE_ROWS = 2048        # block_q * H score rows per tile (see above)


def _prefill_kernel(bt_ref, len_ref, nv_ref, q_ref, ckv_ref, krope_ref,
                    *rest, scale, v_dim, bq, H, bs, nb, rescale, quantized):
    if quantized:
        ckv_s_ref, krope_s_ref, o_ref, acc, m_sc, l_sc = rest
    else:
        o_ref, acc, m_sc, l_sc = rest
    b = pl.program_id(0)
    iq = pl.program_id(1)
    j = pl.program_id(2)
    start = len_ref[b]                  # absolute position of chunk row 0
    nv = nv_ref[b]                      # valid rows in this request's chunk

    @pl.when(j == 0)
    def _init():
        acc[...] = jnp.zeros_like(acc)
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)

    # newest position any valid row of THIS query tile may attend; blocks
    # past it (and tiles wholly past n_valid) skip their compute.
    last_q = start + jnp.minimum(nv, (iq + 1) * bq) - 1

    @pl.when((iq * bq < nv) & (j * bs <= last_q))
    def _compute():
        q = q_ref[0].astype(jnp.float32).reshape(bq * H, -1)  # (bq*H, Dl+Dr)
        ckv = ckv_ref[0].astype(jnp.float32)                  # (bs, Dl)
        krope = krope_ref[0].astype(jnp.float32)              # (bs, Dr)
        if quantized:
            # dequant in-register: per-token-slot f32 scales DMA'd through
            # the same block-table index_map as the data block
            ckv = ckv * ckv_s_ref[0]                          # (bs, 1)
            krope = krope * krope_s_ref[0]
        # two-term scores on the split pool (no fused [ckv|krope] copy)
        s = (jax.lax.dot_general(q[:, :v_dim], ckv, (((1,), (1,)), ((), ())))
             + jax.lax.dot_general(q[:, v_dim:], krope,
                                   (((1,), (1,)), ((), ())))) * scale
        row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        c = iq * bq + row // H          # chunk-row index of each score row
        k_pos = j * bs + col            # absolute pool position
        mask = (k_pos <= start + c) & (c < nv)
        s = jnp.where(mask, s, NEG_INF)
        softmax_tile_update(s, mask, ckv, acc, m_sc, l_sc, rescale=rescale)

    @pl.when(j == nb - 1)
    def _done():
        l = l_sc[...]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc[...] / l_safe).reshape(bq, H, v_dim).astype(o_ref.dtype)


def mla_prefill_paged_kernel(q_full, ckv_pages, krope_pages, block_tables,
                             lengths, n_valid, *,
                             softmax_scale: Optional[float] = None,
                             block_q: int = 0,
                             ckv_scales=None, krope_scales=None,
                             rescale: str = "exp_add",
                             interpret: Optional[bool] = None):
    """Paged chunked-prefill flash attention over the latent block pool.

    q_full (B, C, H, Dl+Dr); ckv_pages (N, bs, Dl); krope_pages
    (N, bs, Dr); block_tables (B, nb) int32; lengths (B,) int32 —
    absolute position of each row's first chunk token; n_valid (B,)
    int32 — real tokens per row (0 = idle slot -> zero output rows).
    ``block_q``: query-tile rows (0 = as many as MAX_TILE_ROWS allows,
    at most the whole chunk; C is padded up to a tile multiple, pad rows
    return zeros).  Returns (B, C, H, Dl).

    Block tables, lengths and n_valid all ride the scalar-prefetch
    operand: the BlockSpec index_map dereferences ``block_tables[b, j]``
    so each grid step DMAs exactly one pool block HBM->VMEM — the
    single-stream property of the paged decode kernel, generalized to C
    causal query positions.

    For a QUANTIZED pool pass ``ckv_scales``/``krope_scales`` (N, bs, 1)
    f32 — dequant happens in-register per pool block.  ``rescale``
    selects the online-softmax correction: 'exp_add' (AMLA exponent
    addition, default) or 'mul' (classic FlashAttention).
    """
    B, C, H, D = q_full.shape
    v_dim, dr = ckv_pages.shape[-1], krope_pages.shape[-1]
    bs = ckv_pages.shape[1]
    nb = block_tables.shape[1]
    scale = softmax_scale if softmax_scale is not None else D ** -0.5
    quantized = ckv_scales is not None
    if quantized != (krope_scales is not None):
        raise ValueError("pass both ckv_scales and krope_scales or neither")
    if block_q <= 0:
        block_q = max(1, MAX_TILE_ROWS // H)
    bq = min(block_q, C)
    pad = -C % bq
    if pad:
        q_full = jnp.pad(q_full, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nq = q_full.shape[1] // bq
    kernel = functools.partial(_prefill_kernel, scale=scale, v_dim=v_dim,
                               bq=bq, H=H, bs=bs, nb=nb, rescale=rescale,
                               quantized=quantized)
    block_tables = jnp.asarray(block_tables, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    n_valid = jnp.asarray(n_valid, jnp.int32)
    in_specs = [
        pl.BlockSpec((1, bq, H, D),
                     lambda b, iq, j, bt, ln, nv: (b, iq, 0, 0)),
        pl.BlockSpec((1, bs, v_dim),
                     lambda b, iq, j, bt, ln, nv: (bt[b, j], 0, 0)),
        pl.BlockSpec((1, bs, dr),
                     lambda b, iq, j, bt, ln, nv: (bt[b, j], 0, 0)),
    ]
    operands = [block_tables, lengths, n_valid, q_full, ckv_pages,
                krope_pages]
    if quantized:
        in_specs += [
            pl.BlockSpec((1, bs, 1),
                         lambda b, iq, j, bt, ln, nv: (bt[b, j], 0, 0)),
            pl.BlockSpec((1, bs, 1),
                         lambda b, iq, j, bt, ln, nv: (bt[b, j], 0, 0)),
        ]
        operands += [ckv_scales, krope_scales]
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B, nq, nb),
            in_specs=in_specs,
            out_specs=pl.BlockSpec(
                (1, bq, H, v_dim),
                lambda b, iq, j, bt, ln, nv: (b, iq, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((bq * H, v_dim), jnp.float32),
                pltpu.VMEM((bq * H, 1), jnp.float32),
                pltpu.VMEM((bq * H, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, nq * bq, H, v_dim), q_full.dtype),
        interpret=resolve_interpret(interpret),
    )(*operands)
    return out[:, :C] if pad else out
