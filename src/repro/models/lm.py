"""Unified decoder-only LM covering 9 of the 10 assigned architectures
(whisper's encoder-decoder lives in whisper.py and reuses these blocks).

Layer stack = prefix (unrolled) + scanned periods (stacked weights) +
suffix (unrolled).  Scanning keeps the HLO — and 512-way GSPMD partitioning
time — independent of depth (granite-34b: 88 layers, one scanned body).

Public API (all pure):
    lm_defs(cfg)                                   param definitions
    forward(params, cfg, tokens, ...)   -> logits, aux       (train)
    prefill(params, cfg, tokens, ...)   -> last_logits, cache
    decode_step(params, cfg, token, cache, index, ...) -> logits, cache
    init_cache(cfg, batch, capacity, dtype)
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..nn import layers as nl
from ..nn import module as nnm
from .blocks import Ctx, ZERO_AUX, sub_apply, sub_cache, sub_defs
from .common import ModelConfig


# ------------------------------------------------------------------ defs ---


def lm_defs(cfg: ModelConfig) -> Dict:
    prefix, period, n_periods, suffix = cfg.layer_plan()
    d: Dict = {"embed": nl.embed_defs(cfg.vocab, cfg.d_model),
               "ln_f": nl.rmsnorm_defs(cfg.d_model)}
    d["prefix"] = {f"l{i}": sub_defs(cfg, desc, d_ff=cfg.first_dense_d_ff or None)
                   for i, desc in enumerate(prefix)}
    if n_periods:
        period_defs = {f"s{i}": sub_defs(cfg, desc) for i, desc in enumerate(period)}
        d["period"] = nnm.stack_defs(period_defs, n_periods, "layers")
    d["suffix"] = {f"l{i}": sub_defs(cfg, desc) for i, desc in enumerate(suffix)}
    return d


def param_count(cfg: ModelConfig) -> int:
    return nnm.count_params(lm_defs(cfg))


# ----------------------------------------------------------------- stack ---


def _tree_add(a, b):
    return jax.tree.map(lambda x, y: x + y, a, b)


def _zero_aux():
    return {k: jnp.asarray(v, jnp.float32) for k, v in ZERO_AUX.items()}


def _run_stack(params, cfg: ModelConfig, x, ctx: Ctx, caches: Optional[Dict]):
    """Returns (x, new_caches (same structure) or None, aux_sum)."""
    prefix, period, n_periods, suffix = cfg.layer_plan()
    aux_sum = _zero_aux()
    new_caches: Dict = {"prefix": {}, "suffix": {}}
    with_cache = ctx.mode != "train"

    for i, desc in enumerate(prefix):
        c = caches["prefix"][f"l{i}"] if caches else None
        x, nc, aux = sub_apply(params["prefix"][f"l{i}"], cfg, desc, x,
                               dataclasses.replace(ctx, cache=c))
        new_caches["prefix"][f"l{i}"] = nc
        aux_sum = _tree_add(aux_sum, aux)

    if n_periods:
        def body(x, slices):
            p_slice, c_slice = slices
            nc_period: Dict = {}
            aux_tot = _zero_aux()
            for i, desc in enumerate(period):
                c = c_slice[f"s{i}"] if c_slice is not None else None
                x, nc, aux = sub_apply(p_slice[f"s{i}"], cfg, desc, x,
                                       dataclasses.replace(ctx, cache=c))
                nc_period[f"s{i}"] = nc
                aux_tot = _tree_add(aux_tot, aux)
            return x, (nc_period, aux_tot)

        if ctx.mode == "train" and cfg.remat:
            body = jax.checkpoint(body)
        c_stacked = caches["period"] if caches else None
        xs = (params["period"], c_stacked)
        x, (nc_stacked, auxs) = jax.lax.scan(body, x, xs)
        if with_cache:
            new_caches["period"] = nc_stacked
        aux_sum = _tree_add(aux_sum, jax.tree.map(jnp.sum, auxs))

    for i, desc in enumerate(suffix):
        c = caches["suffix"][f"l{i}"] if caches else None
        x, nc, aux = sub_apply(params["suffix"][f"l{i}"], cfg, desc, x,
                               dataclasses.replace(ctx, cache=c))
        new_caches["suffix"][f"l{i}"] = nc
        aux_sum = _tree_add(aux_sum, aux)

    return x, (new_caches if with_cache else None), aux_sum


def _embed(params, cfg: ModelConfig, tokens, embeds, dtype):
    x = nl.embed(params["embed"], tokens, dtype)
    if cfg.embed_scale:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), dtype)
    if embeds is not None:
        x = jnp.concatenate([embeds.astype(dtype), x], axis=1)
    return x


def _logits(params, cfg: ModelConfig, x, out_dtype=None):
    x = nl.rmsnorm(params["ln_f"], x, cfg.norm_eps)
    return nl.unembed(params["embed"], x, out_dtype)


def _serve_logits(params, cfg: ModelConfig, x):
    """Logits a token is sampled from, always f32.  Rounded to bf16, the
    top two of a 100k vocabulary often tie, and XLA may skip that rounding
    where it fuses the argmax into the step (the async engine) but not
    where the logits leave it (the sync engine): the two would then pick
    different tokens."""
    return _logits(params, cfg, x, jnp.float32)


# ------------------------------------------------------------ public API ---


def forward(params, cfg: ModelConfig, tokens, *, embeds=None,
            compute_dtype=jnp.bfloat16, impl: str = "ref", mesh=None,
            scheme: str = "seq", return_hidden: bool = False
            ) -> Tuple[jax.Array, Dict]:
    """Training forward. tokens: (B, L_text); embeds: (B, P, D) stub
    modality prefix (vlm/audio). Returns (logits (B, L, V), aux); with
    ``return_hidden`` the final-norm hidden states (B, L, D) instead of
    logits (vocab-chunked loss does its own unembed — see runtime.steps)."""
    x = _embed(params, cfg, tokens, embeds, compute_dtype)
    B, L, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(L), (B, L))
    ctx = Ctx(mode="train", positions=positions, impl=impl, mesh=mesh,
              scheme=scheme)
    x, _, aux = _run_stack(params, cfg, x, ctx, None)
    if return_hidden:
        return nl.rmsnorm(params["ln_f"], x, cfg.norm_eps), aux
    return _logits(params, cfg, x), aux


def prefill(params, cfg: ModelConfig, tokens, *, embeds=None, capacity: int = 0,
            compute_dtype=jnp.bfloat16, impl: str = "ref", mesh=None,
            scheme: str = "seq", shard_mode: str = "train"
            ) -> Tuple[jax.Array, Dict]:
    """Returns (last-token logits (B, V), cache filled with L entries)."""
    x = _embed(params, cfg, tokens, embeds, compute_dtype)
    B, L, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(L), (B, L))
    ctx = Ctx(mode="prefill", positions=positions, impl=impl, mesh=mesh,
              scheme=scheme, capacity=capacity or L, shard_mode=shard_mode)
    x, caches, _ = _run_stack(params, cfg, x, ctx, None)
    return _serve_logits(params, cfg, x[:, -1]), caches


def prefill_chunk_paged(params, cfg: ModelConfig, tokens, pool,
                        block_tables, lengths, n_valid, *,
                        compute_dtype=jnp.bfloat16, impl: str = "ref",
                        mesh=None, scheme: str = "seq",
                        shard_mode: str = "serve") -> Tuple[jax.Array, Dict]:
    """One batched prefill CHUNK straight into the paged pool.

    tokens: (B, C) int32 — row b holds its request's next ``n_valid[b]``
    prompt tokens (rest is padding), starting at absolute position
    ``lengths[b]`` (tokens already resident: prefix-cache hits + earlier
    chunks).  Returns (logits (B, V) of each row's LAST VALID position,
    new_pool) — the logits row matters only for the chunk that finishes a
    request's prompt (it samples generated token #1); other rows are
    discarded by the engine.  One compiled shape per (B, C), independent
    of prompt length — the whole point vs the per-plen retraces of the
    contiguous prefill.

    ``impl`` 'kernel' / 'pallas' routes the chunk attention through the
    fused paged Pallas prefill kernel (kernels.mla_prefill): the block
    table is walked in place, no contiguous (B, S) gather of the pool is
    materialized.  'ref' keeps the gather reference path.  With ``mesh``
    the kernel path runs under shard_map (batch over DP, heads over
    'model', pool replicated — kernels.ops.mla_prefill_paged_attention);
    the gather path is partitioned by GSPMD."""
    x, caches = _chunk_paged_hidden(params, cfg, tokens, pool, block_tables,
                                    lengths, n_valid,
                                    compute_dtype=compute_dtype, impl=impl,
                                    mesh=mesh, scheme=scheme,
                                    shard_mode=shard_mode)
    B = x.shape[0]
    last = jnp.maximum(jnp.asarray(n_valid, jnp.int32) - 1, 0)
    h = x[jnp.arange(B), last]                    # (B, D) last valid hidden
    return _serve_logits(params, cfg, h), caches


def verify_chunk_paged(params, cfg: ModelConfig, tokens, pool,
                       block_tables, lengths, n_valid, *,
                       compute_dtype=jnp.bfloat16, impl: str = "ref",
                       mesh=None, scheme: str = "seq",
                       shard_mode: str = "serve") -> Tuple[jax.Array, Dict]:
    """Multi-token VERIFY step for speculative decoding: the chunked
    paged prefill with logits at EVERY chunk position.

    tokens: (B, C) int32 — row b carries [last sampled token, draft_1 ..
    draft_{n_valid[b]-1}] at absolute positions lengths[b].. (the verify
    window; C = k + 1).  Same attention math and same pool scatter as
    :func:`prefill_chunk_paged` — scoring k + 1 positions re-reads each
    request's resident latent prefix exactly ONCE, which is the cache-read
    amortization speculative decoding exists for (hwmodel.attention_costs
    .mla_verify_cost) — but the head returns (B, C, V): position j's
    logits row is the target's next-token distribution after draft j,
    which the engine samples with the same fold(rid, position) keys plain
    decode uses, so accepted streams are token-identical to plain decode.
    Rows/positions past ``n_valid`` scatter to the null block and their
    logits are garbage the engine never reads."""
    x, caches = _chunk_paged_hidden(params, cfg, tokens, pool, block_tables,
                                    lengths, n_valid,
                                    compute_dtype=compute_dtype, impl=impl,
                                    mesh=mesh, scheme=scheme,
                                    shard_mode=shard_mode)
    return _serve_logits(params, cfg, x), caches


def _chunk_paged_hidden(params, cfg: ModelConfig, tokens, pool,
                        block_tables, lengths, n_valid, *,
                        compute_dtype, impl, mesh, scheme, shard_mode):
    """Shared body of prefill_chunk_paged / verify_chunk_paged: run one
    (B, C) chunk through the stack against the paged pool; returns the
    pre-norm hidden states (B, C, D) and the updated pool."""
    x = _embed(params, cfg, tokens, None, compute_dtype)
    ctx = Ctx(mode="prefill_chunk", positions=None, impl=impl, mesh=mesh,
              scheme=scheme, shard_mode=shard_mode,
              block_tables=block_tables, lengths=lengths, n_valid=n_valid)
    return _run_stack(params, cfg, x, ctx, pool)[:2]


def decode_step(params, cfg: ModelConfig, token, cache, index, *,
                compute_dtype=jnp.bfloat16, impl: str = "ref", mesh=None,
                scheme: str = "seq", shard_mode: str = "train",
                block_tables=None, lengths=None) -> Tuple[jax.Array, Dict]:
    """token: (B,) int32; index: scalar (current cache length).
    Returns (logits (B, V), updated cache).

    Paged continuous-batching decode: pass ``lengths`` (B,) int32 ragged
    per-request cache lengths and ``block_tables`` (B, max_blocks) with a
    paged ``cache`` tree (see init_paged_cache); ``index`` is ignored."""
    x = _embed(params, cfg, token[:, None], None, compute_dtype)[:, 0]
    ctx = Ctx(mode="decode", positions=None, index=index, impl=impl,
              mesh=mesh, scheme=scheme, shard_mode=shard_mode,
              block_tables=block_tables, lengths=lengths)
    x, caches, _ = _run_stack(params, cfg, x, ctx, cache)
    return _serve_logits(params, cfg, x), caches


def init_cache(cfg: ModelConfig, batch: int, capacity: int,
               dtype=jnp.bfloat16) -> Dict:
    prefix, period, n_periods, suffix = cfg.layer_plan()
    out: Dict = {
        "prefix": {f"l{i}": sub_cache(cfg, d, batch, capacity, dtype)
                   for i, d in enumerate(prefix)},
        "suffix": {f"l{i}": sub_cache(cfg, d, batch, capacity, dtype)
                   for i, d in enumerate(suffix)},
    }
    if n_periods:
        one = {f"s{i}": sub_cache(cfg, d, batch, capacity, dtype)
               for i, d in enumerate(period)}
        out["period"] = jax.tree.map(
            lambda a: jnp.tile(a[None], (n_periods,) + (1,) * a.ndim), one)
    return out


def init_paged_cache(cfg: ModelConfig, num_blocks: int, block_size: int,
                     dtype=jnp.bfloat16, cache_dtype=None) -> Dict:
    """Paged decode-state tree: same layer structure as init_cache but every
    MLA latent cache is a (num_blocks, block_size, .) block pool shared by
    all requests.  Block tables / lengths live OUTSIDE this tree (one table
    per request, shared across layers) and are passed to decode_step.
    ``cache_dtype`` in {int8, fp8} quantizes every pool (per-token-slot
    scale leaves ride the tree — see core.cache.paged_latent_cache)."""
    from .blocks import sub_paged_cache
    prefix, period, n_periods, suffix = cfg.layer_plan()
    out: Dict = {
        "prefix": {f"l{i}": sub_paged_cache(cfg, d, num_blocks, block_size,
                                            dtype, cache_dtype)
                   for i, d in enumerate(prefix)},
        "suffix": {f"l{i}": sub_paged_cache(cfg, d, num_blocks, block_size,
                                            dtype, cache_dtype)
                   for i, d in enumerate(suffix)},
    }
    if n_periods:
        one = {f"s{i}": sub_paged_cache(cfg, d, num_blocks, block_size,
                                        dtype, cache_dtype)
               for i, d in enumerate(period)}
        out["period"] = jax.tree.map(
            lambda a: jnp.tile(a[None], (n_periods,) + (1,) * a.ndim), one)
    return out
