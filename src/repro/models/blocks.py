"""Sublayer bodies: (pre-norm mixer) + (pre-norm FFN), dispatched on the
``Sub`` descriptor.  One uniform interface:

    sub_defs(cfg, desc)                          -> param defs
    sub_apply(params, cfg, desc, x, ctx)         -> (x, new_cache)
    sub_cache(cfg, desc, batch, capacity, dtype) -> cache pytree ({} if none)

``ctx`` carries mode ('train'|'prefill'|'decode'), positions, cache slice,
decode index, attention impl ('ref'|'kernel'), mesh, and the MLA execution
scheme — the paper's runtime-selectable feature threads through here.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..core import cache as cachelib
from ..core import mla as mlalib
from ..core.attention import gqa_attention, gqa_decode
from ..core.chunked_attention import chunked_attention_pairs
from ..kernels import ops as kops
from ..nn import layers as nl
from ..nn.module import P
from . import mamba as mambalib
from . import moe as moelib
from . import xlstm as xlstmlib
from .common import ModelConfig, Sub


@dataclasses.dataclass
class Ctx:
    mode: str                       # train | prefill | prefill_chunk | decode
    positions: Optional[jax.Array]  # (B, L) for train/prefill
    index: Any = None               # decode position (traced scalar)
    cache: Optional[Dict] = None    # this sublayer's cache slice
    impl: str = "ref"               # attention impl
    mesh: Any = None
    scheme: str = "seq"             # MLA execution scheme
    capacity: int = 0               # cache capacity for prefill
    shard_mode: str = "train"       # sharding policy (see nn.sharding)
    # Paged continuous batching (MLA only): when ``lengths`` is set the
    # cache slice is a paged pool and ``index`` is unused.  Decode feeds
    # one token per slot; mode 'prefill_chunk' feeds a (B, C) chunk of
    # prompt tokens with ``n_valid`` real tokens per row, scattered into
    # the pool at positions lengths[b]..lengths[b]+n_valid[b]-1.
    block_tables: Any = None        # (B, max_blocks) int32
    lengths: Any = None             # (B,) int32 — ragged per-request
    n_valid: Any = None             # (B,) int32 — chunked prefill only


def _paged_valid(ctx: Ctx, h):
    """The real tokens of a paged serving step, for the MoE: a decode
    row with a cache (length > 0: empty slots and rows still prefilling
    carry 0), a chunk or verify position below its row's ``n_valid``.
    None outside paged steps, where every token is real."""
    if ctx.lengths is None:
        return None
    if ctx.n_valid is None:
        return jnp.asarray(ctx.lengths) > 0
    return jnp.arange(h.shape[1])[None, :] < jnp.asarray(ctx.n_valid)[:, None]


# ------------------------------------------------------------------ defs ---


def _attn_defs(cfg: ModelConfig) -> Dict:
    if cfg.attn_kind == "mla":
        return mlalib.mla_defs(cfg.mla_config())
    D, H, Hkv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    return {
        "w_q": P((D, H, dh), ("embed", "heads", None)),
        "w_k": P((D, Hkv, dh), ("embed", "kv_heads", None)),
        "w_v": P((D, Hkv, dh), ("embed", "kv_heads", None)),
        "w_o": P((H, dh, D), ("heads", None, "embed")),
    }


def sub_defs(cfg: ModelConfig, desc: Sub, d_ff: Optional[int] = None) -> Dict:
    d: Dict = {"ln1": nl.rmsnorm_defs(cfg.d_model)}
    if desc.mixer == "attn":
        d["attn"] = _attn_defs(cfg)
    elif desc.mixer == "mamba":
        d["attn"] = mambalib.mamba_defs(cfg)
    elif desc.mixer == "mlstm":
        d["attn"] = xlstmlib.mlstm_defs(cfg)
    elif desc.mixer == "slstm":
        d["attn"] = xlstmlib.slstm_defs(cfg)
    else:
        raise ValueError(desc.mixer)
    if desc.ffn != "none":
        d["ln2"] = nl.rmsnorm_defs(cfg.d_model)
        if desc.ffn == "moe":
            d["ffn"] = moelib.moe_defs(cfg)
        else:
            d["ffn"] = nl.mlp_defs(cfg.d_model, d_ff or cfg.d_ff, kind=cfg.mlp_kind)
    return d


def sub_cache(cfg: ModelConfig, desc: Sub, batch: int, capacity: int,
              dtype=jnp.bfloat16) -> Dict:
    if desc.mixer == "attn":
        if cfg.attn_kind == "mla":
            return cachelib.latent_cache(batch, capacity, cfg.kv_lora_rank,
                                         cfg.qk_rope_dim, dtype)
        eff_cap = capacity if desc.window is None else min(capacity, cfg.max_seq)
        return cachelib.kv_cache(batch, eff_cap, cfg.n_kv_heads,
                                 cfg.resolved_head_dim, dtype)
    if desc.mixer == "mamba":
        return mambalib.mamba_state_init(cfg, batch, dtype)
    if desc.mixer == "mlstm":
        return xlstmlib.mlstm_state_init(cfg, batch)
    if desc.mixer == "slstm":
        return xlstmlib.slstm_state_init(cfg, batch)
    return {}


def sub_paged_cache(cfg: ModelConfig, desc: Sub, num_blocks: int,
                    block_size: int, dtype=jnp.bfloat16,
                    cache_dtype=None) -> Dict:
    """Paged decode-state for one sublayer.  Only MLA latent caches page
    (the paper's compact cache is what makes a shared block pool pay off);
    other mixers raise — serve those models through the contiguous path.
    ``cache_dtype`` in {int8, fp8} stores the pool quantized with
    per-token-slot scale leaves (core.cache)."""
    if desc.mixer == "attn" and cfg.attn_kind == "mla":
        return cachelib.paged_latent_cache(num_blocks, block_size,
                                           cfg.kv_lora_rank,
                                           cfg.qk_rope_dim, dtype,
                                           cache_dtype=cache_dtype)
    raise NotImplementedError(
        f"paged serving requires MLA attention sublayers, got "
        f"mixer={desc.mixer!r} attn_kind={cfg.attn_kind!r}")


# ------------------------------------------------------------- attention ---


def _gqa_padding(H: int, Hkv: int, model: int):
    """Function-preserving GQA head padding to align with the 'model' mesh
    axis (EXPERIMENTS.md §Perf B1).

    When H % model != 0 the attention activations cannot shard over the
    TP axis and every chip computes ALL heads (measured 12x compute waste
    on starcoder2-7b train_4k, whose 36 heads do not divide a 16-way
    axis).  Pad: replicate each kv head ``rep`` times (Hkv*rep % model ==
    0) and scatter the q heads into H_pad = Hkv*rep*ceil(q_per_kv/rep)
    slots so that slot s attends kv_pad[s // G_pad] == its original kv
    head.  Unused slots carry zero queries and their outputs are dropped,
    so forward AND backward are exactly preserved.

    Returns (src_idx (H_pad,), slot_of_head (H,), rep) or None.
    """
    if model <= 1 or H % model == 0:
        return None
    q_per_kv = H // Hkv
    rep = 1
    while (Hkv * rep) % model:
        rep += 1
    g_pad = -(-q_per_kv // rep)             # ceil
    h_pad = Hkv * rep * g_pad
    slot_of_head = np.array([(h // q_per_kv) * rep * g_pad + (h % q_per_kv)
                             for h in range(H)])
    src_idx = np.zeros(h_pad, dtype=np.int32)
    src_idx[slot_of_head] = np.arange(H)
    mask = np.zeros(h_pad, dtype=np.float32)
    mask[slot_of_head] = 1.0
    return src_idx, slot_of_head, mask, rep


def _dp_axes_of(mesh):
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    return dp if len(dp) > 1 else (dp[0] if dp else None)


def _gqa_seq(params, cfg: ModelConfig, desc: Sub, x, ctx: Ctx):
    """Train/prefill GQA path. x: (B, L, D) normalized input."""
    B, L, _ = x.shape
    dh = cfg.resolved_head_dim
    q = jnp.einsum("bld,dhk->blhk", x, params["w_q"].astype(x.dtype))
    k = jnp.einsum("bld,dhk->blhk", x, params["w_k"].astype(x.dtype))
    v = jnp.einsum("bld,dhk->blhk", x, params["w_v"].astype(x.dtype))
    q = nl.apply_rope(q, ctx.positions, desc.rope_base)
    k = nl.apply_rope(k, ctx.positions, desc.rope_base)
    pad = None
    if ctx.mesh is not None and ctx.impl == "chunked":
        model = dict(zip(ctx.mesh.axis_names, ctx.mesh.devices.shape)
                     ).get("model", 1)
        pad = _gqa_padding(cfg.n_heads, cfg.n_kv_heads, model)
    if pad is not None:
        src_idx, slot_of_head, mask, rep = pad
        from jax.sharding import NamedSharding, PartitionSpec as PS
        dp = _dp_axes_of(ctx.mesh)
        cons = lambda a: jax.lax.with_sharding_constraint(
            a, NamedSharding(ctx.mesh, PS(dp, None, "model", None)))
        q_pad = cons(jnp.take(q, src_idx, axis=2)
                     * jnp.asarray(mask, x.dtype)[None, None, :, None])
        k_pad = cons(jnp.repeat(k, rep, axis=2))
        v_pad = cons(jnp.repeat(v, rep, axis=2))
        o_pad = chunked_attention_pairs(q_pad, k_pad, v_pad, desc.causal,
                                        desc.window, 0, None)
        o = jnp.take(o_pad, slot_of_head, axis=2)
    elif ctx.impl == "kernel":
        o = kops.attention(q.swapaxes(1, 2), k.swapaxes(1, 2), v.swapaxes(1, 2),
                           impl="kernel", causal=desc.causal, window=desc.window,
                           mesh=ctx.mesh).swapaxes(1, 2)
    elif ctx.impl == "chunked":
        o = chunked_attention_pairs(q, k, v, desc.causal, desc.window, 0, None)
    else:
        o = gqa_attention(q, k, v, causal=desc.causal, window=desc.window,
                          q_positions=ctx.positions[0], k_positions=ctx.positions[0])
    out = jnp.einsum("blhk,hkd->bld", o, params["w_o"].astype(x.dtype))
    new_cache = None
    if ctx.mode == "prefill":
        cap = ctx.capacity or L
        kc = jnp.zeros((B, cap, cfg.n_kv_heads, dh), x.dtype)
        vc = jnp.zeros((B, cap, cfg.n_kv_heads, dh), x.dtype)
        new_cache = cachelib.update_kv({"k": kc, "v": vc}, k, v, 0)
    return out, new_cache


def _gqa_step(params, cfg: ModelConfig, desc: Sub, x_t, ctx: Ctx):
    """Decode. x_t: (B, D) normalized input."""
    B, _ = x_t.shape
    pos = jnp.full((B, 1), ctx.index, dtype=jnp.int32)
    q = jnp.einsum("bd,dhk->bhk", x_t, params["w_q"].astype(x_t.dtype))
    k = jnp.einsum("bd,dhk->bhk", x_t, params["w_k"].astype(x_t.dtype))
    v = jnp.einsum("bd,dhk->bhk", x_t, params["w_v"].astype(x_t.dtype))
    q = nl.apply_rope(q[:, None], pos, desc.rope_base)[:, 0]
    k = nl.apply_rope(k[:, None], pos, desc.rope_base)[:, 0]
    cache = cachelib.update_kv(ctx.cache, k[:, None], v[:, None], ctx.index)
    o = gqa_decode(q, cache["k"], cache["v"], ctx.index, window=desc.window)
    out = jnp.einsum("bhk,hkd->bd", o, params["w_o"].astype(x_t.dtype))
    return out, cache


def _mla_seq(params, cfg: ModelConfig, desc: Sub, x, ctx: Ctx):
    mcfg = cfg.mla_config()
    attn_fn = None
    if ctx.impl == "kernel":
        def attn_fn(q, k, v, softmax_scale):
            return kops.attention(
                q.swapaxes(1, 2), k.swapaxes(1, 2), v.swapaxes(1, 2),
                impl="kernel", causal=desc.causal, window=desc.window,
                softmax_scale=softmax_scale, mesh=ctx.mesh).swapaxes(1, 2)
    elif ctx.impl == "chunked":
        def attn_fn(q, k, v, softmax_scale):
            return chunked_attention_pairs(q, k, v, desc.causal, desc.window,
                                           0, softmax_scale)
    out, entries = mlalib.mla_prefill(params, mcfg, x, ctx.positions,
                                      attn_fn=attn_fn,
                                      return_cache=ctx.mode == "prefill")
    new_cache = None
    if ctx.mode == "prefill":
        B, L, _ = x.shape
        cap = ctx.capacity or L
        new_cache = cachelib.update_latent(
            cachelib.latent_cache(B, cap, mcfg.kv_lora_rank,
                                  mcfg.qk_rope_dim, x.dtype),
            entries["ckv"], entries["krope"], 0)
    return out, new_cache


def _mla_step(params, cfg: ModelConfig, desc: Sub, x_t, ctx: Ctx):
    mcfg = cfg.mla_config()
    if ctx.lengths is not None:     # paged continuous-batching decode
        decode_kernel = None
        if ctx.impl in ("kernel", "pallas"):
            def decode_kernel(q_full, ckv, krope, tables, idx, softmax_scale,
                              **qkw):
                return kops.mla_decode_paged_attention(
                    q_full, ckv, krope, tables, idx, impl="kernel",
                    softmax_scale=softmax_scale, mesh=ctx.mesh, **qkw)
        return mlalib.mla_decode_paged(params, mcfg, x_t, ctx.cache,
                                       ctx.block_tables, ctx.lengths,
                                       scheme=ctx.scheme,
                                       decode_kernel=decode_kernel)
    decode_kernel = None
    if ctx.impl in ("kernel", "pallas"):
        def decode_kernel(q_full, ckv, krope, index, softmax_scale):
            return kops.mla_decode_attention(
                q_full, ckv, krope, index, impl="kernel",
                softmax_scale=softmax_scale, mesh=ctx.mesh)
    return mlalib.mla_decode(params, mcfg, x_t, ctx.cache, ctx.index,
                             scheme=ctx.scheme, decode_kernel=decode_kernel)


def _mla_chunk(params, cfg: ModelConfig, desc: Sub, x, ctx: Ctx):
    """Batched chunked prefill into the paged pool (mode 'prefill_chunk').
    x: (B, C, D) normalized chunk; the shared prefix is attended through
    the block table — see core.mla.mla_prefill_chunk_paged.  With
    ctx.impl 'kernel'/'pallas' the fused paged Pallas prefill kernel
    (kernels.mla_prefill) replaces the materialized block-table gather."""
    prefill_kernel, impl = None, "gather"
    if ctx.impl in ("kernel", "pallas"):
        impl = "pallas"

        def prefill_kernel(q_full, ckv, krope, tables, lens, nv,
                           softmax_scale, **qkw):
            return kops.mla_prefill_paged_attention(
                q_full, ckv, krope, tables, lens, nv, impl="kernel",
                softmax_scale=softmax_scale, mesh=ctx.mesh, **qkw)
    return mlalib.mla_prefill_chunk_paged(params, cfg.mla_config(), x,
                                          ctx.cache, ctx.block_tables,
                                          ctx.lengths, ctx.n_valid,
                                          scheme=ctx.scheme, impl=impl,
                                          prefill_kernel=prefill_kernel)


def _slstm_sharded(params, cfg: ModelConfig, x, ctx: Ctx):
    """sLSTM under shard_map over the DP axes (EXPERIMENTS.md §Perf C2).

    Under plain GSPMD autodiff, the gradient of the recurrent weights
    ``rh`` is all-reduced across the data axis INSIDE the backward BPTT
    scan — once per time step (measured: a 16.8 MB all-reduce firing
    12,288 times = 387 GB/chip/step on xlstm-350m train_4k).  Inside
    shard_map the scan runs on the local batch shard with replicated
    weights, and the weight-gradient psum happens ONCE at the shard_map
    boundary."""
    train_like = ctx.mode in ("train", "prefill")
    if ctx.mesh is None or not train_like or x.ndim != 3:
        return xlstmlib.slstm_forward(params, cfg, x,
                                      return_state=ctx.mode == "prefill")
    sizes = dict(zip(ctx.mesh.axis_names, ctx.mesh.devices.shape))
    dp_axes = tuple(a for a in ("pod", "data") if a in ctx.mesh.axis_names)
    dp_size = 1
    for a in dp_axes:
        dp_size *= sizes[a]
    if not dp_axes or x.shape[0] % dp_size:
        return xlstmlib.slstm_forward(params, cfg, x,
                                      return_state=ctx.mode == "prefill")
    from jax.sharding import PartitionSpec as PS
    dp = dp_axes if len(dp_axes) > 1 else dp_axes[0]
    return_state = ctx.mode == "prefill"

    def local(p, xl):
        out, state = xlstmlib.slstm_forward(p, cfg, xl,
                                            return_state=return_state)
        return out, (state if return_state else {})

    pspecs = jax.tree.map(lambda _: PS(), params)
    state_specs = {k: PS(dp, None) for k in ("h", "c", "n", "m")} \
        if return_state else {}
    out, state = jax.shard_map(
        local, mesh=ctx.mesh,
        in_specs=(pspecs, PS(dp, None, None)),
        out_specs=(PS(dp, None, None), state_specs),
        check_vma=False,
    )(params, x)
    return out, (state if return_state else None)


# ---------------------------------------------------------------- apply ----


ZERO_AUX = {"balance": 0.0, "z_loss": 0.0, "dropped_frac": 0.0}


# GSPMD sequence parallelism (§Perf B3) — DEFAULT OFF.  Measured outcome on
# starcoder2-7b train_4k: compute -33%, memory -27%, temp HBM -80%, but the
# COLLECTIVE term (the cell's new bottleneck) grew +12% because GSPMD kept
# lowering the row-parallel output reductions as all-reduce instead of
# reduce-scatter around the constraint boundary.  Hypothesis refuted as a
# net win at this cell; retained for memory-limited configs (temp 55.9 ->
# 11.0 GiB is the difference between fitting and not fitting at seq 8k+).
SEQ_PARALLEL = False


def _seq_parallel_constraint(x, ctx: Ctx, *, on: bool = True):
    """Sequence parallelism, GSPMD-style (EXPERIMENTS.md §Perf B3): pin the
    residual stream's SEQ dim to the 'model' axis between sublayers, so
    norms/elementwise run on 1/model of the tokens.

    ``on=False`` releases the constraint (Megatron SP's pre-attention
    all-gather): transitioning a seq-sharded tensor directly into the
    head-sharded QKV layout makes GSPMD fall back to full
    rematerialization (measured +2s collective on starcoder2 train_4k);
    gathering the sequence FIRST makes the head shard a free slice."""
    if not SEQ_PARALLEL or ctx.mesh is None or ctx.mode != "train" \
            or x.ndim != 3:
        return x
    sizes = dict(zip(ctx.mesh.axis_names, ctx.mesh.devices.shape))
    if x.shape[1] % sizes.get("model", 1):
        return x
    from jax.sharding import NamedSharding, PartitionSpec as PS
    spec = PS(_dp_axes_of(ctx.mesh), "model" if on else None, None)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(ctx.mesh, spec))


def sub_apply(params, cfg: ModelConfig, desc: Sub, x, ctx: Ctx):
    """x: (B, L, D) for train/prefill, (B, D) for decode.
    Returns (x, new_cache, aux) — aux has a FIXED structure (zeros when the
    sublayer has no router) so it can thread through lax.scan ys."""
    sp = desc.mixer == "attn"   # SSM scans iterate the seq dim: keep whole
    x = _seq_parallel_constraint(x, ctx, on=sp)
    h = nl.rmsnorm(params["ln1"], x, cfg.norm_eps)
    if sp:
        # Megatron-SP boundary: gather the sequence before the QKV
        # projection (head sharding then becomes a free slice).
        h = _seq_parallel_constraint(h, ctx, on=False)
    if ctx.mode == "prefill_chunk" and \
            not (desc.mixer == "attn" and cfg.attn_kind == "mla"):
        raise NotImplementedError(
            "chunked paged prefill requires MLA attention sublayers")
    if desc.mixer == "attn":
        if cfg.attn_kind == "mla":
            fn = {"decode": _mla_step,
                  "prefill_chunk": _mla_chunk}.get(ctx.mode, _mla_seq)
            # named scopes group the device ops by layer in a profile
            with jax.named_scope("mla_attention"):
                a, new_cache = fn(params["attn"], cfg, desc, h, ctx)
        else:
            fn = _gqa_step if ctx.mode == "decode" else _gqa_seq
            a, new_cache = fn(params["attn"], cfg, desc, h, ctx)
    elif desc.mixer == "mamba":
        if ctx.mode == "decode":
            a, new_cache = mambalib.mamba_step(params["attn"], cfg, h, ctx.cache)
        else:
            a, new_cache = mambalib.mamba_forward(
                params["attn"], cfg, h, return_state=ctx.mode == "prefill")
    elif desc.mixer == "mlstm":
        if ctx.mode == "decode":
            a, new_cache = xlstmlib.mlstm_step(params["attn"], cfg, h, ctx.cache)
        else:
            a, new_cache = xlstmlib.mlstm_forward(
                params["attn"], cfg, h, return_state=ctx.mode == "prefill")
    elif desc.mixer == "slstm":
        if ctx.mode == "decode":
            a, new_cache = xlstmlib.slstm_step(params["attn"], cfg, h, ctx.cache)
        else:
            a, new_cache = _slstm_sharded(params["attn"], cfg, h, ctx)
    else:
        raise ValueError(desc.mixer)
    x = x + a

    aux = {k: jnp.asarray(v, jnp.float32) for k, v in ZERO_AUX.items()}
    if desc.ffn != "none":
        h = nl.rmsnorm(params["ln2"], x, cfg.norm_eps)
        if desc.ffn == "moe":
            f, aux = moelib.moe_apply(params["ffn"], cfg, h, mesh=ctx.mesh,
                                      shard_mode=ctx.shard_mode,
                                      valid=_paged_valid(ctx, h))
            aux = {k: jnp.asarray(aux[k], jnp.float32) for k in ZERO_AUX}
        else:
            f = nl.mlp(params["ffn"], h, kind=cfg.mlp_kind)
        x = x + f
    return x, (new_cache if new_cache is not None else {}), aux
