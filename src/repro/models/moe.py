"""Mixture-of-Experts FFN with expert parallelism (EP over 'model').

Dispatch strategy (see DESIGN.md §7): activations are replicated across the
'model' axis at the MoE boundary (they already are, post-attention
all-reduce), so each EP rank *locally selects* the tokens routed to its own
expert shard — no all-to-all is required; the outputs are combined by the
same psum a row-parallel FFN would need anyway.  Sort-based position
assignment (argsort over expert ids) avoids materializing the (T, E, C)
one-hot dispatch tensor of the GShard formulation, which at
T=32k, E=160, C=1.5k would be ~16 GB/device.

Capacity: C = ceil(T_local * top_k / E * capacity_factor); overflow tokens
are dropped (standard token-choice semantics).  Padding tokens of a
serving step (``valid`` False: empty slots, chunk positions past a row's
prompt) take no capacity, so they never displace a real token.  Router aux losses
(load-balance + z-loss) are returned for the trainer.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..nn import layers as nl
from ..nn.module import P
from .common import ModelConfig


def moe_defs(cfg: ModelConfig) -> Dict:
    E, D, F = cfg.n_experts, cfg.d_model, cfg.moe_d_ff
    # 'expert_mlp' on F: replicated under the train rules (EP over 'model'
    # suffices); 2D-sharded (experts x F) under serve_2dtp so the expert
    # bank stays resident at decode (EXPERIMENTS.md §Perf A1).
    d: Dict = {
        "router": P((D, E), ("embed", None), init="normal", scale=0.02),
        "gate_up": P((E, D, 2, F), ("experts", "embed", None, "expert_mlp")),
        "down": P((E, F, D), ("experts", "expert_mlp", "embed")),
    }
    if cfg.n_shared_experts:
        d["shared"] = nl.mlp_defs(D, cfg.n_shared_experts * F, kind="swiglu")
    return d


def _capacity(t_local: int, cfg: ModelConfig) -> int:
    return max(4, int(math.ceil(t_local * cfg.top_k / cfg.n_experts
                                * cfg.capacity_factor)))


def _moe_local(x, valid, router_w, gate_up, down, *, cfg: ModelConfig,
               model_axis: Optional[str], f_axis: Optional[str] = None):
    """x: (T, D) local tokens (replicated over model axis), valid: (T,)
    bool; gate_up/down are the LOCAL expert shard (possibly also F-sharded
    over ``f_axis`` in the serve_2dtp layout). Returns (out (T,D)
    partial-summed, aux dict)."""
    T, D = x.shape
    E, k = cfg.n_experts, cfg.top_k
    E_local = gate_up.shape[0]
    C = _capacity(T, cfg)

    with jax.named_scope("moe_router"):
        logits = (x @ router_w.astype(x.dtype)).astype(jnp.float32)   # (T, E)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, sel = jax.lax.top_k(probs, k)                      # (T, k)
        gate_vals = gate_vals / jnp.sum(gate_vals, axis=-1, keepdims=True)

        # ---- aux losses (load balance + z-loss) ---------------------------
        density = jnp.mean(jax.nn.one_hot(sel, E, dtype=jnp.float32), axis=(0, 1))
        balance = E * jnp.sum(density * jnp.mean(probs, axis=0)) * k
        z_loss = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)

        # ---- sort-based slot assignment ------------------------------------
        # padding routes to expert E, past every real expert's segment
        flat_e = jnp.where(jnp.repeat(valid, k), sel.reshape(-1), E)  # (T*k,)
        flat_t = jnp.repeat(jnp.arange(T), k)
        flat_g = gate_vals.reshape(-1)
        order = jnp.argsort(flat_e)
        se, st, sg = flat_e[order], flat_t[order], flat_g[order]
        seg_start = jnp.searchsorted(se, jnp.arange(E))
        pos = jnp.arange(T * k) - seg_start[se]
        keep = (pos < C) & (se < E)

        tok_tbl = jnp.full((E, C), T, jnp.int32)                      # T = pad row
        tok_tbl = tok_tbl.at[se, pos].set(jnp.where(keep, st, T), mode="drop")
        gate_tbl = jnp.zeros((E, C), jnp.float32)
        gate_tbl = gate_tbl.at[se, pos].set(jnp.where(keep, sg, 0.0), mode="drop")

    with jax.named_scope("moe_routed_experts"):
        # local expert shard of the tables
        r = jax.lax.axis_index(model_axis) if model_axis else 0
        tok_loc = jax.lax.dynamic_slice_in_dim(tok_tbl, r * E_local, E_local, 0)
        gate_loc = jax.lax.dynamic_slice_in_dim(gate_tbl, r * E_local, E_local, 0)

        x_pad = jnp.concatenate([x, jnp.zeros((1, D), x.dtype)], axis=0)
        x_e = x_pad[tok_loc]                                          # (El, C, D)
        h = jnp.einsum("ecd,edgf->ecgf", x_e, gate_up.astype(x.dtype))
        h = jax.nn.silu(h[:, :, 0]) * h[:, :, 1]                      # (El, C, F)
        y_e = jnp.einsum("ecf,efd->ecd", h, down.astype(x.dtype))
        y_e = y_e * gate_loc[..., None].astype(x.dtype)

        out = jnp.zeros((T + 1, D), x.dtype)
        out = out.at[tok_loc.reshape(-1)].add(y_e.reshape(-1, D))[:T]
        axes = tuple(a for a in (model_axis, f_axis) if a)
        if axes:
            out = jax.lax.psum(out, axes)
    dropped = 1.0 - jnp.mean(keep.astype(jnp.float32))
    aux = {"balance": balance, "z_loss": z_loss, "dropped_frac": dropped}
    return out, aux


def moe_apply(params, cfg: ModelConfig, x, *, mesh=None,
              shard_mode: str = "train",
              valid=None) -> Tuple[jax.Array, Dict]:
    """x: (B, L, D) or (B, D). Shared experts (dense, TP-sharded) computed
    outside the shard_map; routed experts inside (EP).  ``valid`` (x's
    shape without D, bool) marks a serving step's real tokens; padding
    gets no routed expert and takes no capacity.  None: every token.

    shard_mode='serve_2dtp': tokens replicated (decode activations are
    KB-sized), expert bank 2D-sharded (experts over 'model', F over
    'data') and RESIDENT — the psum over both axes replaces the baseline's
    per-step 5 GB/layer weight all-gather with an activation-sized
    reduction (EXPERIMENTS.md §Perf A1)."""
    shape = x.shape
    x2 = x.reshape(-1, shape[-1]) if x.ndim == 3 else x
    v2 = jnp.ones(x2.shape[:1], bool) if valid is None \
        else jnp.reshape(valid, (-1,))

    if mesh is not None and "model" in mesh.axis_names:
        from jax.sharding import PartitionSpec as PS
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        if shard_mode == "serve_2dtp":
            f_ax = "data" if "data" in mesh.axis_names and \
                cfg.moe_d_ff % sizes.get("data", 1) == 0 else None
            fn = lambda xl, vl, rw, gu, dn: _moe_local(
                xl, vl, rw, gu, dn, cfg=cfg, model_axis="model", f_axis=f_ax)
            out, aux = jax.shard_map(
                fn, mesh=mesh,
                in_specs=(PS(None, None), PS(None), PS(None, None),
                          PS("model", None, None, f_ax),
                          PS("model", f_ax, None)),
                out_specs=(PS(None, None), PS()),
                check_vma=False,
            )(x2, v2, params["router"], params["gate_up"], params["down"])
            if cfg.n_shared_experts:
                with jax.named_scope("moe_shared_experts"):
                    out = out + nl.mlp(params["shared"], x2, kind="swiglu")
            return out.reshape(shape), aux
        dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
        dp_size = 1
        for a in dp:
            dp_size *= sizes[a]
        if x2.shape[0] % dp_size != 0:   # e.g. batch=1 long-decode
            dp = ()
        fn = lambda xl, vl, rw, gu, dn: _moe_local(
            xl, vl, rw, gu, dn, cfg=cfg, model_axis="model")
        # tokens sharded over DP (flattened B*L), replicated over model;
        # experts sharded over model; router replicated.
        out, aux = jax.shard_map(
            fn, mesh=mesh,
            in_specs=(PS(dp or None, None), PS(dp or None), PS(None, None),
                      PS("model", None, None, None), PS("model", None, None)),
            out_specs=(PS(dp or None, None), PS()),
            check_vma=False,
        )(x2, v2, params["router"], params["gate_up"], params["down"])
    else:
        out, aux = _moe_local(x2, v2, params["router"], params["gate_up"],
                              params["down"], cfg=cfg, model_axis=None)

    if cfg.n_shared_experts:
        with jax.named_scope("moe_shared_experts"):
            out = out + nl.mlp(params["shared"], x2, kind="swiglu")
    return out.reshape(shape), aux
