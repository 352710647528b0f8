"""Base layers: dense, norms, embedding, RoPE, MLPs, causal conv1d.

Every layer is a (defs, apply) pair of pure functions; params are nested
dicts produced by ``module.init_params``.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from .module import P

# ---------------------------------------------------------------- dense ----


def dense_defs(in_dim: int, out_dim: int, in_ax: Optional[str], out_ax: Optional[str],
               bias: bool = False, init: str = "fan_in", scale: Optional[float] = None):
    d = {"w": P((in_dim, out_dim), (in_ax, out_ax), init=init, scale=scale)}
    if bias:
        d["b"] = P((out_dim,), (out_ax,), init="zeros")
    return d


def dense(params, x, compute_dtype=None):
    w = params["w"]
    if compute_dtype is not None:
        w = w.astype(compute_dtype)
        x = x.astype(compute_dtype)
    y = x @ w
    if "b" in params:
        y = y + params["b"].astype(y.dtype)
    return y


# ----------------------------------------------------------------- norms ---


def rmsnorm_defs(dim: int, ax: Optional[str] = None):
    return {"scale": P((dim,), (ax,), init="ones")}


def rmsnorm(params, x, eps: float = 1e-6):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * params["scale"].astype(jnp.float32)).astype(dtype)


def layernorm_defs(dim: int, ax: Optional[str] = None):
    return {"scale": P((dim,), (ax,), init="ones"), "bias": P((dim,), (ax,), init="zeros")}


def layernorm(params, x, eps: float = 1e-5):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32) + params["bias"].astype(jnp.float32)).astype(dtype)


# ------------------------------------------------------------- embedding ---


def embed_defs(vocab: int, dim: int):
    return {"table": P((vocab, dim), ("vocab", "embed"), init="embed", scale=0.02)}


def embed(params, ids, compute_dtype=jnp.bfloat16):
    return jnp.take(params["table"], ids, axis=0).astype(compute_dtype)


def unembed(params, x, out_dtype=None):
    """Logits projection with the (possibly tied) embedding table;
    ``out_dtype`` sets the accumulator's output dtype (default x's)."""
    table = params["table"].astype(x.dtype)
    return jnp.matmul(x, table.T, preferred_element_type=out_dtype)


# ------------------------------------------------------------------ RoPE ---


def rope_angles(positions, dim: int, base: float):
    """positions: (..., L) int -> cos,sin of shape (..., L, dim//2) f32."""
    half = dim // 2
    inv_freq = 1.0 / (base ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[..., None] * inv_freq
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, positions, base: float = 10000.0):
    """x: (B, L, H, D) or (B, L, D); positions: (B, L). Rotate-half (NeoX)."""
    d = x.shape[-1]
    cos, sin = rope_angles(positions, d, base)  # (B, L, d/2)
    if x.ndim == 4:
        cos, sin = cos[:, :, None, :], sin[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ------------------------------------------------------------------ MLPs ---


def mlp_defs(d_model: int, d_ff: int, kind: str = "swiglu", bias: bool = False):
    if kind == "swiglu":
        return {
            "wi": P((d_model, 2, d_ff), ("embed", None, "mlp")),  # [gate; up] fused
            "wo": P((d_ff, d_model), ("mlp", "embed")),
        }
    d = {"wi": dense_defs(d_model, d_ff, "embed", "mlp", bias=bias),
         "wo": dense_defs(d_ff, d_model, "mlp", "embed", bias=bias)}
    return d


def mlp(params, x, kind: str = "swiglu"):
    if kind == "swiglu":
        h = jnp.einsum("...d,dcf->...cf", x, params["wi"].astype(x.dtype))
        gate, up = h[..., 0, :], h[..., 1, :]
        h = jax.nn.silu(gate) * up
        return h @ params["wo"].astype(x.dtype)
    h = dense(params["wi"], x)
    h = jax.nn.gelu(h, approximate=True)
    return dense(params["wo"], h)


# -------------------------------------------------- causal depthwise conv ---


def causal_conv1d_defs(channels: int, width: int):
    return {"w": P((width, channels), (None, "mlp"), init="fan_in"),
            "b": P((channels,), ("mlp",), init="zeros")}


def causal_conv1d(params, x):
    """x: (B, L, C) -> (B, L, C), causal depthwise conv."""
    w, b = params["w"], params["b"]
    width = w.shape[0]
    pad = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    # depthwise: sum_k w[k, c] * x[:, t - (width-1) + k, c]
    out = jnp.zeros_like(x)
    for k in range(width):
        out = out + w[k].astype(x.dtype) * jax.lax.dynamic_slice_in_dim(
            pad, k, x.shape[1], axis=1)
    return out + b.astype(x.dtype)


def causal_conv1d_step(params, x_t, conv_state):
    """Single decode step. x_t: (B, C); conv_state: (B, width-1, C)."""
    w, b = params["w"], params["b"]
    window = jnp.concatenate([conv_state, x_t[:, None, :]], axis=1)  # (B,W,C)
    out = jnp.einsum("bwc,wc->bc", window.astype(jnp.float32),
                     w.astype(jnp.float32)).astype(x_t.dtype) + b.astype(x_t.dtype)
    return out, window[:, 1:, :]
