"""Plain reference of the DeepSeek MLA + MoE decoder, as the program is
meant to compute it, in float32 at ``highest`` matmul precision.

It imports nothing of the program and takes only the configuration file
(``configs/<name>.json``) and the weight tree the benchmark drew
(``weights.py``).  The equations follow the DeepSeek-V2 paper
(arXiv:2405.04434, section 2.1) with the departures the configuration file
lists under ``departures``, which the program makes and this reference
makes alike:

* RoPE is plain rotate-half (NeoX) with ``rope_theta``; no YaRN.
* Routing is a softmax over every router output, the top
  ``num_experts_per_tok`` gates renormalised to sum to 1; no groups, no
  routed scaling.  Only the routed experts this chip holds (ids
  ``0 .. n_routed_experts - 1`` here) contribute, as on EP rank 0.
* The output head is the embedding table (tied).

Per layer: ``x += MLA(rmsnorm(x))``, then ``x += FFN(rmsnorm(x))``, where
FFN is a SwiGLU MLP for the leading dense layers and shared experts plus
the held routed experts for the rest.  MLA:

    q      = rmsnorm(x W_dq) W_uq              -> [q_nope | rope(q_rope)]
    c      = x W_dkv                           -> [ckv | krope]
    ckv    = rmsnorm(ckv);  krope = rope(krope)
    k      = [ckv W_uk | krope],  v = ckv W_uv
    out    = softmax(q k^T / sqrt(dn + dr), causal) v W_o

It runs layer by layer over a segment of tokens, attention in blocks of
query rows, so that a document of 12k tokens fits beside the weights; the
latent cache of a segment can be kept and continued from, which is how a
shared document is computed once for all the requests that ask about it.
Every device function is jitted at a few fixed shapes (rows padded to
``BLOCK``, cache extents to ``KEY_STEP``, expert batches to
``EXPERT_STEP``), so a run compiles a bounded set of programs.

``quant="fp8"`` is the control: the same computation with both operands of
every matrix product rounded to float8 e4m3 (absmax-scaled per tensor for
weights and attention values, per row for the other operand), the step
below the bfloat16 the configuration serves in.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
ROWS = 128          # query rows per attention block
BLOCK = 256         # a segment's rows are padded to a multiple of this
KEY_STEP = 2048     # cache extents are rounded up to a multiple of this
EXPERT_STEP = 256   # an expert's token count is padded to a multiple
VOCAB_CHUNK = 16384  # logits are computed at most this many ids at a time


@dataclasses.dataclass(frozen=True)
class Dims:
    """The static numbers of the computation (hashable: a jit key)."""
    eps: float
    theta: float
    dn: int
    dr: int
    dl: int
    scale: float
    top_k: int
    quant: str


def _fp8(x, axis):
    """Round to float8 e4m3 with an absmax scale over ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def _mm(d, spec, a, b, b_axis=None):
    """einsum in float32 at highest precision; under the control both
    operands are first rounded to float8 (``a`` per row, ``b`` per
    tensor, or over ``b_axis``)."""
    a, b = a.astype(F32), b.astype(F32)
    if d.quant == "fp8":
        a, b = _fp8(a, -1), _fp8(b, b_axis)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _rms(d, x, scale):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + d.eps) \
        * scale.astype(F32)


def _rope(d, x, pos):
    """Rotate-half RoPE over the last axis; ``pos`` indexes x's rows."""
    half = x.shape[-1] // 2
    inv = 1.0 / (d.theta ** (jnp.arange(half, dtype=F32) / half))
    ang = pos.astype(F32)[:, None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if x.ndim == 3:
        cos, sin = cos[:, None], sin[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _swiglu(d, w, x):
    h = _mm(d, "rd,dcf->rcf", x, w["wi"])
    return _mm(d, "rf,fd->rd", jax.nn.silu(h[:, 0]) * h[:, 1], w["wo"])


def _pick(tree, j):
    """Layer j of a stack whose leaves carry a leading layer axis; an
    unstacked layer (j None) as it is."""
    return tree if j is None else jax.tree.map(lambda a: a[j], tree)


@functools.partial(jax.jit, static_argnums=(0,))
def _keys(d, a, ckv):
    """[k_nope | v] of every cached latent row."""
    return jnp.concatenate([_mm(d, "rl,lhn->rhn", ckv, a["w_uk"]),
                            _mm(d, "rl,lhv->rhv", ckv, a["w_uv"])], -1)


@functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(3, 4, 5, 6))
def _attn_block(d, lw, j, x, ckv_all, krope_all, k_all, row, start):
    """Rows [row, row + ROWS) of the segment (absolute positions start +
    row ..) through layer ``lw`` (layer j of a stack): write their
    latents, keys and values into the caches, attend the cache causally,
    add the output to x."""
    w = _pick(lw, j)
    a = w["attn"]
    xb = jax.lax.dynamic_slice_in_dim(x, row, ROWS)
    pos = start + row + jnp.arange(ROWS)
    h = _rms(d, xb, w["ln1"]["scale"])
    ql = _rms(d, _mm(d, "rd,dq->rq", h, a["w_dq"]), a["q_norm"]["scale"])
    qf = _mm(d, "rq,qhe->rhe", ql, a["w_uq"])
    q_nope, q_rope = qf[..., :d.dn], _rope(d, qf[..., d.dn:], pos)
    c = _mm(d, "rd,dc->rc", h, a["w_dkv"])
    ckv = _rms(d, c[:, :d.dl], a["kv_norm"]["scale"])
    krope = _rope(d, c[:, d.dl:], pos)
    p0 = start + row
    ckv_all = jax.lax.dynamic_update_slice_in_dim(ckv_all, ckv, p0, 0)
    krope_all = jax.lax.dynamic_update_slice_in_dim(krope_all, krope, p0, 0)
    k_all = jax.lax.dynamic_update_slice_in_dim(k_all, _keys(d, a, ckv), p0, 0)
    s = (_mm(d, "rhn,khn->hrk", q_nope, k_all[..., :d.dn], -1)
         + _mm(d, "rhe,ke->hrk", q_rope, krope_all, -1)) * d.scale
    kpos = jnp.arange(k_all.shape[0])
    s = jnp.where(kpos[None, None, :] <= pos[None, :, None], s, -jnp.inf)
    o = _mm(d, "hrk,khv->rhv", jax.nn.softmax(s, axis=-1), k_all[..., d.dn:])
    xb = xb + _mm(d, "rhv,hvd->rd", o, a["w_o"])
    return (jax.lax.dynamic_update_slice_in_dim(x, xb, row, 0),
            ckv_all, krope_all, k_all)


@functools.partial(jax.jit, static_argnums=(0,))
def _layer_keys(d, lw, j, ckv):
    return _keys(d, _pick(lw, j)["attn"], ckv)


@functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(3,))
def _dense(d, lw, j, x):
    w = _pick(lw, j)
    return x + _swiglu(d, w["ffn"], _rms(d, x, w["ln2"]["scale"]))


@functools.partial(jax.jit, static_argnums=(0,))
def _route(d, lw, j, x):
    """Norm, shared experts and routing of every row."""
    w = _pick(lw, j)
    ffn = w["ffn"]
    h = _rms(d, x, w["ln2"]["scale"])
    probs = jax.nn.softmax(_mm(d, "rd,de->re", h, ffn["router"]), -1)
    gates, sel = jax.lax.top_k(probs, d.top_k)
    gates = gates / jnp.sum(gates, -1, keepdims=True)
    y = x + _swiglu(d, ffn["shared"], h) if "shared" in ffn else x
    return y, h, gates, sel


@functools.partial(jax.jit, static_argnums=(0,), donate_argnums=(4,))
def _expert(d, lw, j, e, y, h, idx, gate):
    """Add routed expert e's gated output at rows ``idx``."""
    ffn = _pick(lw, j)["ffn"]
    hh = _mm(d, "rd,dcf->rcf", h[idx], ffn["gate_up"][e])
    out = _mm(d, "rf,fd->rd", jax.nn.silu(hh[:, 0]) * hh[:, 1],
              ffn["down"][e])
    return y.at[idx].add(out * gate[:, None])


@functools.partial(jax.jit, static_argnums=(0, 5))
def _head(d, ln_f, table, x, row, n):
    """Logits of rows [row, row + n) against the whole vocabulary."""
    h = _rms(d, jax.lax.dynamic_slice_in_dim(x, row, n), ln_f)
    V, D = table.shape
    chunks = -(-V // VOCAB_CHUNK)
    while V % chunks:
        chunks += 1
    t = table.reshape(chunks, V // chunks, D)
    out = jax.lax.map(lambda tc: _mm(d, "rd,vd->rv", h, tc), t)
    return jnp.moveaxis(out, 0, 1).reshape(n, V)


@jax.jit
def _embed(table, tok):
    return table[tok].astype(F32)


@functools.partial(jax.jit, static_argnums=(1,))
def _grow(a, cap):
    """``a`` with its leading axis zero-padded to ``cap`` rows."""
    return jnp.pad(a, [(0, cap - a.shape[0])] + [(0, 0)] * (a.ndim - 1))


class Reference:
    """``forward(tokens, state)`` -> (state, hidden rows, padded);
    ``logits(hidden, row, n)`` -> (n, vocab) float32.

    ``state`` is (position, [(ckv, krope) per layer]) after the tokens
    seen so far; ``None`` starts at position 0."""

    def __init__(self, spec: dict, weights, quant: str = "f32"):
        self.w = weights
        self.d = Dims(eps=float(spec["rms_norm_eps"]),
                      theta=float(spec["rope_theta"]),
                      dn=spec["qk_nope_head_dim"],
                      dr=spec["qk_rope_head_dim"], dl=spec["kv_lora_rank"],
                      scale=(spec["qk_nope_head_dim"]
                             + spec["qk_rope_head_dim"]) ** -0.5,
                      top_k=spec["num_experts_per_tok"], quant=quant)
        self.n_layers = spec["num_hidden_layers"]
        self.n_dense = min(spec["first_k_dense_replace"], self.n_layers)
        self.held = spec["n_routed_experts"]
        self.heads = spec["num_attention_heads"]
        self.dv = spec["v_head_dim"]

    def layer(self, i: int):
        """(layer tree, index in its stack or None) of layer i."""
        if "period" not in self.w or i < self.n_dense:
            return self.w["prefix"][f"l{i}"], None
        return self.w["period"]["s0"], jnp.int32(i - self.n_dense)

    def forward(self, tokens, state=None):
        d = self.d
        start, lat = state if state is not None else (0, None)
        L = len(tokens)
        Lp = -(-L // BLOCK) * BLOCK
        cap = -(-(start + Lp) // KEY_STEP) * KEY_STEP
        tok = np.zeros(Lp, np.int32)
        tok[:L] = tokens
        x = _embed(self.w["embed"]["table"], jnp.asarray(tok))
        new_lat = []
        for i in range(self.n_layers):
            lw, j = self.layer(i)
            if lat is None:
                ckv_all = jnp.zeros((cap, d.dl), F32)
                krope_all = jnp.zeros((cap, d.dr), F32)
            else:
                ckv_all, krope_all = _grow(lat[i][0], cap), \
                    _grow(lat[i][1], cap)
            k_all = _layer_keys(d, lw, j, ckv_all)
            for row in range(0, Lp, ROWS):
                x, ckv_all, krope_all, k_all = _attn_block(
                    d, lw, j, x, ckv_all, krope_all, k_all, jnp.int32(row),
                    jnp.int32(start))
            del k_all
            new_lat.append((ckv_all, krope_all))
            x = self._ffn(i, lw, j, x)
        return (start + L, new_lat), x

    def _ffn(self, i, lw, j, x):
        d = self.d
        if i < self.n_dense:
            return _dense(d, lw, j, x)
        y, h, gates, sel = _route(d, lw, j, x)
        gates, sel = np.asarray(gates), np.asarray(sel)
        for e in range(self.held):
            rows, slot = np.nonzero(sel == e)
            if len(rows) == 0:
                continue
            n = -(-len(rows) // EXPERT_STEP) * EXPERT_STEP
            idx = np.zeros(n, np.int32)
            idx[:len(rows)] = rows
            g = np.zeros(n, np.float32)
            g[:len(rows)] = gates[rows, slot]
            y = _expert(d, lw, j, jnp.int32(e), y, h, jnp.asarray(idx),
                        jnp.asarray(g))
        return y

    def logits(self, hidden, row: int, n: int) -> np.ndarray:
        """(n, vocab) float32 logits of hidden rows [row, row + n)."""
        m = -(-n // ROWS) * ROWS
        r0 = min(row, hidden.shape[0] - m)
        out = np.asarray(_head(self.d, self.w["ln_f"]["scale"],
                               self.w["embed"]["table"], hidden,
                               jnp.int32(r0), m))
        return out[row - r0:row - r0 + n]
