"""Weights for a configuration, drawn from the seed on the device.

The benchmark makes the weights, not the program: one jitted call draws
every leaf in bfloat16 from ``jax.random.key(seed, impl="rbg")``, in the
parameter layout the program's serving steps take.  The same tree feeds
the program and the plain reference (``reference.py``).

Layout (the program's ``lm_defs``): ``embed.table``, ``ln_f.scale``, the
leading dense layers under ``prefix/l<i>`` and the MoE layers stacked on
a leading axis under ``period/s0`` (or, with fewer than two MoE layers,
unrolled under ``prefix``).  Only the routed experts this chip holds are
drawn; the router keeps its published width.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp

from . import model

# std of the embedding table and the router; every other matrix draws
# with 1/sqrt(its contraction width), norm scales are 1
EMBED_STD = 0.02


def _mla(s: dict) -> dict:
    D, Q, Dl = s["hidden_size"], s["q_lora_rank"], s["kv_lora_rank"]
    H = s["num_attention_heads"]
    dn, dr, dv = s["qk_nope_head_dim"], s["qk_rope_head_dim"], s["v_head_dim"]
    # leaf -> (shape, contraction width or None for a norm scale)
    return {
        "w_dq": ((D, Q), D), "q_norm": {"scale": ((Q,), None)},
        "w_uq": ((Q, H, dn + dr), Q),
        "w_dkv": ((D, Dl + dr), D), "kv_norm": {"scale": ((Dl,), None)},
        "w_uk": ((Dl, H, dn), Dl), "w_uv": ((Dl, H, dv), Dl),
        "w_o": ((H, dv, D), H * dv),
    }


def _swiglu(D: int, F: int) -> dict:
    return {"wi": ((D, 2, F), D), "wo": ((F, D), F)}


def _layer(s: dict, moe: bool) -> dict:
    D = s["hidden_size"]
    d = {"ln1": {"scale": ((D,), None)}, "attn": _mla(s),
         "ln2": {"scale": ((D,), None)}}
    if not moe:
        d["ffn"] = _swiglu(D, s["intermediate_size"])
        return d
    F, E = s["moe_intermediate_size"], model.held_experts(s)
    d["ffn"] = {"router": ((D, model.router_width(s)), "router"),
                "gate_up": ((E, D, 2, F), D), "down": ((E, F, D), F)}
    if s["n_shared_experts"]:
        d["ffn"]["shared"] = _swiglu(D, s["n_shared_experts"] * F)
    return d


def _stack(tree, n: int):
    if isinstance(tree, dict):
        return {k: _stack(v, n) for k, v in tree.items()}
    shape, fan = tree
    return ((n,) + shape, fan)


def layout(s: dict) -> dict:
    """{path: (shape, contraction width | None | 'router' | 'embed')} as
    a nested dict in the program's parameter layout."""
    n, k = s["num_hidden_layers"], model.dense_layers(s)
    tree = {"embed": {"table": ((s["vocab_size"], s["hidden_size"]),
                                "embed")},
            "ln_f": {"scale": ((s["hidden_size"],), None)}}
    if n - k >= 2:
        tree["prefix"] = {f"l{i}": _layer(s, False) for i in range(k)}
        tree["period"] = {"s0": _stack(_layer(s, True), n - k)}
    else:
        tree["prefix"] = {f"l{i}": _layer(s, i >= k) for i in range(n)}
    tree["suffix"] = {}
    return tree


def _leaves(tree, prefix=""):
    for k, v in tree.items():
        if isinstance(v, dict):
            yield from _leaves(v, f"{prefix}/{k}")
        else:
            yield f"{prefix}/{k}", v


def _set(tree, path, value):
    *parts, last = path.strip("/").split("/")
    for p in parts:
        tree = tree.setdefault(p, {})
    tree[last] = value


def shapes(s: dict) -> dict:
    """ShapeDtypeStruct tree of :func:`init` (no allocation)."""
    out = {"suffix": {}}
    for path, (shape, _) in _leaves(layout(s)):
        _set(out, path, jax.ShapeDtypeStruct(shape, jnp.bfloat16))
    return out


def init(s: dict, seed: int, dtype=jnp.bfloat16) -> dict:
    """Every leaf drawn on the device in ``dtype``, in one jitted call."""
    leaves = list(_leaves(layout(s)))

    def draw(key):
        out = {"suffix": {}}
        for path, (shape, fan) in leaves:
            if fan is None:
                w = jnp.ones(shape, dtype)
            else:
                std = EMBED_STD if fan in ("embed", "router") else fan ** -0.5
                k = jax.random.fold_in(key, zlib.crc32(path.encode()))
                w = jax.random.normal(k, shape, dtype) * jnp.asarray(std, dtype)
            _set(out, path, w)
        return out

    return jax.jit(draw)(jax.random.key(seed % (2 ** 32), impl="rbg"))
