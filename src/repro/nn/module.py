"""Minimal functional module system.

Design: a *module* is a triple of pure functions over a config:

  - ``param_defs(cfg) -> tree[P]``   declarative parameter definitions
  - ``init(key, defs, dtype) -> tree[Array]``
  - ``apply(params, cfg, *inputs) -> outputs``

Parameter definitions carry *logical axis names* (``'embed'``, ``'heads'``,
``'mlp'`` ...) so the same model definition yields both the init shapes and
the GSPMD ``PartitionSpec`` tree via ``repro.nn.sharding``.  Keeping defs
declarative guarantees init / sharding / eval_shape never drift apart.
"""
from __future__ import annotations

import dataclasses
import hashlib
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Tree = Any  # nested dict


@dataclasses.dataclass(frozen=True)
class P:
    """Declarative parameter definition.

    shape : concrete shape
    axes  : logical axis name per dim (None = replicated / not sharded)
    init  : 'normal' | 'zeros' | 'ones' | 'embed' | 'fan_in'
    scale : stddev override (default: fan-in scaled)
    dtype : override of the module-wide param dtype
    """

    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]
    init: str = "fan_in"
    scale: Optional[float] = None
    dtype: Optional[Any] = None

    def __post_init__(self):
        if len(self.shape) != len(self.axes):
            raise ValueError(f"shape {self.shape} / axes {self.axes} rank mismatch")


def _path_key(key: jax.Array, path: str) -> jax.Array:
    # Deterministic, order-independent per-parameter key derivation.
    h = int.from_bytes(hashlib.sha256(path.encode()).digest()[:4], "little")
    return jax.random.fold_in(key, h)


def _init_one(key: jax.Array, p: P, default_dtype) -> jax.Array:
    """Draw one leaf directly in its dtype: a bf16 leaf never exists as an
    f32 temporary (at DeepSeek-V2 widths the stacked expert weights alone
    would take 4 GiB), and f32 draws are unchanged."""
    dtype = p.dtype or default_dtype
    if p.init == "zeros":
        return jnp.zeros(p.shape, dtype)
    if p.init == "ones":
        return jnp.ones(p.shape, dtype)
    if p.init == "normal":
        std = p.scale if p.scale is not None else 0.02
    elif p.init == "embed":
        std = p.scale if p.scale is not None else 1.0
    elif p.init == "fan_in":
        # fan-in = product of all dims except the last (output) dim.
        fan_in = max(1, int(np.prod(p.shape[:-1])))
        std = p.scale if p.scale is not None else fan_in ** -0.5
    else:
        raise ValueError(f"unknown init {p.init}")
    return jax.random.normal(key, p.shape, dtype) * jnp.asarray(std, dtype)


def is_def(x) -> bool:
    return isinstance(x, P)


def map_defs(fn: Callable[[str, P], Any], defs: Tree, prefix: str = "") -> Tree:
    """Map over a tree of P leaves, passing the string path to ``fn``."""
    if is_def(defs):
        return fn(prefix, defs)
    if isinstance(defs, dict):
        return {k: map_defs(fn, v, f"{prefix}/{k}") for k, v in defs.items()}
    if isinstance(defs, (list, tuple)):
        t = type(defs)
        return t(map_defs(fn, v, f"{prefix}/{i}") for i, v in enumerate(defs))
    raise TypeError(f"unexpected node {type(defs)} at {prefix}")


def init_params(key: jax.Array, defs: Tree, param_dtype=jnp.float32) -> Tree:
    return map_defs(lambda path, p: _init_one(_path_key(key, path), p, param_dtype), defs)


def shapes(defs: Tree, param_dtype=jnp.float32) -> Tree:
    """ShapeDtypeStruct tree — used by the dry-run (no allocation)."""
    return map_defs(
        lambda _, p: jax.ShapeDtypeStruct(p.shape, p.dtype or param_dtype), defs
    )


def logical_axes(defs: Tree) -> Tree:
    return map_defs(lambda _, p: p.axes, defs)


def count_params(defs: Tree) -> int:
    n = [0]

    def add(_, p):
        n[0] += int(np.prod(p.shape))
        return None

    map_defs(add, defs)
    return n[0]


def stack_defs(defs: Tree, n: int, axis_name: Optional[str] = None) -> Tree:
    """Prepend a stacking dim of size n (for scan-over-layers weights)."""
    return map_defs(
        lambda _, p: dataclasses.replace(
            p, shape=(n,) + p.shape, axes=(axis_name,) + p.axes
        ),
        defs,
    )
