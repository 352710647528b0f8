"""A configuration file, read: the source's keys mapped onto the program's
``ModelConfig``, with every key accounted for.

Each key of the published ``config.json`` is one of three things here:

* mapped: it sets a ``ModelConfig`` field, or it is a value the program
  honours by construction and is checked here (``hidden_act`` must be
  ``silu``, ``attention_bias`` false, ...);
* reduced: the file changes it and says why under ``reduced``;
* a departure: the program does not honour it, and ``departures`` says
  what it does instead.

Any other key is an error, so a configuration can never carry a value the
benchmark silently ignores.  This module imports nothing of the program:
:func:`model_config` takes the program's ``ModelConfig`` class as an
argument.
"""
from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))

# source key -> ModelConfig field it sets
FIELDS = {
    "hidden_size": "d_model",
    "intermediate_size": "first_dense_d_ff",
    "moe_intermediate_size": "moe_d_ff",
    "kv_lora_rank": "kv_lora_rank",
    "q_lora_rank": "q_lora_rank",
    "qk_nope_head_dim": "qk_nope_dim",
    "qk_rope_head_dim": "qk_rope_dim",
    "v_head_dim": "v_head_dim",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "num_hidden_layers": "n_layers",
    "first_k_dense_replace": "first_dense_layers",
    "num_experts_per_tok": "top_k",
    "n_shared_experts": "n_shared_experts",
    "rms_norm_eps": "norm_eps",
    "rope_theta": "rope_base",
    "vocab_size": "vocab",
}
# source key -> the value the program's equations assume
CHECKED = {
    "hidden_act": "silu",
    "attention_bias": False,
    "moe_layer_freq": 1,
    "ep_size": 1,
    "num_nextn_predict_layers": 0,
}
# keys whose published value the program honours as it stands
HONOURED = {
    "model_type": ("deepseek_v2", "deepseek_v3"),
    "norm_topk_prob": (True,),          # the program renormalises top-k
    "scoring_func": ("softmax",),
}
# the key that counts routed experts: the file gives the number held on
# this chip, ``reduced`` the published count, which is the router's width
EXPERTS = "n_routed_experts"
# keys that are not the source's
OWN = ("name", "source", "reference", "deployment", "assumed", "departures",
       "settings", "reduced")
# a width may never be cut (contract: hidden, intermediate, latent, head
# sizes, keys ending in _dim or _rank, experts per token)
WIDTHS = ("hidden_size", "intermediate_size", "moe_intermediate_size",
          "kv_lora_rank", "q_lora_rank", "qk_nope_head_dim",
          "qk_rope_head_dim", "v_head_dim", "num_attention_heads",
          "num_key_value_heads", "num_experts_per_tok", "vocab_size")


def load(name: str) -> dict:
    """The configuration file ``configs/<name>.json``, checked."""
    with open(os.path.join(HERE, "configs", f"{name}.json")) as f:
        spec = json.load(f)
    if spec.get("name") != name:
        raise ValueError(f"configs/{name}.json names itself {spec.get('name')!r}")
    check(spec)
    return spec


def source_keys(spec: dict) -> list:
    return [k for k in spec if k not in OWN]


def check(spec: dict) -> None:
    """Raise ValueError unless every source key is mapped, reduced or a
    departure, and no width is reduced."""
    reduced, departures = spec["reduced"], spec["departures"]
    for k, r in reduced.items():
        if k in WIDTHS or k.endswith(("_dim", "_rank")):
            raise ValueError(f"{k} is a width and may not be reduced")
        if spec.get(k) != r["here"]:
            raise ValueError(f"{k}: file gives {spec.get(k)!r}, reduced "
                             f"says {r['here']!r}")
    for k in source_keys(spec):
        if k in departures:
            continue
        if k in FIELDS or k == EXPERTS or k == "max_position_embeddings":
            continue
        if k in CHECKED:
            if spec[k] != CHECKED[k]:
                raise ValueError(f"{k}={spec[k]!r}: the program assumes "
                                 f"{CHECKED[k]!r}; list it in departures")
            continue
        if k in HONOURED:
            if spec[k] not in HONOURED[k]:
                raise ValueError(f"{k}={spec[k]!r} is not honoured; list "
                                 f"it in departures")
            continue
        raise ValueError(f"{k} is neither mapped, reduced nor a departure")
    for k in departures:
        if k not in spec:
            raise ValueError(f"departure {k} is not a key of the file")


def router_width(spec: dict) -> int:
    """Experts the router scores: the published count."""
    r = spec["reduced"].get(EXPERTS)
    return r["published"] if r else spec[EXPERTS]


def held_experts(spec: dict) -> int:
    return spec[EXPERTS]


def dense_layers(spec: dict) -> int:
    return min(spec["first_k_dense_replace"], spec["num_hidden_layers"])


def model_config(spec: dict, model_config_cls, *, max_seq: int):
    """The program's ``ModelConfig`` for ``spec``: the router at its
    published width (``n_experts``), drop-free capacity from
    ``settings``; the held expert count lives in the weights."""
    kw = {field: spec[key] for key, field in FIELDS.items()}
    kw["d_ff"] = spec["moe_intermediate_size"]
    if max_seq > spec["max_position_embeddings"]:
        raise ValueError(f"max_seq {max_seq} exceeds the model's "
                         f"{spec['max_position_embeddings']} positions")
    return model_config_cls(
        name=spec["name"], family="moe", attn_kind="mla",
        n_experts=router_width(spec),
        capacity_factor=float(spec["settings"]["capacity_factor"]),
        max_seq=max_seq, remat=False, **kw)


def smoke_spec(spec: dict) -> dict:
    """The same architecture at tiny widths for CPU rehearsals: every
    routing and depth setting kept, widths divided down."""
    s = json.loads(json.dumps(spec))
    s.update({"hidden_size": 64, "intermediate_size": 128,
              "moe_intermediate_size": 32, "kv_lora_rank": 32,
              "q_lora_rank": 48, "qk_nope_head_dim": 16,
              "qk_rope_head_dim": 8, "v_head_dim": 16,
              "num_attention_heads": 4, "num_key_value_heads": 4,
              "vocab_size": 512})
    width = router_width(spec)
    held = held_experts(spec)
    # keep the held share of the router: 2 of 16 for V2's 20 of 160
    s["reduced"][EXPERTS]["published"] = 16
    s[EXPERTS] = s["reduced"][EXPERTS]["here"] = max(2, 16 * held // width)
    s["num_experts_per_tok"] = min(spec["num_experts_per_tok"], 4)
    s["settings"]["capacity_factor"] = 16 / s["num_experts_per_tok"]
    return s
