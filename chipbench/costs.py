"""The yardstick: peaks of the chip, and the operations and bytes that
the model and its two attention kernels need, computed from the
configuration file and the rows a call served.

Counts follow ``hwmodel/attention_costs.py`` of the program (operations
are 2 x multiply-adds; bytes are off-chip bytes), copied here so that a
change to the program cannot move the yardstick.  Kernel work counts the
valid context of each row only, never the block-table width the kernel
scans or the padding rows it carries.
"""
from __future__ import annotations

# Published peaks per chip, keyed by jax ``device_kind``.  Source: Google
# Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 819 GB/s HBM).
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}
BF16 = 2        # bytes per element the serving path stores and streams


def peaks(device_kind: str) -> dict:
    """The peaks of ``device_kind``; an unknown kind is an error."""
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device_kind {device_kind!r}; known: "
                       f"{sorted(PEAKS)}")
    return PEAKS[device_kind]


def _mla(spec):
    return (spec["num_attention_heads"], spec["kv_lora_rank"],
            spec["qk_rope_head_dim"])


def decode_kernel(spec: dict, contexts) -> tuple:
    """(flops, bytes) of one layer's paged decode-attention call over rows
    whose valid context (new token included) is ``contexts``: scores
    against [latent | rope key] and the latent-weighted sum, reading each
    row's latent cache once, its query and writing its output."""
    H, Dl, Dr = _mla(spec)
    flops = bytes_ = 0.0
    for L in contexts:
        flops += 2 * H * L * (Dl + Dr) + 2 * H * L * Dl
        bytes_ += (L * (Dl + Dr) + H * (Dl + Dr) + H * Dl) * BF16
    return flops, bytes_


def prefill_kernel(spec: dict, rows) -> tuple:
    """(flops, bytes) of one layer's paged prefill-attention call; ``rows``
    are (start, n_valid): n_valid causal queries at positions start.. over
    the resident latent [0, start + n_valid)."""
    H, Dl, Dr = _mla(spec)
    flops = bytes_ = 0.0
    for start, nv in rows:
        pairs = nv * start + nv * (nv + 1) / 2
        flops += 2 * H * pairs * (Dl + Dr) + 2 * H * pairs * Dl
        bytes_ += ((start + nv) * (Dl + Dr) + nv * H * (Dl + Dr)
                   + nv * H * Dl) * BF16
    return flops, bytes_


def token_flops(spec: dict, context: int, logits: bool) -> float:
    """Model operations one token needs on this chip at ``context``
    positions (itself included): every projection, attention in the
    latent form, the dense and shared FFNs, and the routed experts at the
    share this chip holds (top-k x held / router width).  With ``logits``
    the output head too."""
    D, Q = spec["hidden_size"], spec["q_lora_rank"]
    H, Dl, Dr = _mla(spec)
    dn, dv = spec["qk_nope_head_dim"], spec["v_head_dim"]
    F, Fd = spec["moe_intermediate_size"], spec["intermediate_size"]
    n = spec["num_hidden_layers"]
    k = min(spec["first_k_dense_replace"], n)
    width = spec["reduced"].get("n_routed_experts", {}).get(
        "published", spec["n_routed_experts"])
    share = spec["num_experts_per_tok"] * spec["n_routed_experts"] / width
    proj = D * Q + Q * H * (dn + Dr) + D * (Dl + Dr) + Dl * H * dn \
        + Dl * H * dv + H * dv * D
    attn = H * context * (Dl + Dr) + H * context * Dl
    dense = 3 * D * Fd
    moe = D * width + 3 * D * F * (spec["n_shared_experts"] + share)
    macs = n * (proj + attn) + k * dense + (n - k) * moe
    if logits:
        macs += D * spec["vocab_size"]
    return 2.0 * macs


def call_flops(spec: dict, kind: str, rows) -> float:
    """Model operations of one step call: a decode call's rows are
    (length, 1) and each yields logits; a prefill call's are (start,
    n_valid), projected and attended causally, without the head."""
    total = 0.0
    for start, nv in rows:
        if kind == "decode":
            total += token_flops(spec, start + 1, True)
        else:
            for i in range(nv):
                total += token_flops(spec, start + i + 1, False)
    return total
