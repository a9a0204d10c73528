"""Analytic operation and byte counts of a latent-attention, sparse-expert
decode step (DeepSeek-V3 / Moonlight), for the rooflines and the
utilization of `moonlight-16b-a3b` cells.  Counted from shapes alone, as
the algorithm needs them, on the reference's model dict
(`configs/moonlight-16b-a3b.py`): padding, idle rows and masked-out work
are not counted.  `flops.py` counts qwen-style layers.

A multiply-add counts as two operations.  Norms, activations, softmax
exponentials, RoPE and routing's top-k are left out.  Decode attention
runs absorbed: a head's query folds through its W_UK slice into the
latent space, attends the cached latent rows (kv_lora_rank +
qk_rope_head_dim wide; the values are their first kv_lora_rank entries),
and its output leaves through W_UV.
"""
from __future__ import annotations


def _dims(m: dict):
    return (m["n_layers"], m["first_dense"], m["d_model"], m["n_heads"],
            m["kv_lora_rank"], m["qk_nope_head_dim"], m["qk_rope_head_dim"],
            m["v_head_dim"])


def decode_flops(m: dict, context: int) -> int:
    """One decode step of one row whose query attends `context` cached
    rows (its own included): every layer's attention, the leading dense
    FFNs, each expert layer's router and shared experts, and the readout.
    The routed experts' work is counted from the routings that landed on
    this chip's experts (`routed_flops`)."""
    L, L0, d, H, r, nope, rope, dv = _dims(m)
    proj = d * H * (nope + rope) + d * (r + rope) + H * nope * r \
        + H * r * dv + H * dv * d
    attn = H * context * (r + rope) + H * context * r
    dense = 3 * d * m["d_ff"]
    expert_layer = d * m["n_experts"] + 3 * d * m["n_shared"] * m["expert_d_ff"]
    return 2 * (L * (proj + attn) + L0 * dense + (L - L0) * expert_layer
                + d * m["vocab"])


def routed_flops(m: dict, routings: int) -> int:
    """SwiGLU work of `routings` token-to-expert routings."""
    return 2 * 3 * m["d_model"] * m["expert_d_ff"] * routings


def latent_bytes(m: dict, context: int, dtype_bytes: int = 2) -> int:
    """Bytes the decode-attention kernel must move for one row, in every
    layer: its cached latent rows over `context` positions, its absorbed
    query and its output."""
    L, _, _, H, r, _, rope, _ = _dims(m)
    return L * dtype_bytes * (context * (r + rope) + H * (r + rope) + H * r)


def expert_bytes(m: dict, experts_hit: int, routings: int,
                 dtype_bytes: int = 2) -> int:
    """Bytes the held experts' grouped SwiGLU must move: the weights of
    each expert some row routed to (gate, up, down), once per layer and
    step it was hit, and each routing's input row and float32 output row."""
    d, f = m["d_model"], m["expert_d_ff"]
    return (experts_hit * 3 * d * f * dtype_bytes
            + routings * d * (dtype_bytes + 4))
