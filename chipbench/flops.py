"""Analytic operation and byte counts for the benchmark's rooflines and
utilizations.  Counted from shapes alone, as the algorithm needs them:
padding, recomputation and masked-out work are not counted.

A multiply-add counts as two operations.  Norms, activations, softmax
exponentials and bias adds are left out: they are a fraction of a
percent of a transformer token at these widths.
"""
from __future__ import annotations


def _lm(m: dict):
    hd = m["d_model"] // m["n_heads"] if not m.get("head_dim") else m["head_dim"]
    return (m["n_layers"], m["d_model"], m["n_heads"], m["n_kv_heads"], hd,
            m["d_ff"], m["vocab"])


def lm_matmul_flops_per_token(m: dict) -> int:
    """Projections and the SwiGLU FFN of every layer, for one token."""
    L, d, hq, hkv, hd, dff, _ = _lm(m)
    per_layer = 2 * (d * hq * hd + 2 * d * hkv * hd + hq * hd * d
                     + 3 * d * dff)
    return L * per_layer


def lm_attention_flops(m: dict, context: int) -> int:
    """Scores and weighted values of one query token against `context`
    keys, in every layer."""
    L, _, hq, _, hd, _, _ = _lm(m)
    return L * 4 * context * hq * hd


def lm_readout_flops(m: dict) -> int:
    """Logits of one position over the whole vocabulary."""
    _, d, _, _, _, _, v = _lm(m)
    return 2 * d * v


def lm_decode_flops(m: dict, context: int) -> int:
    """One decode step of one row whose query attends to `context` keys
    (its own new key included)."""
    return (lm_matmul_flops_per_token(m) + lm_attention_flops(m, context)
            + lm_readout_flops(m))


def decode_attention_bytes(m: dict, context: int, dtype_bytes: int = 4) -> int:
    """Bytes one paged decode-attention call must move for one row, in
    every layer: the row's K and V over `context` positions, its query
    and its output."""
    L, _, hq, hkv, hd, _, _ = _lm(m)
    kv = 2 * context * hkv * hd
    return L * dtype_bytes * (kv + 2 * hq * hd)
