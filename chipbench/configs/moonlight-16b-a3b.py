"""Plain float32 reference of Moonlight-16B-A3B (a DeepSeek-V3 block,
huggingface.co/moonshotai/Moonlight-16B-A3B) and the seeded weights the
benchmark serves.

Nothing here imports the program under test.  `make_params` lays the
weights out in the tree the serving program takes: the leading dense
layers under "lead", the expert layers under "blocks", each a stack of
one-layer superblocks, and of each expert layer only the experts this
chip holds.  The reference rebuilds them from the seed and runs the
published equations with no cache and no kernel: RMSNorm; multi-head
latent attention in its expanded form (per-head keys and values from the
normed latent, a RoPE key part shared by all heads, scale
1/sqrt(qk_nope + qk_rope)); SwiGLU; sigmoid routing over every expert,
top-k chosen on score + selection bias and weighted by the normalized
scores times routed_scale, of which only the held experts' part is
computed (each held expert over every token, weighted by its routing
weight, zero where not chosen) beside the shared experts; untied readout.

The model dict `model` uses short names: n_layers, first_dense, d_model,
n_heads, vocab, d_ff, kv_lora_rank, qk_nope_head_dim, qk_rope_head_dim,
v_head_dim, n_experts (the router's width), n_held, held_first, top_k,
expert_d_ff, n_shared, routed_scale, rms_norm_eps, rope_theta,
param_dtype.  RoPE rotates split halves, as the program does (the
published code de-interleaves pairs first; with random weights only
consistency matters), and the latent's RMSNorm uses eps 1e-6.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

LATENT_EPS = 1e-6
SEQ_STEP = 1024          # sequences pad to a multiple of this (few programs)
VOCAB_BLOCKS = 10        # the readout runs over the vocabulary in blocks


def _leaves(m):
    """(path, shape, kind, fan) of every weight, in a fixed order."""
    L0, L1 = m["first_dense"], m["n_layers"] - m["first_dense"]
    d, H, V = m["d_model"], m["n_heads"], m["vocab"]
    r, nope, rope, dv = (m["kv_lora_rank"], m["qk_nope_head_dim"],
                         m["qk_rope_head_dim"], m["v_head_dim"])
    E, Eh, f = m["n_experts"], m["n_held"], m["expert_d_ff"]
    fs = m["n_shared"] * f
    out = [(("embed", "table"), (V, d), "small", 0.02),
           (("final_norm", "scale"), (d,), "norm", 0),
           (("lm_head", "w"), (d, V), "lecun", d)]
    for stack, n in (("lead", L0), ("blocks", L1)):
        out += [
            ((stack, "norm", "scale"), (n, d), "norm", 0),
            ((stack, "attn", "wq", "w"), (n, d, H * (nope + rope)), "lecun", d),
            ((stack, "attn", "wkv_a", "w"), (n, d, r + rope), "lecun", d),
            ((stack, "attn", "kv_norm", "scale"), (n, r), "norm", 0),
            ((stack, "attn", "wkv_b", "w"), (n, r, H * (nope + dv)), "lecun", r),
            ((stack, "attn", "wo", "w"), (n, H * dv, d), "lecun", H * dv),
            ((stack, "ffn_norm", "scale"), (n, d), "norm", 0)]
    ff = m["d_ff"]
    out += [(("lead", "ffn", "gate", "w"), (L0, d, ff), "lecun", d),
            (("lead", "ffn", "up", "w"), (L0, d, ff), "lecun", d),
            (("lead", "ffn", "down", "w"), (L0, ff, d), "lecun", ff),
            (("blocks", "moe", "router", "w"), (L1, d, E), "lecun", d),
            (("blocks", "moe", "router", "select_bias"), (L1, E), "bias", 0.05),
            (("blocks", "moe", "gate"), (L1, Eh, d, f), "lecun", d),
            (("blocks", "moe", "up"), (L1, Eh, d, f), "lecun", d),
            (("blocks", "moe", "down"), (L1, Eh, f, d), "lecun", f),
            (("blocks", "shared", "gate", "w"), (L1, d, fs), "lecun", d),
            (("blocks", "shared", "up", "w"), (L1, d, fs), "lecun", d),
            (("blocks", "shared", "down", "w"), (L1, fs, d), "lecun", fs)]
    return out


@partial(jax.jit, static_argnames=("shape", "kind", "fan", "dtype"))
def _leaf(key, *, shape, kind, fan, dtype):
    if kind == "lecun":
        x = jax.random.truncated_normal(key, -2.0, 2.0, shape) / math.sqrt(fan)
    elif kind == "norm":
        x = 1.0 + 0.1 * jax.random.normal(key, shape)
    else:                                  # "small", "bias": N(0, fan)
        x = fan * jax.random.normal(key, shape)
    return x.astype(dtype)


def make_params(model: dict, seed32: int):
    """The served weights, made on the device one leaf at a time (the
    largest leaf's float32 draw is the peak), in the configuration's
    parameter dtype; the router's selection bias stays float32."""
    key = jax.random.key(seed32)
    tree: dict = {}
    for i, (path, shape, kind, fan) in enumerate(_leaves(model)):
        dtype = ("float32" if kind == "bias" else model["param_dtype"])
        x = _leaf(jax.random.fold_in(key, i), shape=shape, kind=kind,
                  fan=fan, dtype=dtype)
        node = tree
        for k in path[:-1]:
            node = node.setdefault(k, {})
        node[path[-1]] = x
    for stack in ("lead", "blocks"):       # stacks of one-layer superblocks
        tree[stack] = (tree[stack],)
    return jax.block_until_ready(tree)


def _rms(x, scale, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def _rope(x, pos, theta):
    """x: (T, H, D), split halves rotated by position."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def _low(x, low):
    """x rounded through the control's dtype (identity without one)."""
    return x if low is None else x.astype(low).astype(x.dtype)


def _attention(m, a, x, pos, mask, low):
    T = x.shape[0]
    H, r = m["n_heads"], m["kv_lora_rank"]
    nope, rope, dv = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                      m["v_head_dim"])
    q = (x @ a["wq"]["w"]).reshape(T, H, nope + rope)
    q_nope, q_pe = jnp.split(q, [nope], axis=-1)
    c, k_pe = jnp.split(x @ a["wkv_a"]["w"], [r], axis=-1)
    c = _rms(c, a["kv_norm"]["scale"], LATENT_EPS)
    theta = m["rope_theta"]
    q_pe = _rope(q_pe, pos, theta)
    k_pe = _rope(k_pe[:, None], pos, theta)[:, 0]
    c, k_pe = _low(c, low), _low(k_pe, low)          # the cached latent row
    kv = (c @ a["wkv_b"]["w"]).reshape(T, H, nope + dv)
    k_nope, v = jnp.split(kv, [nope], axis=-1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_pe[:, None],
                                                  (T, H, rope))], -1)
    qq = jnp.concatenate([q_nope, q_pe], -1)
    s = jnp.einsum("thd,shd->hts", qq, k).astype(jnp.float32)
    s = jnp.where(mask[None], s / math.sqrt(nope + rope), -jnp.inf)
    w = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    o = jnp.einsum("hts,shd->thd", w, v).reshape(T, H * dv)
    return o @ a["wo"]["w"]


def _moe(m, p, x, low):
    """The held experts' part of the routed sum, plus the shared experts."""
    rt = p["moe"]["router"]
    scores = jax.nn.sigmoid(x.astype(jnp.float32)
                            @ rt["w"].astype(jnp.float32))
    _, idx = jax.lax.top_k(scores + rt["select_bias"].astype(jnp.float32),
                           m["top_k"])
    w = jnp.take_along_axis(scores, idx, -1)
    w = w / jnp.sum(w, -1, keepdims=True) * m["routed_scale"]
    xe = _low(x, low)

    def expert(y, e):
        we = jnp.sum(jnp.where(idx == m["held_first"] + e[0], w, 0.0), -1)
        g, u, dn = (_low(t, low) for t in e[1:])
        return y + we[:, None] * _swiglu(xe, g, u, dn).astype(jnp.float32), None

    mo = p["moe"]
    y, _ = jax.lax.scan(expert, jnp.zeros(x.shape, jnp.float32),
                        (jnp.arange(m["n_held"]), mo["gate"], mo["up"],
                         mo["down"]))
    sh = p["shared"]
    return (y.astype(x.dtype)
            + _swiglu(x, sh["gate"]["w"], sh["up"]["w"], sh["down"]["w"]))


def hidden_states(model: dict, params, tokens, dtype, low=None):
    """Final-norm hidden states (T, d) of one causal sequence, each
    layer's weights cast to `dtype` as it runs; with `low`, the latent
    rows and the held experts' matmul operands rounded through it."""
    m = model
    eps = m["rms_norm_eps"]
    T = tokens.shape[0]
    pos = jnp.arange(T)
    mask = pos[:, None] >= pos[None, :]
    x = params["embed"]["table"][tokens].astype(dtype)

    def layer(x, p, moe):
        p = jax.tree.map(lambda a: a.astype(dtype), p)
        x = x + _attention(m, p["attn"], _rms(x, p["norm"]["scale"], eps),
                           pos, mask, low)
        h = _rms(x, p["ffn_norm"]["scale"], eps)
        if moe:
            return x + _moe(m, p, h, low), None
        f = p["ffn"]
        return x + _swiglu(h, f["gate"]["w"], f["up"]["w"],
                           f["down"]["w"]), None

    x, _ = jax.lax.scan(partial(layer, moe=False), x, params["lead"][0])
    x, _ = jax.lax.scan(partial(layer, moe=True), x, params["blocks"][0])
    return _rms(x, params["final_norm"]["scale"].astype(dtype), eps)


def _logits(model, params, tokens, rows, dtype, low=None):
    """Float32 logits at positions `rows`, over the vocabulary in blocks."""
    h = hidden_states(model, params, tokens, dtype, low)[rows]
    w = params["lm_head"]["w"]
    d, V = w.shape
    nb = VOCAB_BLOCKS if V % VOCAB_BLOCKS == 0 else 1
    wb = w.reshape(d, nb, V // nb).transpose(1, 0, 2)
    out = jax.lax.map(lambda b: (h @ b.astype(dtype)).astype(jnp.float32), wb)
    return out.transpose(1, 0, 2).reshape(h.shape[0], V)


@partial(jax.jit, static_argnames=("model_items", "lower"))
def _compare(params, tokens, rows, served, first, *, model_items, lower):
    """At the positions `rows` of a padded sequence (padding lies after
    every row read; row 0 is the prompt's last position, whose logits
    give the first token): the gap by which each served token's
    reference logit lies below the reference's best, the standard
    deviation of each row's reference logits, and the mean absolute and
    mean squared gap between `first`, the served first-token logits, and
    the reference's at row 0.  With `lower`, the same for the control:
    bfloat16 weights and activations with the latent rows and the held
    experts' matmul operands in `lower`."""
    model = dict(model_items)
    ref = _logits(model, params, tokens, rows, jnp.float32)
    best = ref.max(-1)

    def gap_of(tok):
        return best - jnp.take_along_axis(ref, tok[:, None], -1)[:, 0]

    def first_err(lg):
        d = lg - ref[0]
        return jnp.stack([jnp.abs(d).mean(), (d * d).mean()])

    out = {"gap": gap_of(served), "std": ref.std(-1),
           "first": first_err(first)}
    if lower is not None:
        low = _logits(model, params, tokens, rows, jnp.bfloat16,
                      jnp.dtype(lower))
        out["control_gap"] = gap_of(low.argmax(-1))
        out["control_first"] = first_err(low[0])
    return out


def compare(model: dict, seed32: int, seqs, *, pad_to: int,
            precision: str, lower: str | None = None, chunk: int = 512):
    """Score served requests against the reference, rebuilding the
    weights from the seed.  `seqs` holds (prompt, served tokens, served
    first-token logits) per request; `precision` is the matmul precision
    the configuration states for the reference.  Per request: the gaps of
    its served tokens, the reference logits' standard deviation at each
    of those positions, and [mean |d|, mean d^2] of its first-token
    logits; with `lower`, the control's gaps and first-token errors too.
    Each sequence pads to a multiple of SEQ_STEP (at most pad_to)."""
    params = make_params(model, seed32)
    items = tuple(sorted(model.items()))
    res = []
    with jax.default_matmul_precision(precision):
        for prompt, served, first in seqs:
            seq = np.concatenate([prompt, served]).astype(np.int32)
            T, n = len(prompt), len(served)
            if len(seq) > pad_to:
                raise ValueError(f"sequence {len(seq)} exceeds {pad_to}")
            toks = np.zeros((min(pad_to, -(-len(seq) // SEQ_STEP)
                                 * SEQ_STEP),), np.int32)
            toks[:len(seq)] = seq
            parts = []
            for c0 in range(0, n, chunk):
                k = min(chunk, n - c0)
                rows = np.full((chunk,), T - 1, np.int32)
                rows[:k] = T - 1 + c0 + np.arange(k)
                tok = np.zeros((chunk,), np.int32)
                tok[:k] = served[c0:c0 + k]
                o = jax.device_get(_compare(
                    params, jnp.asarray(toks), jnp.asarray(rows),
                    jnp.asarray(tok), jnp.asarray(first, jnp.float32),
                    model_items=items, lower=lower))
                parts.append((o, k))
            r = {key: np.concatenate([o[key][:k] for o, k in parts])
                 for key in ("gap", "std", "control_gap") if key in parts[0][0]}
            for key in ("first", "control_first"):
                if key in parts[0][0]:
                    r[key] = np.asarray(parts[0][0][key], np.float64)
            res.append(r)
    del params
    return res
