"""Plain float32 reference of Qwen2 (arXiv:2407.10671) and the seeded
weights the benchmark serves.

Nothing here imports the program under test.  `make_params` lays the
weights out in the tree the serving program takes (one stacked block of
`n_layers` decoder layers); the reference rebuilds them from the seed
itself and runs the published equations: RMSNorm, grouped-query attention
with q/k/v biases and rotary phases over split halves, SwiGLU, tied
readout.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def _dims(m):
    hd = m.get("head_dim") or m["d_model"] // m["n_heads"]
    return m["n_layers"], m["d_model"], m["n_heads"], m["n_kv_heads"], hd


def _params(m, key):
    L, d, hq, hkv, hd = _dims(m)
    ff, V = m["d_ff"], m["vocab"]
    ks = iter(jax.random.split(key, 16))

    def lecun(shape, fan):
        return (jax.random.truncated_normal(next(ks), -2.0, 2.0, shape)
                / math.sqrt(fan))

    def small(shape, std):
        return std * jax.random.normal(next(ks), shape)

    attn = {
        "wq": {"w": lecun((L, d, hq * hd), d), "b": small((L, hq * hd), 0.02)},
        "wk": {"w": lecun((L, d, hkv * hd), d), "b": small((L, hkv * hd), 0.02)},
        "wv": {"w": lecun((L, d, hkv * hd), d), "b": small((L, hkv * hd), 0.02)},
        "wo": {"w": lecun((L, hq * hd, d), hq * hd)},
    }
    layer = {
        "norm": {"scale": 1.0 + small((L, d), 0.1)},
        "attn": attn,
        "ffn_norm": {"scale": 1.0 + small((L, d), 0.1)},
        "ffn": {"gate": {"w": lecun((L, d, ff), d)},
                "up": {"w": lecun((L, d, ff), d)},
                "down": {"w": lecun((L, ff, d), ff)}},
    }
    return {"embed": {"table": small((V, d), 0.02)},
            "final_norm": {"scale": 1.0 + small((d,), 0.1)},
            "blocks": (layer,)}


def make_params(model: dict, seed32: int):
    """The served weights, made on the device in one jitted call, in the
    configuration's parameter dtype."""
    dtype = jnp.dtype(model["param_dtype"])

    @jax.jit
    def build(key):
        p = _params(model, key)
        return jax.tree.map(lambda x: x.astype(dtype), p)

    return jax.block_until_ready(build(jax.random.key(seed32)))


def _rms(x, scale, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def _rope(x, pos, theta):
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * inv           # (T, hd/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def hidden_states(model: dict, params, tokens):
    """Final-norm hidden states (T, d) of one causal sequence."""
    L, d, hq, hkv, hd = _dims(model)
    eps, theta = model["rms_norm_eps"], model["rope_theta"]
    T = tokens.shape[0]
    pos = jnp.arange(T)
    mask = pos[:, None] >= pos[None, :]
    x = params["embed"]["table"][tokens]

    def layer(x, p):
        a = p["attn"]
        h = _rms(x, p["norm"]["scale"], eps)
        q = (h @ a["wq"]["w"] + a["wq"]["b"]).reshape(T, hq, hd)
        k = (h @ a["wk"]["w"] + a["wk"]["b"]).reshape(T, hkv, hd)
        v = (h @ a["wv"]["w"] + a["wv"]["b"]).reshape(T, hkv, hd)
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
        g = hq // hkv
        k = jnp.repeat(k, g, axis=1)            # query head i uses kv i // g
        v = jnp.repeat(v, g, axis=1)
        s = jnp.einsum("thd,shd->hts", q, k) / math.sqrt(hd)
        s = jnp.where(mask[None], s.astype(jnp.float32), -jnp.inf)
        w = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        o = jnp.einsum("hts,shd->thd", w, v).reshape(T, hq * hd)
        x = x + o @ a["wo"]["w"]
        h = _rms(x, p["ffn_norm"]["scale"], eps)
        f = p["ffn"]
        x = x + (jax.nn.silu(h @ f["gate"]["w"]) * (h @ f["up"]["w"])) @ f["down"]["w"]
        return x, None

    x, _ = jax.lax.scan(layer, x, params["blocks"][0])
    return _rms(x, params["final_norm"]["scale"], eps)


def _logits(model, params, tokens, rows, dtype):
    """Logits at positions `rows`, every weight and activation in
    `dtype` (the norms and the softmax compute in float32 inside)."""
    p = jax.tree.map(lambda x: x.astype(dtype), params)
    h = hidden_states(model, p, tokens.astype(jnp.int32))[rows]
    return (h @ p["embed"]["table"].T).astype(jnp.float32)


@partial(jax.jit, static_argnames=("model_items", "lower"))
def _compare(params, tokens, rows, served, first, *, model_items, lower):
    """At the positions `rows` of a padded sequence (padding lies after
    every row read, so causal attention keeps it out; row 0 is the
    prompt's last position, whose logits give the first token): the gap
    by which each served token's reference logit lies below the
    reference's best, the standard deviation of each row's reference
    logits, and the mean absolute and mean squared gap between `first`,
    the served first-token logits, and the reference's at row 0.  With
    `lower`, the same for the control: the gaps of the tokens that the
    lower precision puts first, and its own logits at row 0."""
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
        low = _logits(model, params, tokens, rows, lower)
        out["control_gap"] = gap_of(low.argmax(-1))
        out["control_first"] = first_err(low[0])
    return out


def compare(model: dict, seed32: int, seqs, *, pad_to: int,
            precision: str, lower: str | None = None, chunk: int = 256):
    """Score served requests against the reference, rebuilding the
    weights from the seed.  `seqs` holds (prompt, served tokens, served
    first-token logits) per request; `precision` is the matmul precision
    the configuration states.  Per request: the gaps of its served tokens
    (see `_compare`), the reference logits' standard deviation at each
    of those positions, and [mean |d|, mean d^2] of its first-token
    logits; with `lower`, the control's gaps and first-token errors too.
    One padded length, so one program serves every request."""
    params = make_params(model, seed32)
    items = tuple(sorted(model.items()))
    res = []
    with jax.default_matmul_precision(precision):
        for prompt, served, first in seqs:
            seq = np.concatenate([prompt, served]).astype(np.int32)
            T, n = len(prompt), len(served)
            if len(seq) > pad_to:
                raise ValueError(f"sequence {len(seq)} exceeds {pad_to}")
            toks = np.zeros((pad_to,), np.int32)
            toks[:len(seq)] = seq
            parts = []
            for c0 in range(0, n, chunk):
                m = min(chunk, n - c0)
                rows = np.full((chunk,), T - 1, np.int32)
                rows[:m] = T - 1 + c0 + np.arange(m)
                tok = np.zeros((chunk,), np.int32)
                tok[:m] = served[c0:c0 + m]
                o = jax.device_get(_compare(
                    params, jnp.asarray(toks), jnp.asarray(rows),
                    jnp.asarray(tok), jnp.asarray(first, jnp.float32),
                    model_items=items, lower=lower))
                parts.append((o, m))
            r = {k: np.concatenate([o[k][:m] for o, m in parts])
                 for k in ("gap", "std", "control_gap") if k in parts[0][0]}
            for k in ("first", "control_first"):
                if k in parts[0][0]:
                    r[k] = np.asarray(parts[0][0][k], np.float64)
            res.append(r)
    del params
    return res
