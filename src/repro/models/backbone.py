"""Backbone assembly: ArchConfig -> init / forward / prefill / decode.

Layers are grouped into homogeneous *superblocks* (dense: 1 layer; jamba:
1 attention + 7 mamba; xLSTM: 7 mLSTM + 1 sLSTM) whose parameters are
stacked with a leading (n_superblocks,) axis and executed with
jax.lax.scan — this keeps HLO size and compile time bounded at
72-layer / 512-device scale.  Each superblock body is jax.checkpoint'ed
(remat) so train-time activation memory is O(layers * B * T * d_model)
instead of O(layers * B * T * d_ff).

Modes:
  forward(..., labels)      training loss (+ MoE aux losses)
  prefill(...)              logits of last position + decode cache
  decode_step(...)          one token with ring-buffer KV / recurrent state
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.nn.activations import mlp_apply, mlp_init, swiglu_ffn_apply, swiglu_ffn_init
from repro.nn.attention import (
    attention_apply,
    attention_decode_apply,
    attention_init,
    cross_attention_apply,
    cross_attention_decode,
    cross_kv,
    flash_attention,
    project_qkv,
)
from repro.nn.linear import dense_apply, dense_init, embedding_apply, embedding_init
from repro.nn.moe import moe_apply, moe_init
from repro.nn.module import split_keys
from repro.nn.norm import layernorm_apply, layernorm_init, rmsnorm_apply, rmsnorm_init
from repro.nn.rope import apply_rope
from repro.nn.ssm import (
    mamba_apply,
    mamba_decode_apply,
    mamba_decode_init_state,
    mamba_init,
)
from repro.nn.xlstm import (
    mlstm_apply,
    mlstm_decode_apply,
    mlstm_decode_init_state,
    mlstm_init,
    slstm_apply,
    slstm_decode_apply,
    slstm_decode_init_state,
    slstm_init,
)

# --------------------------------------------------------------- helpers ---


def _norm_init(cfg: ArchConfig, dim=None):
    dim = dim or cfg.d_model
    return rmsnorm_init(dim, cfg.dtype) if cfg.norm == "rmsnorm" else layernorm_init(dim, cfg.dtype)


def _norm_apply(cfg: ArchConfig, p, x):
    return rmsnorm_apply(p, x) if cfg.norm == "rmsnorm" else layernorm_apply(p, x)


def sinusoidal_positions(T: int, d: int, offset=0) -> jnp.ndarray:
    pos = (jnp.arange(T) + offset)[:, None].astype(jnp.float32)
    div = jnp.exp(jnp.arange(0, d, 2, dtype=jnp.float32) * (-math.log(10000.0) / d))
    pe = jnp.zeros((T, d), jnp.float32)
    pe = pe.at[:, 0::2].set(jnp.sin(pos * div))
    pe = pe.at[:, 1::2].set(jnp.cos(pos * div))
    return pe


def sublayer_specs(cfg: ArchConfig) -> list[dict]:
    """Per-sublayer spec for one superblock."""
    specs = []
    for j in range(cfg.superblock):
        if cfg.xlstm is not None:
            kind = "slstm" if j == cfg.xlstm.slstm_index else "mlstm"
            ffn = "none"
        elif cfg.hybrid is not None:
            kind = "attn" if j == cfg.hybrid.attn_index else "mamba"
            ffn = "moe" if (cfg.moe and j % cfg.moe.every == cfg.moe.every - 1) else "dense"
        else:
            kind = "attn"
            ffn = "moe" if cfg.moe else "dense"
        specs.append({"kind": kind, "ffn": ffn})
    return specs


def _mamba_kwargs(cfg: ArchConfig) -> dict:
    h = cfg.hybrid
    return dict(d_state=h.d_state, d_conv=h.d_conv)


# ------------------------------------------------------------------ init ---


def _init_sublayer(cfg: ArchConfig, spec: dict, key) -> dict:
    kk = split_keys(key, ["mix", "norm", "ffn", "ffn_norm", "extra", "shared"])
    p: dict[str, Any] = {"norm": _norm_init(cfg)}
    if spec["kind"] == "attn":
        p["attn"] = attention_init(kk["mix"], cfg.d_model, cfg.n_heads,
                                   cfg.n_kv_heads, cfg.resolved_head_dim,
                                   qkv_bias=cfg.qkv_bias, dtype=cfg.dtype)
    elif spec["kind"] == "mamba":
        p["mamba"] = mamba_init(kk["mix"], cfg.d_model, expand=cfg.hybrid.expand,
                                d_state=cfg.hybrid.d_state, d_conv=cfg.hybrid.d_conv,
                                dtype=cfg.dtype)
    elif spec["kind"] == "mlstm":
        p["cell"] = mlstm_init(kk["mix"], cfg.d_model, cfg.n_heads, dtype=cfg.dtype)
    elif spec["kind"] == "slstm":
        p["cell"] = slstm_init(kk["mix"], cfg.d_model, cfg.n_heads, dtype=cfg.dtype)

    if spec["ffn"] == "dense":
        p["ffn_norm"] = _norm_init(cfg)
        if cfg.norm == "layernorm":  # whisper-style plain MLP
            p["ffn"] = mlp_init(kk["ffn"], cfg.d_model, cfg.d_ff, dtype=cfg.dtype)
        else:
            p["ffn"] = swiglu_ffn_init(kk["ffn"], cfg.d_model, cfg.d_ff, dtype=cfg.dtype)
    elif spec["ffn"] == "moe":
        p["ffn_norm"] = _norm_init(cfg)
        p["moe"] = moe_init(kk["ffn"], cfg.d_model, cfg.moe.expert_d_ff,
                            cfg.moe.n_experts, dtype=cfg.dtype)
        if cfg.moe.dense_residual_ff:
            p["dense_res"] = swiglu_ffn_init(kk["extra"], cfg.d_model,
                                             cfg.moe.dense_residual_ff, dtype=cfg.dtype)
        if cfg.moe.shared_expert_ff:
            p["shared"] = swiglu_ffn_init(kk["shared"], cfg.d_model,
                                          cfg.moe.shared_expert_ff, dtype=cfg.dtype)
    return p


def _init_superblock(cfg: ArchConfig, key):
    specs = sublayer_specs(cfg)
    keys = jax.random.split(key, len(specs))
    return tuple(_init_sublayer(cfg, s, k) for s, k in zip(specs, keys))


def _init_encoder_layer(cfg: ArchConfig, key) -> dict:
    kk = split_keys(key, ["attn", "norm", "ffn", "ffn_norm"])
    return {
        "norm": _norm_init(cfg),
        "attn": attention_init(kk["attn"], cfg.d_model, cfg.n_heads, cfg.n_heads,
                               cfg.resolved_head_dim, dtype=cfg.dtype),
        "ffn_norm": _norm_init(cfg),
        "ffn": mlp_init(kk["ffn"], cfg.d_model, cfg.d_ff, dtype=cfg.dtype),
    }


def _init_decoder_layer_encdec(cfg: ArchConfig, key) -> dict:
    kk = split_keys(key, ["self", "cross", "norm", "cross_norm", "ffn", "ffn_norm"])
    return {
        "norm": _norm_init(cfg),
        "attn": attention_init(kk["self"], cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                               cfg.resolved_head_dim, dtype=cfg.dtype),
        "cross_norm": _norm_init(cfg),
        "cross": attention_init(kk["cross"], cfg.d_model, cfg.n_heads, cfg.n_heads,
                                cfg.resolved_head_dim, dtype=cfg.dtype),
        "ffn_norm": _norm_init(cfg),
        "ffn": mlp_init(kk["ffn"], cfg.d_model, cfg.d_ff, dtype=cfg.dtype),
    }


def init_params(cfg: ArchConfig, key) -> dict:
    kk = split_keys(key, ["embed", "blocks", "final_norm", "head", "vision",
                          "encoder"])
    params: dict[str, Any] = {
        "embed": embedding_init(kk["embed"], cfg.vocab, cfg.d_model, cfg.dtype),
        "final_norm": _norm_init(cfg),
    }
    if cfg.encdec is not None:
        enc_keys = jax.random.split(kk["encoder"], cfg.encdec.n_encoder_layers)
        params["encoder"] = jax.vmap(partial(_init_encoder_layer, cfg))(enc_keys)
        params["enc_final_norm"] = _norm_init(cfg)
        dec_keys = jax.random.split(kk["blocks"], cfg.n_layers)
        params["blocks"] = jax.vmap(partial(_init_decoder_layer_encdec, cfg))(dec_keys)
    else:
        sb_keys = jax.random.split(kk["blocks"], cfg.n_superblocks)
        params["blocks"] = jax.vmap(partial(_init_superblock, cfg))(sb_keys)
    if cfg.vlm is not None:
        params["vision_proj"] = dense_init(kk["vision"], cfg.vlm.vision_dim,
                                           cfg.d_model, dtype=cfg.dtype)
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(kk["head"], cfg.d_model, cfg.vocab,
                                       use_bias=False, dtype=cfg.dtype)
    return params


# --------------------------------------------------------------- forward ---


def _apply_ffn(cfg: ArchConfig, spec, p, x, *, dropless: bool = False):
    """Post-mixer FFN sublayer.  Returns (x, aux).

    dropless=True (inference) sizes MoE capacity so no token is dropped;
    training keeps the configured capacity factor (tokens over capacity
    fall through the residual, standard GShard/Switch behaviour).
    """
    aux = {}
    if spec["ffn"] == "none":
        return x, aux
    h = _norm_apply(cfg, p["ffn_norm"], x)
    if spec["ffn"] == "dense":
        if cfg.norm == "layernorm":
            y = mlp_apply(p["ffn"], h)
        else:
            y = swiglu_ffn_apply(p["ffn"], h)
    else:
        if dropless:
            # provably dropless when the expert count is small; for very
            # wide MoEs (arctic: 128e) a 4x capacity factor keeps memory
            # bounded with negligible overflow probability
            e_over_k = cfg.moe.n_experts / cfg.moe.top_k
            cap = e_over_k if cfg.moe.n_experts <= 8 else min(4.0, e_over_k)
        else:
            cap = cfg.moe.capacity_factor
        y, aux = moe_apply(p["moe"], h, top_k=cfg.moe.top_k,
                           capacity_factor=cap)
        if "dense_res" in p:
            y = y + swiglu_ffn_apply(p["dense_res"], h)
        if "shared" in p:
            y = y + swiglu_ffn_apply(p["shared"], h)
    return x + y, aux


def _apply_sublayer(cfg: ArchConfig, spec, p, x, *, window: int,
                    dropless: bool = False, kv_valid_len=None):
    """Full-sequence (train/prefill) sublayer.  Returns (x, kv_or_state, aux)."""
    h = _norm_apply(cfg, p["norm"], x)
    state = None
    if spec["kind"] == "attn":
        y, k, v = attention_apply(
            p["attn"], h, n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
            head_dim=cfg.resolved_head_dim, causal=True, window=window,
            rope_theta=cfg.rope_theta, return_kv=True,
            kv_valid_len=kv_valid_len)
        state = (k, v)
    elif spec["kind"] == "mamba":
        y, state = mamba_apply(p["mamba"], h, return_state=True, **_mamba_kwargs(cfg))
    elif spec["kind"] == "mlstm":
        y, state = mlstm_apply(p["cell"], h, n_heads=cfg.n_heads, return_state=True)
    elif spec["kind"] == "slstm":
        y, state = slstm_apply(p["cell"], h, n_heads=cfg.n_heads, return_state=True)
    x = x + y
    x, aux = _apply_ffn(cfg, spec, p, x, dropless=dropless)
    return x, state, aux


def _zero_aux():
    return {"load_balance_loss": jnp.zeros((), jnp.float32),
            "dropped_fraction": jnp.zeros((), jnp.float32)}


def _acc_aux(acc, aux, n: int):
    if not aux:
        return acc
    return {"load_balance_loss": acc["load_balance_loss"] + aux["load_balance_loss"] / n,
            "dropped_fraction": acc["dropped_fraction"] + aux["dropped_fraction"] / n}


def _moe_layer_count(cfg: ArchConfig) -> int:
    return sum(1 for s in sublayer_specs(cfg) if s["ffn"] == "moe") * cfg.n_superblocks or 1


def _run_superblocks(cfg: ArchConfig, params, x, *, window: int,
                     collect_cache: bool = False, remat: bool = True,
                     dropless: bool = False, kv_valid_len=None):
    """Scan over stacked superblocks.  Returns (x, aux, caches or None)."""
    specs = sublayer_specs(cfg)
    n_moe = _moe_layer_count(cfg)

    # NOTE: sb_params is a tuple of per-sublayer dicts (the scan strips the
    # stacked leading axis); iterate positionally.
    def body(carry, sb_params):
        h, aux_acc = carry
        states = []
        for spec, p in zip(specs, sb_params):
            h, st, aux = _apply_sublayer(cfg, spec, p, h, window=window,
                                         dropless=dropless,
                                         kv_valid_len=kv_valid_len)
            aux_acc = _acc_aux(aux_acc, aux, n_moe)
            states.append(st)
        out = _stack_states(cfg, specs, states) if collect_cache else None
        return (h, aux_acc), out

    if remat:
        body = jax.checkpoint(body)
    (x, aux), caches = jax.lax.scan(body, (x, _zero_aux()), params["blocks"])
    return x, aux, caches


def _stack_states(cfg, specs, states):
    """Group per-sublayer prefill states by kind for the decode cache."""
    out = {}
    attn_states = [s for spec, s in zip(specs, states) if spec["kind"] == "attn"]
    if attn_states:
        out["k"] = jnp.stack([k for k, _ in attn_states])   # (n_attn, B, T, Hkv, D)
        out["v"] = jnp.stack([v for _, v in attn_states])
    mamba_states = [s for spec, s in zip(specs, states) if spec["kind"] == "mamba"]
    if mamba_states:
        out["mamba_conv"] = jnp.stack([s["conv"] for s in mamba_states])
        out["mamba_ssm"] = jnp.stack([s["ssm"] for s in mamba_states])
    ml = [s for spec, s in zip(specs, states) if spec["kind"] == "mlstm"]
    if ml:
        out["mlstm_C"] = jnp.stack([s["C"] for s in ml])
        out["mlstm_n"] = jnp.stack([s["n"] for s in ml])
        out["mlstm_m"] = jnp.stack([s["m"] for s in ml])
    sl = [s for spec, s in zip(specs, states) if spec["kind"] == "slstm"]
    if sl:
        out["slstm_h"] = jnp.stack([s["h"] for s in sl])
        out["slstm_c"] = jnp.stack([s["c"] for s in sl])
        out["slstm_n"] = jnp.stack([s["n"] for s in sl])
        out["slstm_m"] = jnp.stack([s["m"] for s in sl])
    return out


def _readout_weight(cfg: ArchConfig, params):
    if cfg.tie_embeddings:
        return params["embed"]["table"].T          # (d, V)
    return params["lm_head"]["w"]


def chunked_cross_entropy(x, w_vocab, labels, *, chunk: int = 512,
                          ignore_label: int = -100):
    """Mean CE without materializing (B, T, V): scan over T chunks.

    x: (B, T, d); w_vocab: (d, V); labels: (B, T) int32.
    """
    B, T, d = x.shape
    Tc = min(chunk, T)
    n_chunks = -(-T // Tc)
    Tp = n_chunks * Tc
    xp = jnp.pad(x, ((0, 0), (0, Tp - T), (0, 0)))
    lp = jnp.pad(labels, ((0, 0), (0, Tp - T)), constant_values=ignore_label)

    V = w_vocab.shape[-1]

    def body(acc, idx):
        xc = jax.lax.dynamic_slice_in_dim(xp, idx * Tc, Tc, axis=1)
        lc = jax.lax.dynamic_slice_in_dim(lp, idx * Tc, Tc, axis=1)
        logits = (xc.astype(jnp.float32) @ w_vocab.astype(jnp.float32))
        logz = jax.nn.logsumexp(logits, axis=-1)
        # gold logit via a fused one-hot reduction: keeps the vocab axis
        # sharded (a take_along_axis would force an all-gather of logits)
        onehot = (jax.lax.broadcasted_iota(jnp.int32, logits.shape, 2)
                  == jnp.maximum(lc, 0)[..., None])
        gold = jnp.sum(jnp.where(onehot, logits, 0.0), axis=-1)
        mask = (lc != ignore_label).astype(jnp.float32)
        loss_sum, cnt = acc
        return (loss_sum + jnp.sum((logz - gold) * mask), cnt + jnp.sum(mask)), None

    (loss_sum, cnt), _ = jax.lax.scan(body, (jnp.zeros(()), jnp.zeros(())),
                                      jnp.arange(n_chunks))
    return loss_sum / jnp.maximum(cnt, 1.0)


# ------------------------------------------------------------- embedding ---


def _embed_inputs(cfg: ArchConfig, params, batch) -> tuple[jnp.ndarray, jnp.ndarray | None]:
    """Returns (x (B, T, d), labels or None)."""
    tokens = batch["tokens"]
    x = embedding_apply(params["embed"], tokens)
    labels = batch.get("labels")
    if cfg.vlm is not None and "patches" in batch:
        pv = dense_apply(params["vision_proj"], batch["patches"].astype(cfg.dtype))
        x = jnp.concatenate([pv, x], axis=1)
        if labels is not None:
            pad = jnp.full(pv.shape[:2], -100, labels.dtype)
            labels = jnp.concatenate([pad, labels], axis=1)
    if cfg.rope_theta == 0:  # sinusoidal positions (whisper)
        x = x + sinusoidal_positions(x.shape[1], cfg.d_model).astype(x.dtype)
    return x, labels


def _run_encoder(cfg: ArchConfig, params, frames) -> jnp.ndarray:
    """Whisper-style encoder over stubbed frame embeddings (B, F, d)."""
    x = frames.astype(cfg.dtype)
    x = x + sinusoidal_positions(x.shape[1], cfg.d_model).astype(x.dtype)

    def body(h, layer):
        a = _norm_apply(cfg, layer["norm"], h)
        h = h + attention_apply(layer["attn"], a, n_heads=cfg.n_heads,
                                n_kv_heads=cfg.n_heads,
                                head_dim=cfg.resolved_head_dim, causal=False,
                                rope_theta=0.0)
        f = _norm_apply(cfg, layer["ffn_norm"], h)
        h = h + mlp_apply(layer["ffn"], f)
        return h, None

    x, _ = jax.lax.scan(jax.checkpoint(body), x, params["encoder"])
    return _norm_apply(cfg, params["enc_final_norm"], x)


def _run_decoder_encdec(cfg: ArchConfig, params, x, enc_out, *,
                        collect_cache: bool = False):
    """Whisper-style decoder (full sequence)."""

    def body(carry, layer):
        h = carry
        a = _norm_apply(cfg, layer["norm"], h)
        sa, k, v = attention_apply(layer["attn"], a, n_heads=cfg.n_heads,
                                   n_kv_heads=cfg.n_kv_heads,
                                   head_dim=cfg.resolved_head_dim, causal=True,
                                   rope_theta=0.0, return_kv=True)
        h = h + sa
        c = _norm_apply(cfg, layer["cross_norm"], h)
        ck, cv = cross_kv(layer["cross"], enc_out, n_kv_heads=cfg.n_heads,
                          head_dim=cfg.resolved_head_dim)
        h = h + cross_attention_apply(layer["cross"], c, ck, cv,
                                      n_heads=cfg.n_heads,
                                      head_dim=cfg.resolved_head_dim)
        f = _norm_apply(cfg, layer["ffn_norm"], h)
        h = h + mlp_apply(layer["ffn"], f)
        cache = {"k": k, "v": v, "ck": ck, "cv": cv} if collect_cache else None
        return h, cache

    x, caches = jax.lax.scan(jax.checkpoint(body), x, params["blocks"])
    return x, caches


# ------------------------------------------------------------ public API ---


def forward_loss(cfg: ArchConfig, params, batch, *, window: int = 0,
                 loss_chunk: int = 512):
    """Training forward: mean next-token CE + aux losses.

    batch: tokens (B,T), labels (B,T) [+ patches/frames for vlm/audio].
    """
    window = window or cfg.sliding_window
    if cfg.encdec is not None:
        enc_out = _run_encoder(cfg, params, batch["frames"])
        x, labels = _embed_inputs(cfg, params, batch)
        x, _ = _run_decoder_encdec(cfg, params, x, enc_out)
        aux = _zero_aux()
    else:
        x, labels = _embed_inputs(cfg, params, batch)
        x, aux, _ = _run_superblocks(cfg, params, x, window=window)
    x = _norm_apply(cfg, params["final_norm"], x)
    ce = chunked_cross_entropy(x, _readout_weight(cfg, params), labels,
                               chunk=loss_chunk)
    lb_weight = 0.01 if cfg.moe is not None else 0.0
    loss = ce + lb_weight * aux["load_balance_loss"]
    metrics = {"ce": ce, **aux}
    return loss, metrics


def forward_hidden(cfg: ArchConfig, params, batch, *, window: int = 0):
    """Final hidden states (B, T, d) — used by AgileNN's remote path."""
    window = window or cfg.sliding_window
    if cfg.encdec is not None:
        enc_out = _run_encoder(cfg, params, batch["frames"])
        x, _ = _embed_inputs(cfg, params, batch)
        x, _ = _run_decoder_encdec(cfg, params, x, enc_out)
    else:
        x, _ = _embed_inputs(cfg, params, batch)
        x, _, _ = _run_superblocks(cfg, params, x, window=window)
    return _norm_apply(cfg, params["final_norm"], x)


# ------------------------------------------------------------- decoding ----


def cache_window(cfg: ArchConfig, context_len: int, *, long_context: bool = False) -> int:
    """KV ring-buffer capacity for a decode context of `context_len`."""
    if cfg.sliding_window:
        return min(cfg.sliding_window, context_len)
    if long_context and cfg.hybrid is None and cfg.xlstm is None:
        # full-attention archs switch to the sliding-window variant at 500k
        return min(cfg.long_context_window, context_len)
    return context_len


def init_cache(cfg: ArchConfig, batch: int, context_len: int, *,
               long_context: bool = False) -> dict:
    """Zero decode cache (the dry-run passes ShapeDtypeStructs of this tree)."""
    specs = sublayer_specs(cfg)
    n_sb = cfg.n_superblocks
    S = cache_window(cfg, context_len, long_context=long_context)
    hd = cfg.resolved_head_dim
    out: dict[str, Any] = {}
    if cfg.encdec is not None:
        F = cfg.encdec.n_frames
        L = cfg.n_layers
        out["k"] = jnp.zeros((L, batch, S, cfg.n_kv_heads, hd), cfg.dtype)
        out["v"] = jnp.zeros((L, batch, S, cfg.n_kv_heads, hd), cfg.dtype)
        out["ck"] = jnp.zeros((L, batch, F, cfg.n_heads, hd), cfg.dtype)
        out["cv"] = jnp.zeros((L, batch, F, cfg.n_heads, hd), cfg.dtype)
        return out
    n_attn = sum(1 for s in specs if s["kind"] == "attn")
    n_mamba = sum(1 for s in specs if s["kind"] == "mamba")
    n_ml = sum(1 for s in specs if s["kind"] == "mlstm")
    n_sl = sum(1 for s in specs if s["kind"] == "slstm")
    if n_attn:
        shape = (n_sb, n_attn, batch, S, cfg.n_kv_heads, hd)
        out["k"] = jnp.zeros(shape, cfg.dtype)
        out["v"] = jnp.zeros(shape, cfg.dtype)
    if n_mamba:
        h = cfg.hybrid
        d_inner = h.expand * cfg.d_model
        out["mamba_conv"] = jnp.zeros((n_sb, n_mamba, batch, h.d_conv - 1, d_inner), cfg.dtype)
        out["mamba_ssm"] = jnp.zeros((n_sb, n_mamba, batch, d_inner, h.d_state), jnp.float32)
    if n_ml:
        out["mlstm_C"] = jnp.zeros((n_sb, n_ml, batch, cfg.n_heads, hd, hd), jnp.float32)
        out["mlstm_n"] = jnp.zeros((n_sb, n_ml, batch, cfg.n_heads, hd), jnp.float32)
        out["mlstm_m"] = jnp.full((n_sb, n_ml, batch, cfg.n_heads), -1e30, jnp.float32)
    if n_sl:
        out["slstm_h"] = jnp.zeros((n_sb, n_sl, batch, cfg.d_model), cfg.dtype)
        out["slstm_c"] = jnp.zeros((n_sb, n_sl, batch, cfg.d_model), jnp.float32)
        out["slstm_n"] = jnp.zeros((n_sb, n_sl, batch, cfg.d_model), jnp.float32)
        out["slstm_m"] = jnp.full((n_sb, n_sl, batch, cfg.d_model), -1e30, jnp.float32)
    return out


# carried-cache leaves of each recurrent kind, as (leaf, state key)
_STATE_LEAVES = {
    "mamba": (("mamba_conv", "conv"), ("mamba_ssm", "ssm")),
    "mlstm": (("mlstm_C", "C"), ("mlstm_n", "n"), ("mlstm_m", "m")),
    "slstm": (("slstm_h", "h"), ("slstm_c", "c"), ("slstm_n", "n"),
              ("slstm_m", "m")),
}


def _layer_get(leaf, sb, i: int):
    """Sublayer i of superblock sb (traced) of a (n_sb, n_kind, ...) leaf."""
    start = (sb, i) + (0,) * (leaf.ndim - 2)
    return jax.lax.dynamic_slice(leaf, start, (1, 1) + leaf.shape[2:])[0, 0]


def _layer_put(leaf, sb, i: int, new):
    """Write sublayer i of superblock sb into the leaf, in place."""
    start = (sb, i) + (0,) * (leaf.ndim - 2)
    return jax.lax.dynamic_update_slice(
        leaf, new.astype(leaf.dtype)[None, None], start)


def _decode_sublayer(cfg: ArchConfig, spec, p, x, cache, sb, counters,
                     cache_len):
    """One-token sublayer of superblock sb (traced) against the whole
    carried decode cache, which it updates in place.  counters track the
    per-kind index within the superblock."""
    h = _norm_apply(cfg, p["norm"], x)
    kind = spec["kind"]
    i = counters[kind]
    counters[kind] += 1
    cache = dict(cache)
    if kind == "attn":
        y, cache["k"], cache["v"] = attention_decode_apply(
            p["attn"], h, cache["k"], cache["v"], cache_len,
            n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
            head_dim=cfg.resolved_head_dim, rope_theta=cfg.rope_theta,
            layer=(sb, i))
    else:
        leaves = _STATE_LEAVES[kind]
        st = {nm: _layer_get(cache[leaf], sb, i) for leaf, nm in leaves}
        if kind == "mamba":
            y, st = mamba_decode_apply(p["mamba"], h, st, **_mamba_kwargs(cfg))
        elif kind == "mlstm":
            y, st = mlstm_decode_apply(p["cell"], h, st, n_heads=cfg.n_heads)
        else:
            y, st = slstm_decode_apply(p["cell"], h, st, n_heads=cfg.n_heads)
        for leaf, nm in leaves:
            cache[leaf] = _layer_put(cache[leaf], sb, i, st[nm])
    x = x + y
    x, _ = _apply_ffn(cfg, spec, p, x, dropless=True)
    return x, cache


def decode_step(cfg: ArchConfig, params, tokens, cache, cache_len):
    """One decoding step.  tokens: (B, 1) int32; cache from init_cache/prefill.

    cache_len may be a scalar (all rows at one depth, the classic path) or
    a (B,) vector (slot-pool decode: every row tracks its own context
    depth; attention writes/attends per row).  Returns (logits (B, vocab),
    new_cache).

    The layer loop carries the whole cache, indexed by superblock: each
    attention layer writes its new K/V with one update at [layer, row,
    cache_len % S] and attends that layer of the stacked pool (the TPU
    kernel reads its pages straight from it), recurrent layers update
    their state slice.  Under a donated cache, as in the scheduler's
    decode chunk, the step therefore moves one token of K/V per layer,
    not the pool.  The encoder-decoder branch keeps its per-layer scan.
    """
    if jnp.ndim(cache_len) > 0:
        assert cfg.rope_theta > 0, \
            "per-row cache_len needs RoPE positions (absolute sinusoidal " \
            "offsets are scalar-only)"
    x = embedding_apply(params["embed"], tokens)
    if cfg.rope_theta == 0:
        x = x + sinusoidal_positions(1, cfg.d_model, offset=cache_len).astype(x.dtype)

    if cfg.encdec is not None:
        def body(h, xs):
            layer, c = xs
            a = _norm_apply(cfg, layer["norm"], h)
            sa, k_new, v_new = attention_decode_apply(
                layer["attn"], a, c["k"], c["v"], cache_len,
                n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
                head_dim=cfg.resolved_head_dim, rope_theta=0.0)
            h = h + sa
            cr = _norm_apply(cfg, layer["cross_norm"], h)
            h = h + cross_attention_decode(layer["cross"], cr, c["ck"], c["cv"],
                                           n_heads=cfg.n_heads,
                                           head_dim=cfg.resolved_head_dim)
            f = _norm_apply(cfg, layer["ffn_norm"], h)
            h = h + mlp_apply(layer["ffn"], f)
            return h, {"k": k_new, "v": v_new, "ck": c["ck"], "cv": c["cv"]}

        x, new_cache = jax.lax.scan(body, x, (params["blocks"], cache))
    else:
        specs = sublayer_specs(cfg)

        def body(carry, sb_params):
            h, cache, sb = carry
            counters = dict.fromkeys(_STATE_LEAVES, 0) | {"attn": 0}
            for spec, p in zip(specs, sb_params):
                h, cache = _decode_sublayer(cfg, spec, p, h, cache, sb,
                                            counters, cache_len)
            return (h, cache, sb + 1), None

        (x, new_cache, _), _ = jax.lax.scan(
            body, (x, cache, jnp.zeros((), jnp.int32)), params["blocks"])

    x = _norm_apply(cfg, params["final_norm"], x)
    logits = (x[:, 0].astype(jnp.float32)
              @ _readout_weight(cfg, params).astype(jnp.float32))
    return logits, new_cache


def prefill(cfg: ArchConfig, params, batch, *, long_context: bool = False,
            max_len: int = 0, lengths=None):
    """Prefill: run the context, return (last-token logits, decode cache).

    batch: tokens (B, T) [+ patches/frames].  The returned cache is ring-
    compacted to cache_window(max_len) capacity (max_len: total context +
    generation budget; defaults to prompt length + 64).

    lengths: optional (B,) valid prompt lengths for a right-padded
    (bucketed) batch.  Pad keys are masked out of attention for every row
    — real-token activations are bit-identical to an unpadded prefill —
    and the returned logits are gathered at each row's last real token.
    Padded prefill is attention-only (recurrent state would integrate the
    pad tokens) and requires the bucket to fit the cache window.
    """
    tokens = batch["tokens"]
    B, T = tokens.shape
    window = cfg.sliding_window
    if lengths is not None:
        assert (cfg.encdec is None and cfg.hybrid is None
                and cfg.xlstm is None and "patches" not in batch), \
            "bucketed (right-padded) prefill supports attention-only " \
            "token batches"
    if cfg.encdec is not None:
        enc_out = _run_encoder(cfg, params, batch["frames"])
        x, _ = _embed_inputs(cfg, params, batch)
        x, caches = _run_decoder_encdec(cfg, params, x, enc_out, collect_cache=True)
        total_T = x.shape[1]
        S = cache_window(cfg, max_len or total_T + 64, long_context=long_context)
        caches = {
            "k": _ring_compact(caches["k"], S, total_T),
            "v": _ring_compact(caches["v"], S, total_T),
            "ck": caches["ck"], "cv": caches["cv"],
        }
    else:
        x, _ = _embed_inputs(cfg, params, batch)
        total_T = x.shape[1]
        eff_window = window or (cache_window(cfg, total_T, long_context=long_context)
                                if long_context else 0)
        x, _, caches = _run_superblocks(cfg, params, x, window=eff_window,
                                        collect_cache=True, dropless=True,
                                        kv_valid_len=lengths)
        S = cache_window(cfg, max_len or total_T + 64, long_context=long_context)
        if lengths is not None:
            assert total_T <= S, \
                f"bucket {total_T} exceeds cache window {S}: ring " \
                "compaction would drop real (non-pad) tokens"
        if "k" in caches:
            caches = dict(caches)
            caches["k"] = _ring_compact(caches["k"], S, total_T)
            caches["v"] = _ring_compact(caches["v"], S, total_T)
    x = _norm_apply(cfg, params["final_norm"], x)
    last = x[:, -1] if lengths is None else x[jnp.arange(B), lengths - 1]
    logits = (last.astype(jnp.float32)
              @ _readout_weight(cfg, params).astype(jnp.float32))
    return logits, caches, total_T


def prefill_chunk(cfg: ArchConfig, params, tokens, cache, depth, *,
                  attend_width: int, last_index=0):
    """Advance a chunked prefill by one token segment.

    tokens: (B, C) the next C prompt tokens (pad-extended past the prompt
    tail); cache: k/v decode cache from `init_cache` whose rows [0, depth)
    already hold the previous segments' keys; depth: () tokens already
    prefilled (traced — one compiled program serves every segment of a
    bucket).  Attention runs the segment's queries against the first
    `attend_width` cache slots via `flash_attention(q_offset=depth)`, so
    a row at absolute position depth+i sees exactly the keys a one-shot
    prefill of the same padded width would show it — segment boundaries
    cannot move a logit by one ULP.  Stale keys past depth+C are causally
    masked (slot index == absolute position for a non-ring prefill).

    Returns (logits (B, vocab) at segment row `last_index`, new cache).
    Chunked prefill is attention-only, like bucketed prefill: recurrent
    state would integrate pad tokens, and SWA rings compact slots away
    from the slot==position layout this relies on.
    """
    assert (cfg.encdec is None and cfg.hybrid is None and cfg.xlstm is None
            and cfg.vlm is None and cfg.moe is None and cfg.rope_theta > 0
            and cfg.sliding_window == 0), \
        f"{cfg.name}: chunked prefill needs a pure-attention dense-FFN " \
        "RoPE decoder (MoE capacity couples rows across the segment)"
    B, C = tokens.shape
    assert attend_width <= cache["k"].shape[3], (attend_width, cache["k"].shape)
    specs = sublayer_specs(cfg)
    x = embedding_apply(params["embed"], tokens)
    depth = jnp.asarray(depth, jnp.int32)
    positions = depth + jnp.arange(C)[None, :]
    hd = cfg.resolved_head_dim

    def body(h, xs):
        sb_params, cache_sb = xs
        counters = {"attn": 0}
        for spec, p in zip(specs, sb_params):
            hn = _norm_apply(cfg, p["norm"], h)
            i = counters["attn"]
            q, k, v = project_qkv(p["attn"], hn, n_heads=cfg.n_heads,
                                  n_kv_heads=cfg.n_kv_heads, head_dim=hd)
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)
            kc = jax.lax.dynamic_update_slice_in_dim(
                cache_sb["k"][i], k.astype(cache_sb["k"].dtype), depth,
                axis=1)
            vc = jax.lax.dynamic_update_slice_in_dim(
                cache_sb["v"][i], v.astype(cache_sb["v"].dtype), depth,
                axis=1)
            cache_sb = dict(cache_sb)
            cache_sb["k"] = cache_sb["k"].at[i].set(kc)
            cache_sb["v"] = cache_sb["v"].at[i].set(vc)
            counters["attn"] += 1
            out = flash_attention(
                q, jax.lax.slice_in_dim(kc, 0, attend_width, axis=1),
                jax.lax.slice_in_dim(vc, 0, attend_width, axis=1),
                causal=True, q_offset=depth)
            h = h + dense_apply(p["attn"]["wo"],
                                out.reshape(B, C, cfg.n_heads * hd))
            h, _ = _apply_ffn(cfg, spec, p, h, dropless=True)
        return h, cache_sb

    x, new_cache = jax.lax.scan(body, x, (params["blocks"], cache))
    x = _norm_apply(cfg, params["final_norm"], x)
    last = x[jnp.arange(B), jnp.asarray(last_index)]
    logits = (last.astype(jnp.float32)
              @ _readout_weight(cfg, params).astype(jnp.float32))
    return logits, new_cache


def _ring_compact(kv, S: int, T: int):
    """(..., B, T, H, D) -> ring buffer (..., B, S, H, D) holding the last S
    tokens at slots (pos % S)."""
    tail = jax.lax.slice_in_dim(kv, max(0, T - S), T, axis=-3)
    if T <= S:
        pad = [(0, 0)] * kv.ndim
        pad[-3] = (0, S - T)
        return jnp.pad(tail, pad)
    return jnp.roll(tail, T % S, axis=-3)
