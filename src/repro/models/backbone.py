"""Backbone assembly: ArchConfig -> init / forward / prefill / decode.

Layers are grouped into homogeneous *superblocks* (dense: 1 layer; jamba:
1 attention + 7 mamba; xLSTM: 7 mLSTM + 1 sLSTM) whose parameters are
stacked with a leading (n_superblocks,) axis and executed with
jax.lax.scan — this keeps HLO size and compile time bounded at
72-layer / 512-device scale.  Each superblock body is jax.checkpoint'ed
(remat) so train-time activation memory is O(layers * B * T * d_model)
instead of O(layers * B * T * d_ff).

A model may lead with dense layers before its superblocks
(`ArchConfig.first_dense`, DeepSeek-V3's first_k_dense_replace): they
form a stack of their own (params["lead"]), scanned before the
superblocks; the decode cache counts them first along its superblock
axis.  Attention is GQA, or multi-head latent attention (`ArchConfig.mla`,
`nn.mla`), whose cache is one "latent" leaf of rows shared by all heads.

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
from repro.nn.mla import mla_apply, mla_chunk_attend, mla_decode_apply, mla_init, mla_latent
from repro.nn.moe import moe_apply, moe_init, moe_serve
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
    return (rmsnorm_apply(p, x, eps=cfg.norm_eps) if cfg.norm == "rmsnorm"
            else layernorm_apply(p, x))


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


_LEAD_SPECS = [{"kind": "attn", "ffn": "dense"}]


def _stacks(cfg: ArchConfig, params) -> list:
    """(sublayer specs, stacked params, index of the stack's first layer
    along the cache's superblock axis) of each scanned stack: the leading
    dense layers, if any, then the superblocks."""
    out = []
    if cfg.first_dense:
        out.append((_LEAD_SPECS, params["lead"], 0))
    out.append((sublayer_specs(cfg), params["blocks"], cfg.first_dense))
    return out


_EXPERT_LEAVES = ("gate", "up", "down")


def _hoist_experts(specs, stack):
    """A serving scan's stack with each expert layer's expert weights
    taken out, and those weights, still stacked over the stack's layers
    (one dict per sublayer, None where it has no experts): the expert
    kernel reads the layer it needs from the stack in place, where the
    scan would first slice a copy of all of a layer's experts."""
    stack, experts = list(stack), []
    for j, spec in enumerate(specs):
        if spec["ffn"] != "moe":
            experts.append(None)
            continue
        moe = dict(stack[j]["moe"])
        experts.append({k: moe.pop(k) for k in _EXPERT_LEAVES})
        stack[j] = dict(stack[j], moe=moe)
    return tuple(stack), experts


def _with_experts(p, experts):
    """Sublayer params p with its hoisted expert stacks put back."""
    return p if experts is None else dict(p, moe=dict(p["moe"], **experts))


def _mamba_kwargs(cfg: ArchConfig) -> dict:
    h = cfg.hybrid
    return dict(d_state=h.d_state, d_conv=h.d_conv)


# ------------------------------------------------------------------ init ---


def _init_sublayer(cfg: ArchConfig, spec: dict, key) -> dict:
    kk = split_keys(key, ["mix", "norm", "ffn", "ffn_norm", "extra", "shared"])
    p: dict[str, Any] = {"norm": _norm_init(cfg)}
    if spec["kind"] == "attn" and cfg.mla is not None:
        p["attn"] = mla_init(kk["mix"], cfg.d_model, cfg.n_heads, cfg.mla,
                             dtype=cfg.dtype)
    elif spec["kind"] == "attn":
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
                            cfg.moe.n_experts, dtype=cfg.dtype,
                            n_held=cfg.moe.n_held,
                            select_bias=cfg.moe.select_bias)
        if cfg.moe.dense_residual_ff:
            p["dense_res"] = swiglu_ffn_init(kk["extra"], cfg.d_model,
                                             cfg.moe.dense_residual_ff, dtype=cfg.dtype)
        if cfg.moe.shared_expert_ff:
            p["shared"] = swiglu_ffn_init(kk["shared"], cfg.d_model,
                                          cfg.moe.shared_expert_ff, dtype=cfg.dtype)
    return p


def _init_superblock(cfg: ArchConfig, key, specs=None):
    specs = specs or sublayer_specs(cfg)
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
                          "encoder", "lead"])
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
        if cfg.first_dense:
            params["lead"] = jax.vmap(partial(
                _init_superblock, cfg, specs=_LEAD_SPECS))(
                    jax.random.split(kk["lead"], cfg.first_dense))
    if cfg.vlm is not None:
        params["vision_proj"] = dense_init(kk["vision"], cfg.vlm.vision_dim,
                                           cfg.d_model, dtype=cfg.dtype)
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(kk["head"], cfg.d_model, cfg.vocab,
                                       use_bias=False, dtype=cfg.dtype)
    return params


# --------------------------------------------------------------- forward ---


def _apply_ffn(cfg: ArchConfig, spec, p, x, *, dropless: bool = False,
               valid=None, layer=None):
    """Post-mixer FFN sublayer.  Returns (x, aux).

    dropless=True (prefill, decode) runs MoE through the dropless serving
    layer over the held experts (`moe_serve`; `valid` (B, T) marks the
    rows that route; `layer`, with expert weights stacked over layers,
    which of them to use) and aux holds its expert counts; training keeps the
    capacity dispatch (tokens over capacity fall through the residual,
    standard GShard/Switch behaviour) and aux its losses.
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
        m = cfg.moe
        if dropless:
            y, aux = moe_serve(p["moe"], h, m, valid=valid, layer=layer)
        else:
            y, aux = moe_apply(p["moe"], h, top_k=m.top_k,
                               capacity_factor=m.capacity_factor,
                               scoring=m.scoring, norm_topk=m.norm_topk,
                               routed_scale=m.routed_scale)
        if "dense_res" in p:
            y = y + swiglu_ffn_apply(p["dense_res"], h)
        if "shared" in p:
            y = y + swiglu_ffn_apply(p["shared"], h)
    return x + y, aux


def _apply_sublayer(cfg: ArchConfig, spec, p, x, *, window: int,
                    dropless: bool = False, kv_valid_len=None, layer=None):
    """Full-sequence (train/prefill) sublayer.  Returns (x, kv_or_state, aux)."""
    h = _norm_apply(cfg, p["norm"], x)
    state = None
    valid = None
    if kv_valid_len is not None:
        valid = jnp.arange(x.shape[1])[None, :] < kv_valid_len[:, None]
    if spec["kind"] == "attn" and cfg.mla is not None:
        y, latent = mla_apply(p["attn"], h, n_heads=cfg.n_heads,
                              spec=cfg.mla, rope_theta=cfg.rope_theta,
                              window=window, kv_valid_len=kv_valid_len)
        state = latent[:, :, None]                     # (B, T, 1, Dl)
    elif spec["kind"] == "attn":
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
    x, aux = _apply_ffn(cfg, spec, p, x, dropless=dropless, valid=valid,
                        layer=layer)
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
    """Scan over the stacked leading layers, then superblocks.  Returns
    (x, aux, caches or None); the stacks' caches join along the
    superblock axis."""
    n_moe = _moe_layer_count(cfg)
    aux, caches = _zero_aux(), []
    for specs, stack, _ in _stacks(cfg, params):
        experts = [None] * len(specs)
        if dropless:
            stack, experts = _hoist_experts(specs, stack)

        # NOTE: sb_params is a tuple of per-sublayer dicts (the scan strips
        # the stacked leading axis); iterate positionally.
        def body(carry, sb_params, specs=specs, experts=experts):
            h, aux_acc, l = carry
            states = []
            for spec, p, ex in zip(specs, sb_params, experts):
                h, st, aux = _apply_sublayer(
                    cfg, spec, _with_experts(p, ex), h, window=window,
                    dropless=dropless, kv_valid_len=kv_valid_len,
                    layer=None if ex is None else l)
                if not dropless:
                    aux_acc = _acc_aux(aux_acc, aux, n_moe)
                states.append(st)
            out = _stack_states(cfg, specs, states) if collect_cache else None
            return (h, aux_acc, l + 1), out

        if remat:
            body = jax.checkpoint(body)
        (x, aux, _), c = jax.lax.scan(body, (x, aux, jnp.int32(0)), stack)
        caches.append(c)
    if len(caches) > 1 and collect_cache:
        caches = [jax.tree.map(lambda *a: jnp.concatenate(a), *caches)]
    return x, aux, caches[-1]


def _stack_states(cfg, specs, states):
    """Group per-sublayer prefill states by kind for the decode cache."""
    out = {}
    attn_states = [s for spec, s in zip(specs, states) if spec["kind"] == "attn"]
    if attn_states and cfg.mla is not None:
        out["latent"] = jnp.stack(attn_states)   # (n_attn, B, T, 1, Dl)
    elif attn_states:
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


def _readout(cfg: ArchConfig, params, x):
    """Float32 logits of hidden states x (..., d): x in the weights' dtype
    against them, accumulated in float32 (a bfloat16 head is never
    widened in memory)."""
    w = _readout_weight(cfg, params)
    return jnp.matmul(x.astype(w.dtype), w, preferred_element_type=jnp.float32)


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
    if cfg.first_dense:
        assert n_attn == 1 and cfg.superblock == 1, cfg.name
        n_sb += cfg.first_dense        # the leading layers come first
    if n_attn and cfg.mla is not None:
        out["latent"] = jnp.zeros((n_sb, n_attn, batch, S, 1,
                                   cfg.mla.latent_dim), cfg.dtype)
    elif n_attn:
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


# cache leaves with a token axis, (..., B, S, H, D): what the slot pool
# pages, the prefix cache shares, and prefill ring-compacts
_TOKEN_LEAVES = ("k", "v", "latent")

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
                     cache_len, live, layer=None):
    """One-token sublayer of superblock sb (traced) against the whole
    carried decode cache, which it updates in place.  counters track the
    per-kind index within the superblock.  Returns (x, cache, MoE counts
    [held experts hit, routings landed here])."""
    h = _norm_apply(cfg, p["norm"], x)
    kind = spec["kind"]
    i = counters[kind]
    counters[kind] += 1
    cache = dict(cache)
    if kind == "attn" and cfg.mla is not None:
        y, cache["latent"] = mla_decode_apply(
            p["attn"], h, cache["latent"], cache_len, n_heads=cfg.n_heads,
            spec=cfg.mla, rope_theta=cfg.rope_theta, layer=(sb, i),
            live=live)
    elif kind == "attn":
        y, cache["k"], cache["v"] = attention_decode_apply(
            p["attn"], h, cache["k"], cache["v"], cache_len,
            n_heads=cfg.n_heads, n_kv_heads=cfg.n_kv_heads,
            head_dim=cfg.resolved_head_dim, rope_theta=cfg.rope_theta,
            layer=(sb, i), live=live)
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
    x, aux = _apply_ffn(cfg, spec, p, x, dropless=True,
                        valid=None if live is None else live[:, None],
                        layer=layer)
    counts = (jnp.stack([aux["experts_hit"], aux["routed_local"]])
              if spec["ffn"] == "moe" else jnp.zeros((2,), jnp.int32))
    return x, cache, counts


def decode_step(cfg: ArchConfig, params, tokens, cache, cache_len,
                live=None):
    """One decoding step.  tokens: (B, 1) int32; cache from init_cache/prefill.
    Returns (logits (B, vocab), new_cache); see `decode_step_counted`."""
    logits, cache, _ = decode_step_counted(cfg, params, tokens, cache,
                                           cache_len, live)
    return logits, cache


def decode_step_counted(cfg: ArchConfig, params, tokens, cache, cache_len,
                        live=None):
    """One decoding step that also counts its expert work.

    cache_len may be a scalar (all rows at one depth, the classic path) or
    a (B,) vector (slot-pool decode: every row tracks its own context
    depth; attention writes/attends per row).  live: optional (B,) bool,
    the rows that decode a request: the others route no token to an
    expert and attend and write nothing (their logits are computed and
    ignored).  Returns (logits (B, vocab), new_cache, counts (2,) int32:
    held experts hit and routings that landed on them, summed over the
    MoE layers).

    The layer loop carries the whole cache, indexed by superblock: each
    attention layer writes its new K/V (or latent row) with one update at
    [layer, row, cache_len % S] and attends that layer of the stacked
    pool (the TPU kernel reads its pages straight from it), recurrent
    layers update their state slice.  Under a donated cache, as in the
    scheduler's decode chunk, the step therefore moves one token of K/V
    per layer, not the pool.  The encoder-decoder branch keeps its
    per-layer scan.
    """
    if jnp.ndim(cache_len) > 0:
        assert cfg.rope_theta > 0, \
            "per-row cache_len needs RoPE positions (absolute sinusoidal " \
            "offsets are scalar-only)"
    x = embedding_apply(params["embed"], tokens)
    if cfg.rope_theta == 0:
        x = x + sinusoidal_positions(1, cfg.d_model, offset=cache_len).astype(x.dtype)
    counts = jnp.zeros((2,), jnp.int32)

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
        new_cache = cache
        for specs, stack, sb0 in _stacks(cfg, params):
            stack, experts = _hoist_experts(specs, stack)

            def body(carry, sb_params, specs=specs, experts=experts, sb0=sb0):
                h, cache, sb, n = carry
                counters = dict.fromkeys(_STATE_LEAVES, 0) | {"attn": 0}
                for spec, p, ex in zip(specs, sb_params, experts):
                    h, cache, c = _decode_sublayer(
                        cfg, spec, _with_experts(p, ex), h, cache, sb,
                        counters, cache_len, live,
                        layer=None if ex is None else sb - sb0)
                    n = n + c
                return (h, cache, sb + 1, n), None

            (x, new_cache, _, counts), _ = jax.lax.scan(
                body, (x, new_cache, jnp.int32(sb0), counts), stack)

    x = _norm_apply(cfg, params["final_norm"], x)
    return _readout(cfg, params, x[:, 0]), new_cache, counts


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
        caches = {k: _ring_compact(v, S, total_T) if k in _TOKEN_LEAVES
                  else v for k, v in caches.items()}
    x = _norm_apply(cfg, params["final_norm"], x)
    last = x[:, -1] if lengths is None else x[jnp.arange(B), lengths - 1]
    return _readout(cfg, params, last), caches, total_T


def prefill_chunk(cfg: ArchConfig, params, tokens, cache, depth, *,
                  attend_width: int, last_index=0, lengths=None):
    """Advance a chunked prefill by one token segment.

    tokens: (B, C) the next C prompt tokens (pad-extended past the prompt
    tail); cache: attention decode cache from `init_cache` whose rows
    [0, depth) already hold the previous segments' keys; depth: () tokens
    already prefilled (traced — one compiled program serves every segment
    of a bucket).  Attention runs the segment's queries against the first
    `attend_width` cache slots via `flash_attention(q_offset=depth)`, so
    a row at absolute position depth+i sees exactly the keys a one-shot
    prefill of the same padded width would show it; the segmented sums
    run in another order, so logits and K/V agree with one-shot prefill
    within float32 reassociation (tested against a written tolerance).
    Stale keys past depth+C are causally masked (slot index == absolute
    position for a non-ring prefill).
    lengths: optional (B,) prompt lengths; positions at or past them are
    pads, which route nothing to an expert.

    Returns (logits (B, vocab) at segment row `last_index`, new cache).
    Chunked prefill is attention-only, like bucketed prefill: recurrent
    state would integrate pad tokens, and SWA rings compact slots away
    from the slot==position layout this relies on.
    """
    assert (cfg.encdec is None and cfg.hybrid is None and cfg.xlstm is None
            and cfg.vlm is None and cfg.rope_theta > 0
            and cfg.sliding_window == 0), \
        f"{cfg.name}: chunked prefill needs a pure-attention RoPE decoder"
    B, C = tokens.shape
    leaf = "latent" if cfg.mla is not None else "k"
    assert attend_width <= cache[leaf].shape[3], (attend_width,
                                                  cache[leaf].shape)
    x = embedding_apply(params["embed"], tokens)
    depth = jnp.asarray(depth, jnp.int32)
    positions = depth + jnp.arange(C)[None, :]
    valid = None if lengths is None else positions < lengths[:, None]
    hd = cfg.resolved_head_dim

    def put(cache_sb, name, i, new):
        """Write the segment's rows of layer i at depth; returns them
        with the rows before it."""
        c = jax.lax.dynamic_update_slice_in_dim(
            cache_sb[name][i], new.astype(cache_sb[name].dtype), depth,
            axis=1)
        cache_sb[name] = cache_sb[name].at[i].set(c)
        return c

    def body(carry, xs, specs, experts):
        h, l = carry
        sb_params, cache_sb = xs
        cache_sb = dict(cache_sb)
        for i, (spec, p, ex) in enumerate(zip(specs, sb_params, experts)):
            p = _with_experts(p, ex)
            hn = _norm_apply(cfg, p["norm"], h)
            if cfg.mla is not None:
                lc = put(cache_sb, "latent", i, mla_latent(
                    p["attn"], hn, positions, cfg.mla,
                    cfg.rope_theta)[:, :, None])
                h = h + mla_chunk_attend(
                    p["attn"], hn, lc[:, :, 0], positions, depth,
                    n_heads=cfg.n_heads, spec=cfg.mla,
                    rope_theta=cfg.rope_theta, attend_width=attend_width)
            else:
                q, k, v = project_qkv(p["attn"], hn, n_heads=cfg.n_heads,
                                      n_kv_heads=cfg.n_kv_heads, head_dim=hd)
                q = apply_rope(q, positions, cfg.rope_theta)
                k = apply_rope(k, positions, cfg.rope_theta)
                kc = put(cache_sb, "k", i, k)
                vc = put(cache_sb, "v", i, v)
                out = flash_attention(
                    q, jax.lax.slice_in_dim(kc, 0, attend_width, axis=1),
                    jax.lax.slice_in_dim(vc, 0, attend_width, axis=1),
                    causal=True, q_offset=depth)
                h = h + dense_apply(p["attn"]["wo"],
                                    out.reshape(B, C, cfg.n_heads * hd))
            h, _ = _apply_ffn(cfg, spec, p, h, dropless=True, valid=valid,
                              layer=None if ex is None else l)
        return (h, l + 1), cache_sb

    def run(x, specs, stack, part):
        stack, experts = _hoist_experts(specs, stack)
        (x, _), part = jax.lax.scan(
            partial(body, specs=specs, experts=experts), (x, jnp.int32(0)),
            (stack, part))
        return x, part

    stacks = _stacks(cfg, params)
    if len(stacks) == 1:
        x, new_cache = run(x, stacks[0][0], stacks[0][1], cache)
    else:       # the leading layers' rows, then the superblocks'
        outs = []
        for specs, stack, sb0 in stacks:
            n = jax.tree.leaves(stack)[0].shape[0]
            part = jax.tree.map(lambda c: c[sb0:sb0 + n], cache)
            x, part = run(x, specs, stack, part)
            outs.append(part)
        new_cache = jax.tree.map(lambda *a: jnp.concatenate(a), *outs)
    x = _norm_apply(cfg, params["final_norm"], x)
    last = x[jnp.arange(B), jnp.asarray(last_index)]
    return _readout(cfg, params, last), new_cache


def _ring_compact(kv, S: int, T: int):
    """(..., B, T, H, D) -> ring buffer (..., B, S, H, D) holding the last S
    tokens at slots (pos % S)."""
    tail = jax.lax.slice_in_dim(kv, max(0, T - S), T, axis=-3)
    if T <= S:
        pad = [(0, 0)] * kv.ndim
        pad[-3] = (0, S - T)
        return jnp.pad(tail, pad)
    return jnp.roll(tail, T % S, axis=-3)
