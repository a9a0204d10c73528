"""Mixture-of-Experts layers: top-k routing, grouped capacity dispatch for
training and a dropless expert-parallel layer for serving.

GShard-style grouped dispatch: tokens are reshaped into (G, S) groups of
S <= group_size tokens; routing, position-in-expert cumsums and the
one-hot dispatch/combine tensors are all per-group, so the dispatch
tensor is (G, S, E, C) with C = capacity_factor * S * top_k / E — memory
O(N * S * k * cf) instead of the O(N^2 * k / E) a flat formulation costs
at prefill scale.

Expert-parallel friendly: expert weights carry a leading (n_experts,)
axis sharded over the "model" mesh axis; with tokens (groups) sharded
over "data", the dispatch/combine einsums lower to all-to-all style
collectives under GSPMD.

Capacity semantics: tokens over a group's per-expert capacity are dropped
(they fall through the residual connection) — standard Switch/GShard
training behaviour.  Inference paths pass a large capacity_factor
(n_experts / top_k => provably dropless) via the backbone's `dropless`
flag when expert count is small, or 4.0 for very wide expert counts.

`moe_serve` is the serving layer (prefill and decode): dropless, and told
which experts this device holds (`MoESpec.n_held` from
`MoESpec.held_first`).  It routes every token over all `n_experts`,
computes only the held experts' part of the result, and lays the rows
routed to them out by expert for one grouped SwiGLU
(`repro.kernels.expert_ffn`) that reads only the experts some row hit.
Under expert parallelism each device adds its part to the others' by an
all-to-all exchange; on one device the part is the layer's output.
Rows marked invalid (prefill pads, idle decode slots) route nothing.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from jax.sharding import PartitionSpec as P

from repro.kernels.expert_ffn.ops import held_experts_ffn, held_experts_ffn_decode
from repro.nn import init as initializers
from repro.nn.activations import silu
from repro.nn.linear import dense_init
from repro.nn.module import split_keys


def moe_init(key, d_model: int, d_ff: int, n_experts: int, *, dtype=jnp.float32,
             n_held: int = 0, select_bias: bool = False):
    """Router over `n_experts`; weights of `n_held` experts (0: all), the
    held share of an expert-parallel layer.  With `select_bias`, the
    router's per-expert selection bias (zero at init, as trained models
    start it)."""
    kk = split_keys(key, ["router", "gate", "up", "down"])
    held = n_held or n_experts
    def ek(k, a, b):
        # per-expert stacked weights: (E, a, b)
        return initializers.lecun_normal(k, (held, a, b), dtype, fan_in=a)
    router = dense_init(kk["router"], d_model, n_experts, use_bias=False,
                        dtype=dtype)
    if select_bias:
        router["select_bias"] = jnp.zeros((n_experts,), jnp.float32)
    return {
        "router": router,
        "gate": ek(kk["gate"], d_model, d_ff),
        "up": ek(kk["up"], d_model, d_ff),
        "down": ek(kk["down"], d_ff, d_model),
    }


def route(router, x, *, top_k: int, scoring: str = "softmax",
          norm_topk: bool = True, routed_scale: float = 1.0):
    """Top-k routing in float32.  x: (..., d).  Returns (weights (..., k),
    expert ids (..., k) int32, scores (..., E)).  Softmax or sigmoid
    scores; with a `select_bias` in the router (DeepSeek-V3's noaux_tc)
    the k experts are chosen on score + bias and weighted by the score
    alone; chosen weights are renormalized (`norm_topk`) and scaled."""
    logits = x.astype(jnp.float32) @ router["w"].astype(jnp.float32)
    if scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
    if "select_bias" in router:
        _, idx = jax.lax.top_k(scores + router["select_bias"], top_k)
        vals = jnp.take_along_axis(scores, idx, axis=-1)
    else:
        vals, idx = jax.lax.top_k(scores, top_k)
    if norm_topk:
        vals = vals / jnp.maximum(jnp.sum(vals, axis=-1, keepdims=True), 1e-9)
    if routed_scale != 1.0:
        vals = vals * routed_scale
    return vals, idx, scores


def _expert_ffn(params, x):
    """x: (E, C', d) -> (E, C', d) with per-expert SwiGLU weights."""
    g = silu(jnp.einsum("ecd,edf->ecf", x, params["gate"]))
    u = jnp.einsum("ecd,edf->ecf", x, params["up"])
    return jnp.einsum("ecf,efd->ecd", g * u, params["down"])


def _pick_group_size(N: int, target: int) -> int:
    """Largest power-of-two group size <= target that divides N (falls back
    to N itself for odd token counts)."""
    s = 1
    while s * 2 <= target and N % (s * 2) == 0:
        s *= 2
    return s if N % s == 0 else N


def moe_apply(params, x, *, top_k: int, capacity_factor: float = 1.25,
              min_capacity: int = 4, group_size: int = 4096,
              scoring: str = "softmax", norm_topk: bool = True,
              routed_scale: float = 1.0):
    """x: (B, T, d).  Returns (y, aux) where aux has the load-balance loss.
    Training dispatch: the params hold every expert of the router."""
    B, T, d = x.shape
    E = params["router"]["w"].shape[1]
    assert params["gate"].shape[0] == E, "capacity dispatch holds all experts"
    N = B * T
    S = _pick_group_size(N, group_size)
    G = N // S
    xt = x.reshape(G, S, d)

    gate_vals, expert_idx, scores = route(
        params["router"], xt, top_k=top_k, scoring=scoring,
        norm_topk=norm_topk, routed_scale=routed_scale)          # (G, S, K)
    probs = scores if scoring == "softmax" else \
        scores / jnp.sum(scores, axis=-1, keepdims=True)         # (G, S, E)

    capacity = max(min_capacity, int(capacity_factor * S * top_k / E))
    capacity = min(capacity, S)

    # one-hot over experts per routing slot: (K, G, S, E)
    onehot = jax.nn.one_hot(
        jnp.moveaxis(expert_idx, -1, 0), E, dtype=jnp.float32)
    # position of each token within its expert (per group), counting slot-
    # major: slot 0 tokens first, then slot 1, etc.
    oh_km = onehot.transpose(1, 0, 2, 3).reshape(G, top_k * S, E)
    pos = jnp.cumsum(oh_km, axis=1) - oh_km                      # (G, K*S, E)
    pos = jnp.sum(pos * oh_km, axis=-1).reshape(G, top_k, S)     # (G, K, S)
    pos = pos.transpose(1, 0, 2)                                 # (K, G, S)
    keep = pos < capacity

    gates_k = jnp.moveaxis(gate_vals, -1, 0) * keep              # (K, G, S)
    pos_oh = jax.nn.one_hot(pos, capacity, dtype=jnp.float32)    # (K, G, S, C)
    dispatch = jnp.einsum("kgse,kgsc->gsec", onehot * keep[..., None], pos_oh)
    combine = jnp.einsum("kgse,kgsc,kgs->gsec", onehot, pos_oh, gates_k)

    xin = jnp.einsum("gsd,gsec->gecd", xt, dispatch)             # (G, E, C, d)
    # the expert axis placement (the all-to-all boundary) propagates from
    # the expert weight shardings; no explicit constraint so the strategy
    # (1D model-parallel vs 2D resident) stays a pure partition-rule choice
    xe = xin.transpose(1, 0, 2, 3).reshape(E, G * capacity, d).astype(x.dtype)
    yout = _expert_ffn({k: params[k] for k in ("gate", "up", "down")}, xe)
    yout = yout.reshape(E, G, capacity, d).transpose(1, 0, 2, 3)  # (G, E, C, d)
    y = jnp.einsum("gecd,gsec->gsd", yout.astype(jnp.float32), combine)

    # Switch-style load-balance loss (over all tokens)
    density = jnp.mean(onehot[0].reshape(-1, E), axis=0)
    mean_probs = jnp.mean(probs.reshape(-1, E), axis=0)
    lb_loss = E * jnp.sum(density * mean_probs)

    aux = {"load_balance_loss": lb_loss,
           "dropped_fraction": 1.0 - jnp.mean(keep.astype(jnp.float32))}
    return y.reshape(B, T, d).astype(x.dtype), aux


def held_layout(idx, local, n_held: int, tm: int):
    """Where each routing to a held expert lands in the expert-sorted row
    layout of the grouped SwiGLU.  idx: (R,) expert ids relative to the
    first held expert; local: (R,) routings that land here.  Each held
    expert's rows are consecutive, in routing order, and padded to whole
    tiles of tm rows.  Returns (dest (R,) row per routing, the row count
    M for non-local ones; tile_group (M // tm,) expert of each tile; live
    tiles (); rows per expert, padded (n_held,); routings per expert
    (n_held,)), M = the worst case, every routing here."""
    R = idx.shape[0]
    M = -(-(R + n_held * (tm - 1)) // tm) * tm
    onehot = (local[:, None] & (idx[:, None] == jnp.arange(n_held)[None, :])
              ).astype(jnp.int32)                            # (R, n_held)
    counts = jnp.sum(onehot, axis=0)
    rank = jnp.sum((jnp.cumsum(onehot, axis=0) - onehot) * onehot, axis=1)
    padded = (counts + tm - 1) // tm * tm
    ends = jnp.cumsum(padded)
    starts = ends - padded
    dest = jnp.where(local, starts[jnp.clip(idx, 0, n_held - 1)] + rank, M)
    tiles = jnp.arange(M // tm, dtype=jnp.int32) * tm
    tile_group = jnp.minimum(
        jnp.sum(ends[None, :] <= tiles[:, None], axis=1, dtype=jnp.int32),
        n_held - 1)
    return dest, tile_group, ends[-1] // tm, padded, counts


def moe_serve(params, x, spec, *, valid=None, layer=None,
              interpret: bool = False):
    """Dropless serving layer over this device's held experts.

    x: (B, T, d); spec: the `MoESpec`; valid: optional (B, T) bool, rows
    that route (False: pads, idle slots); layer: with it, the expert
    weights are stacks (L, E, ...) of every layer's and the kernel reads
    this layer's in place.  Routes over all
    `spec.n_experts`, keeps the routings that land on the held experts
    [held_first, held_first + held) and returns their weighted sum
    (float32 accumulation, in x.dtype) with
    {"experts_hit": held experts with at least one routing,
     "routed_local": routings that landed here} (int32 scalars).
    A step of one position per row (decode) runs the grouped SwiGLU
    under the decode step's kernel name."""
    B, T, d = x.shape
    N, K, H = B * T, spec.top_k, spec.held
    xt = x.reshape(N, d)
    w, idx, _ = route(params["router"], xt, top_k=K, scoring=spec.scoring,
                      norm_topk=spec.norm_topk, routed_scale=spec.routed_scale)
    rel = idx - spec.held_first
    local = (rel >= 0) & (rel < H)
    if valid is not None:
        local = local & valid.reshape(N, 1)
    tm = 16 if N * K <= 1024 else 128
    dest, tile_group, n_tiles, padded, counts = held_layout(
        rel.reshape(-1), local.reshape(-1), H, tm)
    M = tile_group.shape[0] * tm
    tok = jnp.arange(N * K) // K
    wdt = params["gate"].dtype
    rows = jnp.zeros((M, d), wdt).at[dest].set(xt[tok].astype(wdt),
                                               mode="drop")
    ws = [params[k] for k in ("gate", "up", "down")]
    if layer is None:
        ws, layer = [w[None] for w in ws], 0
    ffn = held_experts_ffn_decode if T == 1 else held_experts_ffn
    out = ffn(rows, *ws, tile_group, n_tiles, padded,
              jnp.asarray(layer, jnp.int32), tm=tm, interpret=interpret)
    got = jnp.where(local.reshape(-1, 1),
                    out[jnp.minimum(dest, M - 1)], 0.0)       # (N*K, d)
    y = jnp.sum(got.reshape(N, K, d) * w[..., None], axis=1)
    stats = {"experts_hit": jnp.sum(counts > 0, dtype=jnp.int32),
             "routed_local": jnp.sum(counts, dtype=jnp.int32)}
    return y.reshape(B, T, d).astype(x.dtype), stats


def _in_mesh_context() -> bool:
    """True when called under a concrete mesh context (dry-run/launcher)."""
    try:
        from jax._src.mesh import thread_resources
        return not thread_resources.env.physical_mesh.empty
    except Exception:
        return False


def moe_reference(params, x, *, top_k: int):
    """Oracle: loop over experts, no capacity limit (tests use small E)."""
    B, T, d = x.shape
    E = params["router"]["w"].shape[1]
    xt = x.reshape(-1, d)
    logits = xt.astype(jnp.float32) @ params["router"]["w"].astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, top_k)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)
    y = jnp.zeros_like(xt, dtype=jnp.float32)
    for e in range(E):
        pe = {"gate": params["gate"][e], "up": params["up"][e], "down": params["down"][e]}
        fe = (jnp.einsum("nf,fd->nd", silu(xt @ pe["gate"]) * (xt @ pe["up"]), pe["down"]))
        w = jnp.sum(jnp.where(expert_idx == e, gate_vals, 0.0), axis=-1)
        y = y + w[:, None] * fe.astype(jnp.float32)
    return y.reshape(B, T, d).astype(x.dtype)
