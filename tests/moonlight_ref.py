"""CPU-size twin of the benchmark's plain Moonlight-16B-A3B reference.

The reference itself (`chipbench/configs/moonlight-16b-a3b.py`: float32,
no cache, no kernel, the expanded form of latent attention, each held
expert over every token) is loaded from its file, so the program's tests
and the chip's `correct` check hold the program to one reference; here
it runs at a reduced Moonlight size: 1 dense + 2 expert layers, d 256,
4 heads, latent 64 + rope 16, 8 routed experts of which 2 are held.
`program_config` is the serving program's configuration of a model dict.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import os

import jax.numpy as jnp
import numpy as np

_PATH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "chipbench", "configs", "moonlight-16b-a3b.py")
_spec = importlib.util.spec_from_file_location("moonlight_reference", _PATH)
ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ref)

MODEL = {"n_layers": 3, "first_dense": 1, "d_model": 256, "n_heads": 4,
         "vocab": 512, "d_ff": 512, "kv_lora_rank": 64,
         "qk_nope_head_dim": 32, "qk_rope_head_dim": 16, "v_head_dim": 32,
         "n_experts": 8, "n_held": 2, "held_first": 2, "top_k": 3,
         "expert_d_ff": 64, "n_shared": 2, "norm_topk": True,
         "routed_scale": 2.446, "rms_norm_eps": 1e-5, "rope_theta": 50000.0,
         "param_dtype": "float32"}


def program_config(model: dict = MODEL):
    """The moonshot registry entry at the model dict's sizes and share."""
    from repro.configs import MLASpec, get_config
    base = get_config("moonshot-v1-16b-a3b")
    m = model
    return dataclasses.replace(
        base, n_layers=m["n_layers"], first_dense=m["first_dense"],
        d_model=m["d_model"], n_heads=m["n_heads"], n_kv_heads=m["n_heads"],
        d_ff=m["d_ff"], vocab=m["vocab"], rope_theta=m["rope_theta"],
        norm_eps=m["rms_norm_eps"], param_dtype=m["param_dtype"],
        mla=MLASpec(m["kv_lora_rank"], m["qk_nope_head_dim"],
                    m["qk_rope_head_dim"], m["v_head_dim"]),
        moe=dataclasses.replace(
            base.moe, n_experts=m["n_experts"], top_k=m["top_k"],
            expert_d_ff=m["expert_d_ff"],
            shared_expert_ff=m["n_shared"] * m["expert_d_ff"],
            routed_scale=m["routed_scale"], n_held=m["n_held"],
            held_first=m["held_first"]))


def make_params(seed: int = 7, model: dict = MODEL):
    return ref.make_params(model, seed)


def logits(params, tokens, model: dict = MODEL) -> np.ndarray:
    """The reference's float32 logits (T, vocab) at every position of one
    causal sequence, at the highest matmul precision."""
    import jax
    toks = jnp.asarray(np.asarray(tokens, np.int32))
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref._logits(model, params, toks,
                                      jnp.arange(len(toks)), jnp.float32))
