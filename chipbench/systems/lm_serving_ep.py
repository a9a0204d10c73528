"""Serving cells of a DeepSeek-V3-style model with one chip's share of
each expert layer: the `lm_serving` system, built from a configuration
file that carries the model's published config.json keys.

Beside the dense fields, the file's latent-attention keys (kv_lora_rank
and the qk/v head dims), its MoE keys (experts per token, expert and
shared widths, sigmoid scoring with noaux_tc selection, renormalization,
routed scaling, a leading dense layer) and its `expert_parallel` block
(the published expert count the router spans, and the first of the
`n_routed_experts` experts this chip holds) shape the program's
configuration.  Everything else, the window and the reference's
comparison, is `LMServing`'s; the check adds a first-token number that a
rare routing flip cannot swing (`LMServingEP.check`).
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np

from systems.lm_serving import LMServing

import schedule as sched_gen


def model_of(config: dict) -> dict:
    """The reference's model dict from the configuration file.  Settings
    the program does not implement raise: group-limited routing, a query
    low-rank projection, attention biases, another activation, experts
    in only some layers."""
    c = config
    unsupported = {"n_group": (c["n_group"], 1),
                   "topk_group": (c["topk_group"], 1),
                   "q_lora_rank": (c["q_lora_rank"], None),
                   "attention_bias": (c["attention_bias"], False),
                   "hidden_act": (c["hidden_act"], "silu"),
                   "moe_layer_freq": (c["moe_layer_freq"], 1),
                   "scoring_func": (c["scoring_func"], "sigmoid"),
                   "topk_method": (c["topk_method"], "noaux_tc"),
                   "tie_word_embeddings": (c["tie_word_embeddings"], False)}
    for key, (got, want) in unsupported.items():
        if got != want:
            raise ValueError(f"{key}={got!r}: the program serves {want!r}")
    ep = c["expert_parallel"]
    return {
        "n_layers": c["num_hidden_layers"],
        "first_dense": c["first_k_dense_replace"],
        "d_model": c["hidden_size"],
        "n_heads": c["num_attention_heads"],
        "vocab": c["vocab_size"],
        "d_ff": c["intermediate_size"],
        "kv_lora_rank": c["kv_lora_rank"],
        "qk_nope_head_dim": c["qk_nope_head_dim"],
        "qk_rope_head_dim": c["qk_rope_head_dim"],
        "v_head_dim": c["v_head_dim"],
        "n_experts": ep["published_n_routed_experts"],
        "n_held": c["n_routed_experts"],
        "held_first": ep["held_first"],
        "top_k": c["num_experts_per_tok"],
        "expert_d_ff": c["moe_intermediate_size"],
        "n_shared": c["n_shared_experts"],
        "norm_topk": bool(c["norm_topk_prob"]),
        "routed_scale": c["routed_scaling_factor"],
        "rms_norm_eps": c["rms_norm_eps"],
        "rope_theta": float(c["rope_theta"]),
        "param_dtype": c["param_dtype"],
    }


def arch_of(base, m: dict):
    """The program's configuration: `base` (the registry entry) with the
    model dict's widths, latent attention and held-expert share."""
    from repro.configs.base import MLASpec
    mla = MLASpec(kv_lora_rank=m["kv_lora_rank"],
                  qk_nope_head_dim=m["qk_nope_head_dim"],
                  qk_rope_head_dim=m["qk_rope_head_dim"],
                  v_head_dim=m["v_head_dim"])
    moe = dataclasses.replace(
        base.moe, n_experts=m["n_experts"], top_k=m["top_k"],
        expert_d_ff=m["expert_d_ff"],
        shared_expert_ff=m["n_shared"] * m["expert_d_ff"],
        scoring="sigmoid", select_bias=True, norm_topk=m["norm_topk"],
        routed_scale=m["routed_scale"], n_held=m["n_held"],
        held_first=m["held_first"])
    return dataclasses.replace(
        base, n_layers=m["n_layers"], first_dense=m["first_dense"],
        d_model=m["d_model"], n_heads=m["n_heads"], n_kv_heads=m["n_heads"],
        head_dim=0, d_ff=m["d_ff"], vocab=m["vocab"],
        rope_theta=m["rope_theta"], norm_eps=m["rms_norm_eps"],
        tie_embeddings=False, norm="rmsnorm", param_dtype=m["param_dtype"],
        mla=mla, moe=moe)


class LMServingEP(LMServing):
    """One serving deployment of an expert-parallel latent-attention
    model, on the chip that holds one share of each expert layer."""

    def __init__(self, config: dict, ref, mix: dict, seed: int,
                 seconds: float, tel):
        from repro.configs import get_config
        from repro.serve.frontend import StreamingFrontend
        from repro.serve.scheduler import SchedulerConfig
        self.config, self.ref, self.mix = config, ref, mix
        self.seed, self.seconds, self.tel = seed, seconds, tel
        self.model = m = model_of(config)
        self.cfg = arch_of(get_config(config["program_arch"]), m)
        sv = config["serving"]
        sc = dict(sv["scheduler"])
        sc["buckets"] = tuple(sc["buckets"])
        self.sched_cfg = SchedulerConfig(**sc)
        self.max_len = sv["max_len"]
        self.params = ref.make_params(m, sched_gen.seed32(seed, 0))
        self.fe = StreamingFrontend(self.cfg, self.params,
                                    sched=self.sched_cfg,
                                    max_len=self.max_len, telemetry=tel)
        self.reqs = sched_gen.lm_schedule(mix, seed, seconds, m["vocab"])
        self.records = []
        self.gauges = []
        self.first = None
        self.firsts = {}

    def check(self, *, lower: str | None = None):
        """`LMServing.check`, plus `first_logit_nmse_q25`: the lower
        quartile over the sampled requests of each request's first-token
        NMSE.  With bfloat16 activations a near-tie among the router's
        selection scores flips one of a token's experts now and then,
        which moves that request's logits far more than rounding does:
        a fifth to a third of the requests carry such a flip at their
        last prompt token, and the mean over them swings with where the
        flips fall.  The lower quartile reads the rounding of the
        requests without one; a fault in prefill, or a lower precision,
        moves every request."""
        keep = _KeepResults(self.ref)
        real, self.ref = self.ref, keep
        try:
            ours, theirs = super().check(lower=lower)
        finally:
            self.ref = real
        ours["first_logit_nmse_q25"] = _nmse_q25(keep.res, "")
        if theirs is not None:
            theirs["first_logit_nmse_q25"] = _nmse_q25(keep.res, "control_")
        self.last = (ours, theirs)
        return ours, theirs


class _KeepResults:
    """The reference module, keeping the per-request readings of its
    last comparison."""

    def __init__(self, ref):
        self.ref, self.res = ref, []

    def __getattr__(self, name):
        return getattr(self.ref, name)

    def compare(self, *a, **k):
        self.res = self.ref.compare(*a, **k)
        return self.res


def _nmse_q25(res: list, pre: str) -> float:
    """Lower quartile over the requests of one side (program, or control
    with pre="control_") of the mean squared first-token gap over the
    reference logits' variance; inf with no request to compare."""
    if not res:
        return math.inf
    return float(np.percentile(
        [r[pre + "first"][1] / r["std"][0] ** 2 for r in res], 25))


System = LMServingEP
