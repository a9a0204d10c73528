"""The check that decides `correct` in the `moonlight-16b-a3b.chat-long`
cell, driven through a whole run at a small size on the CPU (the
harness's look for a chip is skipped): a sound run passes; the control
(the latent cache and the held experts' matmuls in float8_e4m3, below the
configuration's bfloat16) and two faults fail: a decode step that never
writes its latent row, and a held-expert offset shifted by one.

At this size the program computes in float32 and reads about 0, so each
limit sits far under the control's reading."""
import dataclasses

import run

SEED = 2**33 + 11
TINY = {"num_hidden_layers": 3, "hidden_size": 64, "intermediate_size": 128,
        "num_attention_heads": 4, "num_key_value_heads": 4,
        "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
        "v_head_dim": 16, "moe_intermediate_size": 32,
        "n_routed_experts": 4, "num_experts_per_tok": 4, "vocab_size": 4096,
        "param_dtype": "float32",
        "expert_parallel": {"published_n_routed_experts": 16,
                            "held_first": 4},
        "serving": {"max_len": 96, "scheduler": {
            "buckets": [16, 32, 64], "max_slots": 4, "page_size": 8,
            "prefill_segment": 16, "prefix_hot_pages": 64}},
        "correctness": {"sample_tokens": 256,
                        "reference_precision": "highest",
                        "limit_first_logit_nmse_q25": 1e-5,
                        "limit_mean_gap": 2e-5}}
MIX = {"rate_per_s": 8.0, "tail_s": 1,
       "prompt": {"median": 24, "min": 8, "max": 64},
       "output": {"median": 12, "min": 10, "max": 16}}


def _run(tamper=None, lower=None):
    return run.run_cell("moonlight-16b-a3b.chat-long", SEED, 1.5, False,
                        require_chip=False, config_override=TINY,
                        mix_override=MIX, tamper=tamper, lower=lower,
                        log=lambda *a: None)


def _value(res, key):
    return res["checks"][key]["value"]


def _unwritten_latent(system):
    """A decode step that never writes its latent row: the decode chunk
    hands back the cache it was given."""
    import jax
    s = system.fe.sched
    impl = s._chunk_impl

    def bad(params, pool, active, key, n):
        new, key = impl(params, pool, active, key, n)
        return dict(new, cache=pool["cache"]), key

    s._chunk = jax.jit(bad)


def _shifted_experts(system):
    """The program told that its experts start one index later than the
    ones whose weights it holds."""
    from repro.serve.frontend import StreamingFrontend
    cfg = system.cfg
    system.cfg = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, held_first=cfg.moe.held_first + 1))
    system.fe = StreamingFrontend(system.cfg, system.params,
                                  sched=system.sched_cfg,
                                  max_len=system.max_len,
                                  telemetry=system.tel)


def test_sound_run_passes_and_the_float8_control_fails():
    res = _run(lower="float8_e4m3fn")
    assert res["correct"]
    assert set(res["checks"]) == {"first_logit_nmse_q25", "mean_gap",
                                  "short_outputs"}
    assert res["attempted"] >= 10 and res["failed"] == 0
    assert _value(res, "first_logit_nmse_q25") < 1e-9
    assert _value(res, "mean_gap") < 1e-5
    assert _value(res, "short_outputs") == 0
    ctrl = res["control"]
    assert ctrl["precision"] == "float8_e4m3fn" and not ctrl["correct"]
    for key in ("first_logit_nmse_q25", "mean_gap"):
        assert ctrl["checks"][key]["value"] > 3 * ctrl["checks"][key]["limit"]


def test_unwritten_latent_row_fails():
    bad = _run(tamper=_unwritten_latent)
    assert not bad["correct"]
    assert _value(bad, "mean_gap") > 10 * bad["checks"]["mean_gap"]["limit"]


def test_shifted_expert_offset_fails():
    bad = _run(tamper=_shifted_experts)
    assert not bad["correct"]
    assert _value(bad, "first_logit_nmse_q25") > \
        10 * bad["checks"]["first_logit_nmse_q25"]["limit"]
