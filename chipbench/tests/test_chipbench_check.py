"""The check that decides `correct`, driven through a whole run at a
small size on the CPU (the harness's look for a chip is skipped): sound
runs pass, the control and each fault a cell can have fail.

The limits here are set for this size, as the configurations' are for
theirs: on the CPU the program computes in float32 and reads about 0,
so each limit sits well under the bfloat16 control's reading (at this
size and seed: first_logit_nmse about 2e-4, mean_gap about 3e-4)."""
import jax
import jax.numpy as jnp
import pytest

import run

SEED = 2**33 + 7
TINY_LM = {"model": {"n_layers": 2, "d_model": 64, "n_heads": 4,
                     "n_kv_heads": 2, "head_dim": 16, "d_ff": 128,
                     "vocab": 151936},
           "serving": {"max_len": 96, "scheduler": {
               "buckets": [16, 32, 64], "max_slots": 4, "page_size": 8,
               "prefill_segment": 16, "prefix_hot_pages": 64}},
           "correctness": {"sample_tokens": 256,
                           "reference_precision": "highest",
                           "limit_first_logit_nmse": 1e-5,
                           "limit_mean_gap": 2e-5}}
LM_MIX = {"rate_per_s": 8.0, "tail_s": 1,
          "prompt": {"median": 24, "min": 8, "max": 64},
          "output": {"median": 12, "min": 10, "max": 16}}


def _lm(tamper=None, lower=None):
    return run.run_cell("qwen2-0.5b.prefill-mix", SEED, 1.5, False,
                        require_chip=False, config_override=TINY_LM,
                        mix_override=LM_MIX, tamper=tamper, lower=lower,
                        log=lambda *a: None)


def _value(res, key):
    return res["checks"][key]["value"]


def _alter_tokens(system):
    """A token altered where it is produced: every decode chunk's token
    buffer comes back shifted by one."""
    s = system.fe.sched
    inner, vocab = s._chunk, system.model["vocab"]

    def bad(params, pool, active, key, n):
        pool, key = inner(params, pool, active, key, n)
        return dict(pool, buf=(pool["buf"] + 1) % vocab), key

    bad._cache_size = inner._cache_size
    s._chunk = bad


def _stale_cache(system):
    """A step that returns its state unchanged: the decode chunk hands
    back the KV cache it was given."""
    s = system.fe.sched
    impl = s._chunk_impl

    def bad(params, pool, active, key, n):
        new, key = impl(params, pool, active, key, n)
        return dict(new, cache=pool["cache"]), key

    s._chunk = jax.jit(bad)


def _alter_first_logits(system):
    """A first token altered where it is produced: the one-shot and the
    chunked prefill hand back their logits rolled by one vocabulary
    entry."""
    s = system.fe.sched
    for name in ("_prefill", "_prefill_chunk"):
        inner = getattr(s, name)

        def bad(*a, _inner=inner, **k):
            lg, *rest = _inner(*a, **k)
            return (jnp.roll(lg, 1, axis=-1), *rest)

        bad._cache_size = inner._cache_size
        setattr(s, name, bad)


def test_lm_sound_run_passes_and_the_control_fails():
    res = _lm(lower="bfloat16")
    assert res["correct"]
    assert _value(res, "first_logit_nmse") < 1e-9
    assert _value(res, "mean_gap") < 1e-5
    assert res["checks"]["short_outputs"]["value"] == 0
    ctrl = res["control"]
    assert ctrl["precision"] == "bfloat16" and not ctrl["correct"]
    assert ctrl["checks"]["first_logit_nmse"]["value"] > \
        3 * ctrl["checks"]["first_logit_nmse"]["limit"]


@pytest.mark.parametrize("fault,key", [(_alter_tokens, "mean_gap"),
                                       (_stale_cache, "mean_gap"),
                                       (_alter_first_logits,
                                        "first_logit_nmse")])
def test_lm_faults_fail(fault, key):
    bad = _lm(tamper=fault)
    assert not bad["correct"]
    assert _value(bad, key) > 10 * bad["checks"][key]["limit"]
