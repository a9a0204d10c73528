"""The plain references agree with the program at a small size, and lay
out their weights in the tree the program takes."""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np

import run

CONFIGS = os.path.join(run.HERE, "configs")
TINY_LM = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
           "head_dim": 16, "d_ff": 128, "vocab": 256, "qkv_bias": True,
           "rope_theta": 1000000.0, "tie_embeddings": True, "norm": "rmsnorm",
           "rms_norm_eps": 1e-6, "param_dtype": "float32"}


def _ref(name):
    return run.load_module(os.path.join(CONFIGS, name + ".py"),
                           "ref_" + name.replace("-", "_").replace(".", "_"))


def _lm_cfg(m):
    from repro.configs import get_config
    keys = ("n_layers", "d_model", "n_heads", "n_kv_heads", "head_dim",
            "d_ff", "vocab", "qkv_bias", "rope_theta", "tie_embeddings",
            "norm", "param_dtype")
    return dataclasses.replace(get_config("qwen2-0.5b"),
                               **{k: m[k] for k in keys})


def test_qwen2_weights_have_the_programs_layout():
    from repro.models import backbone as bb
    ref = _ref("qwen2-0.5b")
    ours = jax.eval_shape(lambda: ref._params(TINY_LM, jax.random.key(0)))
    theirs = jax.eval_shape(lambda: bb.init_params(_lm_cfg(TINY_LM),
                                                   jax.random.PRNGKey(0)))
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs)):
        assert a.shape == b.shape and a.dtype == b.dtype


def test_qwen2_reference_matches_program_prefill():
    from repro.models import backbone as bb
    ref = _ref("qwen2-0.5b")
    params = ref.make_params(TINY_LM, 3)
    toks = np.random.default_rng(0).integers(0, 256, 24).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        got, _, _ = bb.prefill(_lm_cfg(TINY_LM), params,
                               {"tokens": jnp.asarray(toks[None])})
        want = ref._logits(TINY_LM, params, jnp.asarray(toks),
                           jnp.asarray([23]), jnp.float32)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                               rtol=1e-4, atol=1e-5)


def _greedy(params, prompt, n):
    """The reference's own greedy tokens after `prompt`, and the logits
    its first one is taken from."""
    ref = _ref("qwen2-0.5b")
    toks, first = list(prompt), None
    with jax.default_matmul_precision("highest"):
        for _ in range(n):
            lg = np.asarray(ref._logits(
                TINY_LM, params, jnp.asarray(toks, jnp.int32),
                jnp.asarray([len(toks) - 1]), jnp.float32)[0])
            first = lg if first is None else first
            toks.append(int(np.argmax(lg)))
    return np.asarray(toks[len(prompt):]), first


def test_qwen2_reference_reads_its_own_greedy_run_as_exact():
    """The reference's own greedy tokens and first logits read no gap;
    altered tokens read a gap at every position."""
    ref = _ref("qwen2-0.5b")
    params = ref.make_params(TINY_LM, 5)
    prompt = np.random.default_rng(1).integers(0, 256, 10)
    served, first = _greedy(params, prompt, 6)
    (r,) = ref.compare(TINY_LM, 5, [(prompt, served, first)], pad_to=32,
                       precision="highest")
    assert r["gap"].shape == (6,) and np.all(r["gap"] <= 1e-5)
    assert r["first"][0] <= 1e-6 and r["first"][1] <= 1e-12
    (r2,) = ref.compare(TINY_LM, 5, [(prompt, (served + 1) % 256,
                                      first[::-1])],
                        pad_to=32, precision="highest")
    assert np.all(r2["gap"] > 0) and r2["first"][0] > 0.1 * r2["std"][0]


def test_qwen2_bfloat16_control_reads_above_float32():
    """The control, the reference computed in bfloat16, reads first
    logits and token gaps that the float32 reference does not."""
    ref = _ref("qwen2-0.5b")
    params = ref.make_params(TINY_LM, 7)
    prompt = np.random.default_rng(2).integers(0, 256, 20)
    served, first = _greedy(params, prompt, 12)
    (r,) = ref.compare(TINY_LM, 7, [(prompt, served, first)], pad_to=64,
                       precision="highest", lower="bfloat16")
    assert r["control_first"][0] > 1e-3 * r["std"][0]
    assert r["control_first"][0] > 100 * r["first"][0]
    assert r["control_gap"].shape == r["gap"].shape
