"""Per-architecture smoke tests (deliverable f): a REDUCED variant of each
assigned architecture runs one forward/train step on CPU with correct
output shapes and no NaNs; decode matches prefill logits."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ASSIGNED_ARCHS, get_config
from repro.models import backbone as bb

KEY = jax.random.PRNGKey(7)


def _batch_for(cfg, B, T):
    batch = {"tokens": jax.random.randint(KEY, (B, T), 0, cfg.vocab),
             "labels": jax.random.randint(KEY, (B, T), 0, cfg.vocab)}
    if cfg.vlm is not None:
        batch["patches"] = jax.random.normal(
            KEY, (B, cfg.vlm.n_patches, cfg.vlm.vision_dim))
    if cfg.encdec is not None:
        batch["frames"] = jax.random.normal(
            KEY, (B, cfg.encdec.n_frames, cfg.d_model))
    return batch


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_reduced_forward_and_train_step(arch):
    cfg = get_config(arch).reduced()
    assert cfg.d_model <= 512 and cfg.vocab <= 512
    if cfg.moe:
        assert cfg.moe.n_experts <= 4
    params = bb.init_params(cfg, KEY)
    batch = _batch_for(cfg, B=2, T=16)
    loss, metrics = bb.forward_loss(cfg, params, batch)
    assert loss.shape == ()
    assert bool(jnp.isfinite(loss)), (arch, loss)

    # one SGD step reduces nothing catastrophic (finite grads)
    def loss_fn(p):
        return bb.forward_loss(cfg, p, batch)[0]

    grads = jax.grad(loss_fn)(params)
    gnorm = sum(float(jnp.sum(jnp.abs(g)))
                for g in jax.tree_util.tree_leaves(grads))
    assert np.isfinite(gnorm) and gnorm > 0.0


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_reduced_decode_consistency(arch):
    cfg = get_config(arch).reduced()
    params = bb.init_params(cfg, KEY)
    B, T = 2, 10
    tokens = jax.random.randint(KEY, (B, T + 2), 0, cfg.vocab)
    batch = _batch_for(cfg, B, T)
    del batch["labels"]
    batch["tokens"] = tokens[:, :T]
    logits0, cache, total_T = bb.prefill(cfg, params, batch)
    assert logits0.shape == (B, cfg.vocab)
    cl = total_T
    for step in range(2):
        logits, cache = bb.decode_step(cfg, params,
                                       tokens[:, T + step:T + step + 1],
                                       cache, cl)
        cl += 1
        b2 = dict(batch)
        b2["tokens"] = tokens[:, :T + step + 1]
        ref, _, _ = bb.prefill(cfg, params, b2)
        np.testing.assert_allclose(logits, ref, atol=3e-3, rtol=3e-3)


def test_all_ten_archs_registered():
    from repro.configs import list_configs
    assert set(ASSIGNED_ARCHS) <= set(list_configs())
    assert len(ASSIGNED_ARCHS) == 10


@pytest.mark.parametrize("arch", ASSIGNED_ARCHS)
def test_full_config_matches_assignment(arch):
    spec = {
        "internvl2-1b": (24, 896, 14, 2, 4864, 151655),
        "moonshot-v1-16b-a3b": (27, 2048, 16, 16, 11264, 163840),
        "qwen2-1.5b": (28, 1536, 12, 2, 8960, 151936),
        "xlstm-350m": (24, 1024, 4, 4, 0, 50304),
        "jamba-1.5-large-398b": (72, 8192, 64, 8, 24576, 65536),
        "arctic-480b": (35, 7168, 56, 8, 4864, 32000),
        "qwen2-0.5b": (24, 896, 14, 2, 4864, 151936),
        "llama3.2-1b": (16, 2048, 32, 8, 8192, 128256),
        "whisper-tiny": (4, 384, 6, 6, 1536, 51865),
        "mixtral-8x7b": (32, 4096, 32, 8, 14336, 32000),
    }[arch]
    cfg = get_config(arch)
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
            cfg.d_ff, cfg.vocab) == spec
    moe_spec = {
        "moonshot-v1-16b-a3b": (64, 6),
        "jamba-1.5-large-398b": (16, 2),
        "arctic-480b": (128, 2),
        "mixtral-8x7b": (8, 2),
    }
    if arch in moe_spec:
        assert (cfg.moe.n_experts, cfg.moe.top_k) == moe_spec[arch]
    else:
        assert cfg.moe is None
    if arch == "moonshot-v1-16b-a3b":       # config.json of Moonlight-16B-A3B
        m, e = cfg.mla, cfg.moe
        assert (m.kv_lora_rank, m.qk_nope_head_dim, m.qk_rope_head_dim,
                m.v_head_dim) == (512, 128, 64, 128)
        assert (cfg.first_dense, e.expert_d_ff, e.shared_expert_ff,
                e.scoring, e.select_bias, e.norm_topk, e.routed_scale,
                cfg.norm_eps, cfg.rope_theta, cfg.tie_embeddings) == \
            (1, 1408, 2816, "sigmoid", True, True, 2.446, 1e-5, 50_000.0,
             False)
    else:
        assert cfg.mla is None and cfg.first_dense == 0
