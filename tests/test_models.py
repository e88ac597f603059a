"""Per-architecture smoke tests (REQUIRED: reduced same-family config, one
forward/train step on CPU, output shapes + no NaNs) and the
decode-with-cache == full-forward consistency property."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, SHAPES, shape_applicable
from repro.models import decode_step, forward, init_cache, init_params, prefill
from repro.training.optimizer import OptimizerConfig
from repro.training.steps import init_train_state, make_train_step

ALL_ARCHS = sorted(ARCHS)


def _smoke_batch(cfg, B=2, S=16, key=jax.random.PRNGKey(7)):
    batch = {"tokens": jax.random.randint(key, (B, S), 0, cfg.vocab_size)}
    if cfg.is_encoder_decoder:
        batch["encoder_embeds"] = jax.random.normal(key, (B, S // 2, cfg.d_model))
    return batch


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_smoke_forward(arch):
    cfg = ARCHS[arch].reduced()
    params = init_params(cfg, jax.random.PRNGKey(0))
    B, S = 2, 16
    logits, aux = forward(cfg, params, _smoke_batch(cfg, B, S))
    assert logits.shape == (B, S, cfg.vocab_size)
    assert not bool(jnp.any(jnp.isnan(logits))), f"{arch}: NaNs in logits"
    assert jnp.isfinite(aux)


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_smoke_train_step(arch):
    cfg = ARCHS[arch].reduced()
    opt = OptimizerConfig(lr=1e-3, warmup_steps=1, total_steps=10)
    state = init_train_state(cfg, opt, jax.random.PRNGKey(0))
    step = make_train_step(cfg, opt)
    batch = _smoke_batch(cfg)
    state, metrics = step(state, batch)
    assert jnp.isfinite(metrics["loss"]), arch
    assert float(metrics["grad_norm"]) > 0
    for leaf in jax.tree_util.tree_leaves(state["params"]):
        assert not bool(jnp.any(jnp.isnan(leaf))), arch


@pytest.mark.parametrize(
    "arch",
    ["qwen3-8b", "qwen2.5-3b", "mamba2-780m", "jamba-1.5-large-398b",
     "olmoe-1b-7b", "whisper-small", "qwen2-vl-72b",
     "granite-4.0-h-small-ep4-rank0"],
)
def test_decode_matches_forward(arch):
    """prefill + token-by-token decode reproduces the full-sequence logits."""
    cfg = ARCHS[arch].reduced()
    if cfg.num_experts:
        cfg = dataclasses.replace(cfg, moe_capacity_factor=8.0)  # drop-free
    params = init_params(cfg, jax.random.PRNGKey(1))
    B, S, Sp = 2, 24, 16
    toks = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0, cfg.vocab_size)
    batch = {"tokens": toks}
    if cfg.is_encoder_decoder:
        batch["encoder_embeds"] = jax.random.normal(jax.random.PRNGKey(3),
                                                    (B, 12, cfg.d_model))
    full, _ = forward(cfg, params, batch)
    cache = init_cache(cfg, B, S + 4,
                       enc_len=12 if cfg.is_encoder_decoder else 0)
    pb = dict(batch)
    pb["tokens"] = toks[:, :Sp]
    lg, cache, _ = prefill(cfg, params, pb, cache)
    np.testing.assert_allclose(np.asarray(lg), np.asarray(full[:, Sp - 1]),
                               atol=2e-3, rtol=1e-3)
    for t in range(Sp, S):
        lg, cache = decode_step(cfg, params, toks[:, t:t + 1],
                                jnp.full((B,), t, jnp.int32), cache)
        np.testing.assert_allclose(np.asarray(lg), np.asarray(full[:, t]),
                                   atol=2e-3, rtol=1e-3)


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_param_count_analytic_matches_actual(arch):
    """The analytic counter (used for roofline MODEL_FLOPS and the daemon's
    memory accounting) must track the real pytree at full scale ratios."""
    cfg = ARCHS[arch].reduced()
    params = init_params(cfg, jax.random.PRNGKey(0))
    actual = sum(x.size for x in jax.tree_util.tree_leaves(params))
    predicted = cfg.param_count()
    assert abs(actual - predicted) / actual < 0.05, (arch, actual, predicted)


def test_long_500k_applicability_rules():
    runs = {a for a in ALL_ARCHS
            if shape_applicable(ARCHS[a], SHAPES["long_500k"])[0]}
    assert runs == {"mamba2-780m", "jamba-1.5-large-398b", "granite-4.0-h-small",
                    "granite-4.0-h-small-ep4-rank0"}


def test_arch_configs_exact():
    """The registry holds the exact assigned numbers."""
    c = ARCHS["qwen2-vl-72b"]
    assert (c.num_layers, c.d_model, c.num_heads, c.num_kv_heads,
            c.d_ff, c.vocab_size) == (80, 8192, 64, 8, 29568, 152064)
    c = ARCHS["mamba2-780m"]
    assert (c.num_layers, c.d_model, c.d_ff, c.vocab_size, c.ssm_state) == \
        (48, 1536, 0, 50280, 128)
    c = ARCHS["olmoe-1b-7b"]
    assert (c.num_experts, c.experts_per_token, c.d_ff) == (64, 8, 1024)
    c = ARCHS["llama4-maverick-400b-a17b"]
    assert (c.num_experts, c.experts_per_token, c.vocab_size) == (128, 1, 202048)
    c = ARCHS["jamba-1.5-large-398b"]
    assert (c.attn_every, c.num_experts, c.experts_per_token) == (8, 16, 2)
    assert c.num_attn_layers == 9 and c.num_mamba_layers == 63
    c = ARCHS["qwen3-32b"]
    assert (c.num_layers, c.d_model, c.head_dim, c.qk_norm) == (64, 5120, 128, True)
    c = ARCHS["qwen2.5-3b"]
    assert (c.num_kv_heads, c.qkv_bias, c.d_ff) == (2, True, 11008)
    c = ARCHS["qwen3-8b"]
    assert (c.num_layers, c.d_model, c.num_heads, c.num_kv_heads) == (36, 4096, 32, 8)
    c = ARCHS["phi4-mini-3.8b"]
    assert (c.num_layers, c.d_model, c.vocab_size) == (32, 3072, 200064)
    c = ARCHS["granite-4.0-h-small"]
    assert (c.num_layers, c.d_model, c.num_experts, c.experts_per_token,
            c.moe_d_ff, c.moe_shared_d_ff, c.vocab_size) == \
        (40, 4096, 72, 10, 768, 1536, 100352)
    assert c.num_attn_layers == 4 and c.num_mamba_layers == 36
    c = ARCHS["granite-4.0-h-small-ep4-rank0"]
    assert (c.num_layers, c.num_experts, c.moe_held, c.expert_offset) == \
        (10, 72, 18, 0)
    assert [s.mixer for s in c.period_spec()].index("attn") == 5
    c = ARCHS["whisper-small"]
    assert (c.encoder_layers, c.num_layers, c.d_model, c.vocab_size) == \
        (12, 12, 768, 51865)


def test_moe_capacity_drops_are_bounded():
    """With capacity factor 1.0 and uniform routing, most tokens survive."""
    from repro.models.layers import init_moe, moe_forward

    cfg = ARCHS["olmoe-1b-7b"].reduced()
    cfg = dataclasses.replace(cfg, moe_capacity_factor=1.0)
    p = init_moe(cfg, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 64, cfg.d_model))
    y, aux = moe_forward(cfg, p, x)
    assert y.shape == x.shape
    # aux loss near 1.0 indicates balanced routing (Switch normalization)
    assert 0.5 < float(aux) < 4.0
