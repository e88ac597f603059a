"""Optimizer, loss, microbatching, and DP-compressed step equivalence."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS
from repro.launch.mesh import make_mesh
from repro.training.loss import lm_loss
from repro.training.optimizer import OptimizerConfig, adamw_init, adamw_update, lr_at
from repro.training.steps import (
    init_dp_state, init_train_state, make_dp_compressed_step, make_train_step,
)


def test_adamw_descends_quadratic():
    cfg = OptimizerConfig(lr=0.1, weight_decay=0.0, warmup_steps=0,
                          total_steps=1000, min_lr_ratio=1.0, clip_norm=0.0)
    params = {"x": jnp.asarray([5.0, -3.0])}
    opt = adamw_init(cfg, params)
    for _ in range(200):
        grads = {"x": 2 * params["x"]}
        params, opt, _ = adamw_update(cfg, grads, opt, params)
    assert float(jnp.max(jnp.abs(params["x"]))) < 1e-2


def test_lr_schedule_shape():
    cfg = OptimizerConfig(lr=1.0, warmup_steps=10, total_steps=100,
                          min_lr_ratio=0.1)
    lrs = [float(lr_at(cfg, jnp.asarray(s))) for s in (0, 5, 10, 55, 100)]
    assert lrs[0] == 0.0
    assert abs(lrs[1] - 0.5) < 1e-6           # mid-warmup
    assert abs(lrs[2] - 1.0) < 1e-6           # peak
    assert lrs[2] > lrs[3] > lrs[4]           # cosine decay
    assert abs(lrs[4] - 0.1) < 1e-6           # floor


def test_loss_matches_manual_ce():
    cfg = ARCHS["qwen2.5-3b"].reduced()
    from repro.models import forward, init_params

    params = init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0, cfg.vocab_size)
    total, metrics = lm_loss(cfg, params, {"tokens": toks}, z_loss=0.0)
    logits, _ = forward(cfg, params, {"tokens": toks})
    logp = jax.nn.log_softmax(logits[:, :-1], -1)
    manual = -jnp.take_along_axis(logp, toks[:, 1:, None], -1).mean()
    np.testing.assert_allclose(float(metrics["loss"]), float(manual), rtol=1e-5)


def test_loss_mask_zeroes_positions():
    cfg = ARCHS["qwen2.5-3b"].reduced()
    from repro.models import init_params

    params = init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 8), 0, cfg.vocab_size)
    mask_all = jnp.ones((1, 8))
    mask_half = mask_all.at[:, 4:].set(0.0)
    _, m1 = lm_loss(cfg, params, {"tokens": toks, "loss_mask": mask_all})
    _, m2 = lm_loss(cfg, params, {"tokens": toks, "loss_mask": mask_half})
    assert float(m2["tokens"]) < float(m1["tokens"])
    assert np.isfinite(float(m2["loss"]))


def test_microbatching_matches_full_batch():
    """grad accumulation over microbatches == single big batch: loss and
    global grad-norm identical to fp tolerance across two steps. (Raw param
    tensors are NOT compared: Adam's first-step normalization m/sqrt(v)
    amplifies 1e-8 fp-accumulation noise to ~lr on zero-grad directions.)"""
    cfg = ARCHS["qwen2.5-3b"].reduced()
    opt = OptimizerConfig(lr=1e-2, warmup_steps=0, total_steps=10,
                          min_lr_ratio=1.0)
    s1 = init_train_state(cfg, opt, jax.random.PRNGKey(0))
    s2 = jax.tree_util.tree_map(jnp.copy, s1)
    step1 = make_train_step(cfg, opt, microbatches=1)
    step2 = make_train_step(cfg, opt, microbatches=2)
    for i in range(2):
        batch = {"tokens": jax.random.randint(jax.random.PRNGKey(i), (4, 16),
                                              0, cfg.vocab_size)}
        s1, m1 = step1(s1, batch)
        s2, m2 = step2(s2, batch)
        np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                                   rtol=2e-3)
        np.testing.assert_allclose(float(m1["grad_norm"]),
                                   float(m2["grad_norm"]), rtol=1e-3)


def test_dp_compressed_step_tracks_uncompressed():
    """On a 1-device mesh the compressed all-reduce is a no-op collective;
    the int8 quantization error must stay within the quantization bound and
    training must still descend."""
    cfg = ARCHS["qwen2.5-3b"].reduced()
    opt = OptimizerConfig(lr=1e-3, warmup_steps=0, total_steps=20,
                          min_lr_ratio=1.0)
    mesh = make_mesh((1,), ("data",))
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                          cfg.vocab_size)}
    state = init_dp_state(cfg, opt, jax.random.PRNGKey(0))
    step = make_dp_compressed_step(cfg, opt, mesh, compress=True)
    losses = []
    for _ in range(5):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0]
