"""Train-step factories.

``make_train_step`` builds the GSPMD step (FSDP+TP via rules.py; optional
microbatch gradient accumulation via scan, fp32 accumulators).

``make_dp_compressed_step`` builds a shard_map data-parallel step with int8
error-feedback gradient all-reduce (the cross-pod/DCN path optimization) for
replicated-parameter runs — used by the 100M training example and validated
against the uncompressed step in tests.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.training import compression
from repro.training.loss import lm_loss
from repro.training.optimizer import OptimizerConfig, adamw_init, adamw_update

TrainState = Dict[str, Any]


def init_train_state(cfg: ModelConfig, opt_cfg: OptimizerConfig, key) -> TrainState:
    from repro.models import init_params

    params = init_params(cfg, key)
    return {"params": params, "opt": adamw_init(opt_cfg, params)}


def _tree_cast(tree, dt):
    return jax.tree_util.tree_map(lambda x: x.astype(dt), tree)


def _tree_add(a, b):
    return jax.tree_util.tree_map(jnp.add, a, b)


def make_train_step(
    cfg: ModelConfig,
    opt_cfg: OptimizerConfig,
    *,
    microbatches: int = 1,
) -> Callable[[TrainState, Dict[str, jax.Array]], Tuple[TrainState, Dict]]:
    """GSPMD train step: loss -> grads (fp32 accum) -> AdamW."""

    def loss_fn(params, batch):
        return lm_loss(cfg, params, batch)

    def train_step(state: TrainState, batch: Dict[str, jax.Array]):
        params = state["params"]
        if microbatches == 1:
            (_, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, batch)
            grads = _tree_cast(grads, jnp.float32)
        else:
            B = batch["tokens"].shape[0]

            def split(x):
                """Split the batch-sized axis (axis 0 for tokens/masks;
                axis 1 for (3, B, S) M-RoPE position ids) into
                (microbatches, B/m)."""
                ax = 0 if x.shape[0] == B else next(
                    i for i, d in enumerate(x.shape) if d == B
                )
                shape = (x.shape[:ax] + (microbatches, B // microbatches)
                         + x.shape[ax + 1:])
                return jnp.moveaxis(x.reshape(shape), ax, 0)

            mb = jax.tree_util.tree_map(split, batch)

            def mb_step(carry, mbatch):
                gsum, msum = carry
                (_, met), g = jax.value_and_grad(loss_fn, has_aux=True)(params, mbatch)
                gsum = _tree_add(gsum, _tree_cast(g, jnp.float32))
                msum = _tree_add(msum, {k: v for k, v in met.items()})
                return (gsum, msum), None

            g0 = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params
            )
            m0 = {
                "loss": jnp.zeros(()), "z_loss": jnp.zeros(()),
                "aux_loss": jnp.zeros(()), "total_loss": jnp.zeros(()),
                "tokens": jnp.zeros(()),
            }
            (gsum, msum), _ = jax.lax.scan(mb_step, (g0, m0), mb)
            grads = jax.tree_util.tree_map(lambda g: g / microbatches, gsum)
            metrics = {k: v / microbatches for k, v in msum.items()}
            metrics["tokens"] = msum["tokens"]

        new_params, new_opt, opt_metrics = adamw_update(
            opt_cfg, grads, state["opt"], params
        )
        metrics.update(opt_metrics)
        return {"params": new_params, "opt": new_opt}, metrics

    return train_step


# ---------------------------------------------------------------------------
# DP + int8-compressed gradient all-reduce (shard_map, replicated params)
# ---------------------------------------------------------------------------


def init_dp_state(cfg: ModelConfig, opt_cfg: OptimizerConfig, key) -> TrainState:
    state = init_train_state(cfg, opt_cfg, key)
    state["residuals"] = compression.init_residuals(state["params"])
    return state


def make_dp_compressed_step(
    cfg: ModelConfig,
    opt_cfg: OptimizerConfig,
    mesh,
    *,
    compress: bool = True,
):
    """Data-parallel step over every mesh axis: params replicated, batch
    sharded on axis 0, gradients all-reduced in int8 with error feedback."""
    from jax.sharding import PartitionSpec as P

    axes = tuple(mesh.axis_names)

    def step(state, batch):
        def inner(state, batch):
            params = state["params"]
            (_, metrics), grads = jax.value_and_grad(
                lambda p, b: lm_loss(cfg, p, b), has_aux=True
            )(params, batch)
            grads = _tree_cast(grads, jnp.float32)
            if compress:
                grads, new_res = compression.compress_allreduce(
                    grads, state["residuals"], axes
                )
            else:
                grads = jax.tree_util.tree_map(
                    lambda g: jax.lax.pmean(g, axes), grads
                )
                new_res = state["residuals"]
            metrics = jax.tree_util.tree_map(lambda m: jax.lax.pmean(m, axes), metrics)
            new_params, new_opt, om = adamw_update(opt_cfg, grads, state["opt"], params)
            metrics.update(om)
            return {"params": new_params, "opt": new_opt, "residuals": new_res}, metrics

        return jax.shard_map(
            inner,
            mesh=mesh,
            in_specs=(P(), P(axes)),  # params replicated; batch row-sharded
            out_specs=(P(), P()),
            check_vma=False,
        )(state, batch)

    return step
