"""Builders turning model-zoo architectures into serverless GPUFunctions.

The real runtime serves *actual* models: the GPU context is a real
``jax.jit(...).lower(...).compile()`` executable for the node's device,
weights are a host (numpy) pytree in the database that the daemon loads
into HBM, compute is the real forward pass. By default the model is the
arch's reduced preset; ``full_width=True`` serves its published
configuration. Declared sizes (from paper Table 2 profiles, or the
weights' real byte count) drive the brokered transfer times and memory
accounting.
"""
from __future__ import annotations

from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import SingleDeviceSharding

from repro.configs import ARCHS
from repro.configs.base import ModelConfig
from repro.core.engine import GPUFunction
from repro.core.profiles import MB, FunctionProfile
from repro.core.request import Data, DataType, Request
from repro.data.database import Database
from repro.models import forward, init_params


def model_config(arch: str, full_width: bool = False) -> ModelConfig:
    """The served configuration: published widths, or the reduced preset."""
    return ARCHS[arch] if full_width else ARCHS[arch].reduced()


def host_params(cfg: ModelConfig, seed: int = 0) -> Any:
    """Weights for ``cfg`` from ``seed`` as a host (numpy) pytree: built
    on the default device, then copied out of it. Op by op: one jit of the
    whole init unrolls every layer, and at qwen2.5-3b widths took about a
    minute to compile for a v5e."""
    return jax.device_get(init_params(cfg, jax.random.PRNGKey(seed)))


def served_logits(cfg: ModelConfig):
    """What one invocation computes: the logits of a token batch; for a
    model with dropless experts, (logits, its expert counters as int32
    (2,)), the counters of ``forward``."""
    if not cfg.moe_dropless:
        return lambda p, t: forward(cfg, p, {"tokens": t})[0]

    def logits_and_counters(p, t):
        logits, _, counters = forward(cfg, p, {"tokens": t}, counters=True)
        return logits, counters

    return logits_and_counters


def make_model_function(
    db: Database,
    fn_name: str,
    arch: str = "qwen3-8b",
    *,
    batch: int = 1,
    seq: int = 16,
    profile: Optional[FunctionProfile] = None,
    declared_ro_bytes: Optional[int] = None,
    seed: int = 0,
    full_width: bool = False,
    device=None,
    params: Any = None,
) -> GPUFunction:
    """Build an inference GPUFunction serving ``arch`` on ``device`` (the
    default device when None). ``params`` shares one host pytree across
    the nodes of a cluster; None builds it from ``seed``."""
    cfg = model_config(arch, full_width)
    if params is None:
        params = host_params(cfg, seed)
    real_bytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(params))
    ro_bytes = declared_ro_bytes or (
        int(profile.read_only_mb * MB) if profile else real_bytes
    )
    weights_key = f"{fn_name}/weights"
    db.put(weights_key, params, size=ro_bytes)

    sharding = SingleDeviceSharding(device if device is not None
                                    else jax.devices()[0])
    param_shapes = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        params)
    tok_shape = jax.ShapeDtypeStruct((batch, seq), jnp.int32, sharding=sharding)

    def context_builder():
        # the 'GPU context': a real AOT compile (shape-only, no data — the
        # knowability property that makes parallel setup possible)
        return jax.jit(served_logits(cfg)).lower(param_shapes, tok_shape).compile()

    def handler(shim, request: Request):
        w = shim.sage_load_to_gpu(weights_key)
        x = shim.sage_load_to_gpu(request.in_data[1].key)
        out = shim.launch_kernel(shim.gpu_ctx, w, x)
        logits, counters = out if cfg.moe_dropless else (out, None)
        out_key = f"{fn_name}/out/{request.uuid}"
        shim.sage_dump_to_db(out_key, np.asarray(logits[:, -1, :8]))
        if counters is not None:  # fetched after the launch, outside the lock
            shim.expert_rows, shim.expert_rows_max = (
                int(c) for c in np.asarray(counters))
        return out_key

    return GPUFunction(
        name=fn_name,
        handler=handler,
        context_builder=context_builder,
        read_only={weights_key: ro_bytes},
        writable_hint=int(profile.writable_mb * MB) if profile else batch * seq * 4,
        compute_s_hint=(profile.compute_ms / 1e3) if profile else 0.0,
    )


def make_request(
    db: Database,
    fn: GPUFunction,
    *,
    batch: int = 1,
    seq: int = 16,
    input_bytes: int = 4 * MB,
    vocab: int = 256,
    seed: int = 0,
) -> Request:
    """A request whose metadata declares everything loadable (Fig 8)."""
    tokens = np.random.default_rng(seed).integers(0, vocab, (batch, seq), dtype=np.int32)
    req = Request(function_name=fn.name)
    in_key = f"{fn.name}/in/{req.uuid}"
    db.put(in_key, tokens, size=input_bytes)
    ro_key = next(iter(fn.read_only))
    req.in_data = [
        Data(key=ro_key, size=fn.read_only[ro_key], dtype=DataType.READ_ONLY),
        Data(key=in_key, size=input_bytes, dtype=DataType.WRITABLE),
    ]
    return req
