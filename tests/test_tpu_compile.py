"""Compile the served path's programs for a described TPU v5e.

Nothing runs: the TPU compiler, installed with JAX, compiles for a chip
that is described and not attached. That catches what interpret mode
cannot — blocks that break the (8, 128) tiling, kernels over the 16 MiB
scoped VMEM limit, a model that does not fit one chip's HBM. The topology
is described inside a fixture, never while this module is imported, so
every pytest-xdist worker collects the same tests; the fixture skips where
no v5e can be described.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.functions import model_config, served_logits
from repro.kernels.decode_attention import decode_attention
from repro.kernels.grouped_matmul import grouped_matmul
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ssd_scan import ssd_scan
from repro.models import init_params

V5E_HBM_BYTES = 16 * 10**9  # one TPU v5e chip (Google Cloud, "TPU v5e")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no compiler logs
    # a described chip cannot read back what the persistent cache holds
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe skips
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _shape(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_compiles(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def test_flash_attention_compiles_at_qwen2_5_3b_widths(one_chip):
    # Hq 16, Hkv 2, Dh 128, S 2048, default blocks
    _kernel_compiles(flash_attention,
                     _shape(one_chip, (1, 2048, 16, 128)),
                     _shape(one_chip, (1, 2048, 2, 128)),
                     _shape(one_chip, (1, 2048, 2, 128)))


def test_decode_attention_compiles_at_qwen2_5_3b_widths(one_chip):
    # batch 8, cache length 4096
    _kernel_compiles(decode_attention,
                     _shape(one_chip, (8, 1, 16, 128)),
                     _shape(one_chip, (8, 4096, 2, 128)),
                     _shape(one_chip, (8, 4096, 2, 128)),
                     _shape(one_chip, (8,), jnp.int32))


def test_ssd_scan_compiles_at_mamba2_780m_widths(one_chip):
    # H 48 heads of P 64, state N 128, S 1024
    _kernel_compiles(ssd_scan,
                     _shape(one_chip, (1, 1024, 48, 64)),
                     _shape(one_chip, (1, 1024, 48), jnp.float32),
                     _shape(one_chip, (48,), jnp.float32),
                     _shape(one_chip, (1, 1024, 128)),
                     _shape(one_chip, (1, 1024, 128)))


def test_served_qwen2_5_3b_forward_fits_one_v5e(one_chip):
    """The full-width program chip_smoke.py serves: batch 1, 16 tokens."""
    cfg = model_config("qwen2.5-3b", full_width=True)
    params = jax.eval_shape(lambda k: init_params(cfg, k),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    params = jax.tree_util.tree_map(
        lambda x: _shape(one_chip, x.shape, x.dtype), params)
    mem = jax.jit(served_logits(cfg)).lower(
        params, _shape(one_chip, (1, 16), jnp.int32)).compile().memory_analysis()
    weights = sum(x.size * x.dtype.itemsize
                  for x in jax.tree_util.tree_leaves(params))
    assert mem.argument_size_in_bytes >= weights > 5.7 * 2**30
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < V5E_HBM_BYTES, total


@pytest.mark.parametrize("k,n", [(4096, 768), (768, 4096)])
def test_grouped_matmul_compiles_at_granite_4_0_h_small_widths(one_chip, k, n):
    """The held experts' gate/up (d 4096 -> 768) and down products over
    the dropless layer's buffer: 18 held experts, 256 tokens x top-10."""
    from functools import partial

    from repro.models.layers import EXPERT_ROW_TILE

    m = 256 * 10 + 18 * EXPERT_ROW_TILE
    _kernel_compiles(partial(grouped_matmul, out_dtype=jnp.bfloat16,
                             tile_rows=EXPERT_ROW_TILE),
                     _shape(one_chip, (m, k)), _shape(one_chip, (18, k, n)),
                     _shape(one_chip, (18,), jnp.int32))


def test_served_granite_rank0_forward_fits_one_v5e(one_chip, monkeypatch):
    """The one-chip share the benchmark serves, 1 x 256 tokens, with the
    Pallas grouped products the chip takes (the CPU backend here would
    take ``ragged_dot``)."""
    import repro.kernels.ops as ops

    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    cfg = model_config("granite-4.0-h-small-ep4-rank0", full_width=True)
    params = jax.eval_shape(lambda k: init_params(cfg, k),
                            jax.ShapeDtypeStruct((2,), jnp.uint32))
    params = jax.tree_util.tree_map(
        lambda x: _shape(one_chip, x.shape, x.dtype), params)
    compiled = jax.jit(served_logits(cfg)).lower(
        params, _shape(one_chip, (1, 256), jnp.int32)).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 3 * cfg.num_layers
    mem = compiled.memory_analysis()
    weights = sum(x.size * x.dtype.itemsize
                  for x in jax.tree_util.tree_leaves(params))
    assert mem.argument_size_in_bytes >= weights > 6.5e9
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < V5E_HBM_BYTES, total
