"""Sharding rules: every production-mesh PartitionSpec must divide the
tensor dims it shards, for every arch x mode, on the abstract 16x16 and
2x16x16 meshes (no devices needed)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh
from jax.sharding import PartitionSpec as P

from repro.configs import ARCHS
from repro.distributed import rules
from repro.models import init_params
from repro.serving.engine import cache_shapes

MESHES = {
    "16x16": AbstractMesh((16, 16), ("data", "model")),
    "2x16x16": AbstractMesh((2, 16, 16), ("pod", "data", "model")),
}


def _check_divisibility(mesh, spec_tree, shape_tree, tag):
    def one(path, spec, leaf):
        assert isinstance(spec, P), (tag, path)
        assert len(spec) <= len(leaf.shape), (tag, path, spec, leaf.shape)
        for dim, ax in zip(leaf.shape, spec):
            if ax is None:
                continue
            axes = ax if isinstance(ax, tuple) else (ax,)
            n = int(np.prod([mesh.shape[a] for a in axes]))
            assert dim % n == 0, (tag, rules.path_str(path), leaf.shape, spec)

    jax.tree_util.tree_map_with_path(
        lambda p, s, l: one(p, s, l), spec_tree, shape_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


@pytest.mark.parametrize("mesh_name", list(MESHES))
@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_param_specs_divide(mesh_name, arch):
    mesh = MESHES[mesh_name]
    cfg = ARCHS[arch]
    shapes = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    for mode in ("train", "decode"):
        specs = rules.tree_param_specs(cfg, mesh, shapes, mode=mode)
        _check_divisibility(mesh, specs, shapes, f"{arch}/{mode}")


@pytest.mark.parametrize("arch", ["qwen3-8b", "jamba-1.5-large-398b",
                                  "whisper-small", "mamba2-780m"])
def test_cache_specs_divide(arch):
    mesh = MESHES["16x16"]
    cfg = ARCHS[arch]
    cs = cache_shapes(cfg, 128, 32768, enc_len=16384 if cfg.is_encoder_decoder else 0)
    specs = rules.tree_cache_specs(cfg, mesh, cs)
    _check_divisibility(mesh, specs, cs, f"{arch}/cache")


def test_zero_decode_only_for_giants():
    mesh = MESHES["16x16"]
    assert rules.needs_zero_decode(ARCHS["llama4-maverick-400b-a17b"], mesh)
    assert rules.needs_zero_decode(ARCHS["jamba-1.5-large-398b"], mesh)
    assert not rules.needs_zero_decode(ARCHS["qwen3-8b"], mesh)
    assert not rules.needs_zero_decode(ARCHS["qwen3-32b"], mesh)


def test_kv_replicated_when_heads_indivisible():
    mesh = MESHES["16x16"]
    cfg = ARCHS["qwen3-8b"]  # kv=8 < 16 shards
    spec = rules.param_spec(cfg, mesh, "layers/sub0/mixer/wk",
                            (36, cfg.d_model, 8 * 128), mode="train")
    assert spec[-1] is None  # replicated over model (Megatron GQA fallback)
    cfg2 = ARCHS["olmoe-1b-7b"]  # kv=16 == 16 shards
    spec2 = rules.param_spec(cfg2, mesh, "layers/sub0/mixer/wk",
                             (16, cfg2.d_model, 16 * 128), mode="train")
    assert spec2[-1] == "model"


def test_moe_experts_shard_over_model():
    mesh = MESHES["16x16"]
    cfg = ARCHS["llama4-maverick-400b-a17b"]
    spec = rules.param_spec(cfg, mesh, "layers/sub1/ffn/wg",
                            (24, 128, cfg.d_model, cfg.moe_d_ff), mode="train")
    assert spec[1] == "model"  # expert axis -> EP


def test_batch_specs_handle_batch_one():
    mesh = MESHES["16x16"]
    cfg = ARCHS["mamba2-780m"]

    class L:  # tiny shape carrier
        shape = (1, 1)

    # batch of 1 cannot shard -> replicated
    specs = rules.batch_specs(cfg, mesh, {"tokens": L()}, mode="decode")
    assert specs["tokens"][0] is None
