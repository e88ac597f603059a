"""FLOPs and bytes of one served forward, counted from the config's shapes."""
import json

import pytest

import _paths
import harness


def _cfg(name):
    with open(_paths.BENCH / "configs" / f"{name}.json") as f:
        return json.load(f)


def _family(cfg):
    return harness.load_module(_paths.BENCH / "families" / f"{cfg['family']}.py")


def test_qwen25_3b_forward_at_1x256_matches_the_hand_count():
    cfg = _cfg("qwen2.5-3b")
    c = _family(cfg).counts(cfg, 1, 256)
    # 2 x 3.086e9 parameters x 256 tokens, plus causal attention (0.6%)
    assert c["flops"] == pytest.approx(1.58e12, rel=0.01)
    assert c["weight_bytes"] == pytest.approx(6.17e9, rel=0.01)
    assert c["params"] == pytest.approx(3.086e9, rel=0.001)
    # the float32 logits of all 256 positions are written too
    assert c["output_bytes"] == 4 * 256 * 151936
    assert c["bytes"] == c["weight_bytes"] + c["output_bytes"]


def test_mamba2_780m_counts():
    cfg = _cfg("mamba2-780m")
    c = _family(cfg).counts(cfg, 1, 256)
    assert c["params"] == pytest.approx(7.80e8, rel=0.01)
    assert c["weight_bytes"] == pytest.approx(1.453 * 2**30, rel=0.01)
    assert c["flops"] == pytest.approx(2 * c["params"] * 256, rel=0.1)


@pytest.mark.parametrize("name", ["qwen2.5-3b", "mamba2-780m"])
def test_counts_agree_with_the_layout(name):
    cfg = _cfg(name)
    fam = _family(cfg)
    import numpy as np
    import weights

    n = sum(int(np.prod(s)) * d.itemsize
            for _, s, d in weights.leaves(fam.param_shapes(cfg)))
    assert fam.counts(cfg, 1, 256)["weight_bytes"] == n


def test_peaks_table_is_keyed_by_device_kind_with_its_source():
    with open(_paths.BENCH / "peaks.json") as f:
        peaks = json.load(f)
    assert "TPU v5e" in peaks["source"]
    v5e = peaks["devices"]["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    cfg = _cfg("qwen2.5-3b")
    c = _family(cfg).counts(cfg, 1, 256)
    # near the ridge: compute 8.0 ms against 7.7 ms of HBM traffic
    assert c["flops"] / v5e["bf16_flops_per_s"] == pytest.approx(8.0e-3, rel=0.02)
    assert c["bytes"] / v5e["hbm_bytes_per_s"] == pytest.approx(7.73e-3, rel=0.02)
