"""HLO analyzer: exact dot-FLOP counting with scan (while) multipliers, and
collective byte attribution — validated against hand-computed programs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.hlo_analysis import (
    analyze_compiled, analyze_hlo_text, xla_cost_analysis,
)
from repro.analysis.roofline import model_flops, roofline_from_report
from repro.configs import ARCHS


def test_single_matmul_flops():
    f = lambda a, b: a @ b
    c = jax.jit(f).lower(
        jax.ShapeDtypeStruct((64, 128), jnp.float32),
        jax.ShapeDtypeStruct((128, 32), jnp.float32),
    ).compile()
    rep = analyze_hlo_text(c.as_text())
    assert rep.dot_flops == 2 * 64 * 128 * 32


def test_scan_multiplies_flops():
    def f(w, x):
        def body(h, wi):
            return h @ wi, None
        h, _ = jax.lax.scan(body, x, w)
        return h

    n = 7
    c = jax.jit(f).lower(
        jax.ShapeDtypeStruct((n, 32, 32), jnp.float32),
        jax.ShapeDtypeStruct((32, 32), jnp.float32),
    ).compile()
    rep = analyze_hlo_text(c.as_text())
    assert rep.dot_flops == n * 2 * 32 * 32 * 32
    assert n in rep.while_trips
    # XLA's own count misses the trip multiplier — that's why we parse
    xla = xla_cost_analysis(c).get("flops", 0)
    assert xla < rep.dot_flops


def test_nested_scan_multiplies_twice():
    def f(w, x):
        def outer(h, wi):
            def inner(h2, _):
                return h2 @ wi, None
            h2, _ = jax.lax.scan(inner, h, None, length=3)
            return h2, None
        h, _ = jax.lax.scan(outer, x, w)
        return h

    c = jax.jit(f).lower(
        jax.ShapeDtypeStruct((5, 16, 16), jnp.float32),
        jax.ShapeDtypeStruct((16, 16), jnp.float32),
    ).compile()
    rep = analyze_hlo_text(c.as_text())
    assert rep.dot_flops == 5 * 3 * 2 * 16 * 16 * 16


def test_roofline_terms_and_dominance():
    cfg = ARCHS["qwen3-8b"]
    report = {
        "flops": 1e12, "dot_flops": 1e12, "hbm_bytes": 1e12,
        "collective_bytes": 1e10, "collective_traffic_bytes": 1e10,
    }
    r = roofline_from_report(cfg, report, chips=256, mode="train",
                             tokens=1_000_000, device_kind="TPU v5 lite")
    assert r["dominant"] == "memory_s"  # 1e12/819e9 > 1e12/197e12
    np.testing.assert_allclose(r["compute_s"], 1e12 / 197e12)
    np.testing.assert_allclose(r["memory_s"], 1e12 / 819e9)
    np.testing.assert_allclose(r["collective_s"], 1e10 / 50e9)
    assert 0 < r["roofline_fraction"] <= 1.5


def test_roofline_refuses_an_unknown_device_kind():
    report = {"flops": 1.0, "dot_flops": 1.0, "hbm_bytes": 1.0,
              "collective_bytes": 0.0, "collective_traffic_bytes": 0.0}
    with pytest.raises(KeyError, match="cpu"):
        roofline_from_report(ARCHS["qwen3-8b"], report, chips=1,
                             mode="train", tokens=1, device_kind="cpu")


def test_model_flops_moe_uses_active_params():
    dense = ARCHS["qwen3-32b"]
    moe = ARCHS["llama4-maverick-400b-a17b"]
    assert moe.active_param_count() < 0.1 * moe.param_count()
    f_dense = model_flops(dense, "train", 1000)
    assert f_dense == 6.0 * dense.param_count() * 1000
    f_moe = model_flops(moe, "decode", 10)
    assert f_moe == 2.0 * moe.active_param_count() * 10


def test_param_counts_sane():
    """Analytic totals should land near the marketing numbers."""
    assert 6.5e10 < ARCHS["qwen2-vl-72b"].param_count() < 8.2e10
    assert 6.0e8 < ARCHS["mamba2-780m"].param_count() < 9.5e8
    assert 5.5e9 < ARCHS["olmoe-1b-7b"].param_count() < 8.0e9
    assert 3.3e11 < ARCHS["llama4-maverick-400b-a17b"].param_count() < 4.7e11
    assert 3.2e11 < ARCHS["jamba-1.5-large-398b"].param_count() < 4.6e11
    assert 2.7e10 < ARCHS["qwen3-32b"].param_count() < 3.7e10
    assert 2.4e9 < ARCHS["qwen2.5-3b"].param_count() < 3.6e9
    assert 6.5e9 < ARCHS["qwen3-8b"].param_count() < 9.0e9
    assert 3.2e9 < ARCHS["phi4-mini-3.8b"].param_count() < 4.6e9
    assert 1.8e8 < ARCHS["whisper-small"].param_count() < 3.5e8
    # MoE actives
    assert 0.9e9 < ARCHS["olmoe-1b-7b"].active_param_count() < 1.6e9
    assert 1.2e10 < ARCHS["llama4-maverick-400b-a17b"].active_param_count() < 2.4e10


def test_collective_bytes_all_gather():
    from jax.sharding import NamedSharding, PartitionSpec as P

    if jax.device_count() < 2:
        pytest.skip("needs >1 device (dry-run covers multi-device)")
