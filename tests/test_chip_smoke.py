"""chip_smoke.py's body at reduced width on the CPU, and the device
binding it relies on: host buffers in the database, loads committed to the
node's device, capacity read from the device, no silent kernel fallback,
and where the compile cache goes."""
import importlib.util
from pathlib import Path
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from repro.core.daemon import MODELED_CAPACITY, Tier, capacity_of
from repro.core.functions import make_model_function, make_request
from repro.core.runtime import ClusterRuntime, SageRuntime

ROOT = Path(__file__).resolve().parents[1]


def _chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_body_at_reduced_width():
    """Cold + 3 warm + 8 replayed invocations, no record errors, warm
    shared hits, logits equal to a plain jit of the forward."""
    lines = []
    out = _chip_smoke().serve(1, full_width=False, log=lines.append)
    assert out["records"] == 12
    assert out["max_abs_diff"] <= 1e-3
    assert any("shared_hits=11" in s for s in lines), lines


def test_database_holds_host_buffers_and_loads_commit_to_the_device():
    rt = SageRuntime("sage", time_scale=0.0)
    rt.sage_init()
    fn = make_model_function(rt.db, "f", arch="qwen2.5-3b")
    rt.register_function(fn)
    req = make_request(rt.db, fn, seed=1)
    for d in req.in_data:
        leaves = jax.tree_util.tree_leaves(rt.db.fetch(d.key))
        assert leaves and all(isinstance(x, np.ndarray) for x in leaves)
    rec = None
    try:
        rt.sage_run(req)
        rec = rt.telemetry.snapshot()[-1]
        entry = next(e for e in rt.daemon.function_entries("f")
                     if e.read_only)
        assert entry.tier is Tier.DEVICE
        for x in jax.tree_util.tree_leaves(entry.dev_obj):
            assert isinstance(x, jax.Array) and x.committed
            assert x.devices() == {rt.device}
    finally:
        rt.shutdown()
    assert rec is not None and rec.error is None


def test_capacity_comes_from_the_device():
    assert capacity_of(jax.devices()[0]) == MODELED_CAPACITY
    tpu = SimpleNamespace(platform="tpu",
                          memory_stats=lambda: {"bytes_limit": 123})
    assert capacity_of(tpu) == 123
    blind = SimpleNamespace(platform="tpu", memory_stats=lambda: None)
    with pytest.raises(RuntimeError, match="bytes_limit"):
        capacity_of(blind)


def test_cluster_nodes_take_devices_in_turn():
    cluster = ClusterRuntime(n_nodes=3, time_scale=0.0)
    try:
        devs = jax.devices()
        assert [n.device for n in cluster.nodes] == \
            [devs[i % len(devs)] for i in range(3)]
    finally:
        cluster.shutdown()


def test_kernels_never_fall_back_silently():
    from repro.kernels import ops

    x = np.zeros((1, 16, 2, 8), np.float32)
    if jax.default_backend() != "tpu":
        with pytest.raises(RuntimeError, match="interpret"):
            ops.flash_attention(x, x, x, use_pallas=True)
    s = np.zeros((1, 16, 2), np.float32)
    a = np.zeros((2,), np.float32)
    b = np.zeros((1, 16, 8), np.float32)
    with pytest.raises(ValueError, match="initial_state"):
        ops.ssd_scan(x, s, a, b, b, chunk=16, use_pallas="interpret",
                     initial_state=np.zeros((1, 2, 8, 8), np.float32))


def test_compile_cache_goes_where_it_is_placed(monkeypatch):
    """A placed ``JAX_COMPILATION_CACHE_DIR`` is left alone; otherwise the
    cache goes to one fixed, gitignored directory in the checkout."""
    from repro.launch.cache import enable_compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/cache")
        assert enable_compile_cache() == "/placed/cache"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        fixed = str(ROOT / ".jax_cache")
        assert enable_compile_cache() == fixed
        assert jax.config.jax_compilation_cache_dir == fixed
        assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
