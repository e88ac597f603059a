"""Hypothesis property tests on system invariants."""
import math

import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core.clock import VirtualClock
from repro.core.datapath import BandwidthBroker
from repro.core.exit_policy import ExitLadder
from repro.training.compression import dequantize, quantize_int8

SETTINGS = dict(max_examples=40, deadline=None)


@settings(**SETTINGS)
@given(
    ttls=st.tuples(*[st.floats(0.01, 100.0) for _ in range(4)]),
    t_complete=st.floats(0.0, 1e6),
    dt=st.floats(0.0, 1e7),
)
def test_ladder_stage_monotonic_nondecreasing(ttls, t_complete, dt):
    """Stages only move forward in time; stage is within [1, 5]."""
    lad = ExitLadder(ttls=ttls)
    lad.on_complete(t_complete)
    s1 = lad.stage_at(t_complete + dt / 2)
    s2 = lad.stage_at(t_complete + dt)
    assert 1 <= s1 <= s2 <= 5


@settings(**SETTINGS)
@given(
    ttls=st.tuples(*[st.floats(0.01, 50.0) for _ in range(4)]),
    checks=st.lists(st.floats(0.0, 300.0), min_size=1, max_size=8),
)
def test_ladder_actions_fire_exactly_once_each(ttls, checks):
    fired = []
    lad = ExitLadder(ttls=ttls)
    lad.on_enter = {k: (lambda k=k: fired.append(k)) for k in (2, 3, 4)}
    lad.on_complete(0.0)
    for t in sorted(checks):
        lad.advance(t)
    assert fired == sorted(set(fired))  # in order, no duplicates


@settings(**SETTINGS)
@given(
    sizes=st.lists(st.integers(1, 200) , min_size=1, max_size=10),
    bw=st.floats(10.0, 1e4),
)
def test_broker_conservation_and_fairness(sizes, bw):
    """All virtual transfers complete; total busy time >= total_bytes / bw
    (a shared link can never beat its own bandwidth)."""
    clock = VirtualClock()
    b = BandwidthBroker(bw, clock)
    done = []
    for s in sizes:
        b.sim_transfer(float(s), lambda s=s: done.append((s, clock.now())))
    clock.run_until(1e9)
    assert len(done) == len(sizes)
    t_end = max(t for _, t in done)
    assert t_end >= 0.99 * sum(sizes) / bw  # conservation bound
    # no transfer finished faster than its solo time
    for s, t in done:
        assert t >= 0.99 * s / bw


@settings(**SETTINGS)
@given(
    arr=st.lists(st.floats(-1e3, 1e3, allow_nan=False, width=32),
                 min_size=1, max_size=64),
)
def test_int8_error_feedback_bounded(arr):
    """Quantization error per step is bounded by the scale, and the residual
    carries it exactly (x + r_in = q*scale + r_out)."""
    x = jnp.asarray(arr, jnp.float32)
    r = jnp.zeros_like(x)
    q, scale, r2 = quantize_int8(x, r)
    np.testing.assert_allclose(
        np.asarray(x + r), np.asarray(dequantize(q, scale) + r2), rtol=1e-5,
        atol=1e-5 * float(scale),
    )
    assert float(jnp.max(jnp.abs(r2))) <= float(scale) * 0.5 + 1e-6


@settings(**SETTINGS)
@given(st.data())
def test_int8_error_feedback_converges_on_repeat(data):
    """Feeding the same gradient repeatedly, the accumulated dequantized sum
    tracks the true sum (error feedback prevents bias accumulation)."""
    n = data.draw(st.integers(4, 32))
    g = np.asarray(data.draw(st.lists(
        st.floats(-10, 10, allow_nan=False, width=32), min_size=8, max_size=8)),
        np.float32)
    r = jnp.zeros(8, jnp.float32)
    acc = np.zeros(8, np.float64)
    for _ in range(n):
        q, s, r = quantize_int8(jnp.asarray(g), r)
        acc += np.asarray(dequantize(q, s), np.float64)
    true = g.astype(np.float64) * n
    scale_bound = max(np.abs(g).max() / 127.0, 1e-12)
    np.testing.assert_allclose(acc, true, atol=2 * scale_bound + 1e-6)


@settings(**SETTINGS)
@given(
    B=st.integers(1, 3), S=st.integers(2, 24),
    Hkv=st.sampled_from([1, 2]), G=st.sampled_from([1, 2, 4]),
    Dh=st.sampled_from([8, 16]),
    seed=st.integers(0, 2**31 - 1),
)
def test_attention_rowsum_property(B, S, Hkv, G, Dh, seed):
    """With v = ones, attention output must be exactly ones (softmax rows
    sum to 1) for any causal mask pattern."""
    from repro.models.layers import flash_attention_ref

    key = jax.random.PRNGKey(seed)
    Hq = Hkv * G
    q = jax.random.normal(key, (B, S, Hq, Dh))
    k = jax.random.normal(jax.random.fold_in(key, 1), (B, S, Hkv, Dh))
    v = jnp.ones((B, S, Hkv, Dh))
    out = flash_attention_ref(q, k, v, causal=True, block_q=8, block_k=8)
    np.testing.assert_allclose(np.asarray(out), 1.0, atol=2e-5)


@settings(max_examples=20, deadline=None)
@given(
    steps=st.lists(st.integers(0, 500), min_size=1, max_size=5, unique=True),
    host_split=st.sampled_from([1, 2, 4]),
)
def test_pipeline_deterministic_and_host_sharded(steps, host_split):
    """batch_at is pure in (seed, step); host shards partition the batch."""
    from repro.data.pipeline import DataConfig, TokenPipeline

    cfg = DataConfig(vocab_size=97, global_batch=8, seq_len=16, seed=5)
    p = TokenPipeline(cfg)
    for s in steps:
        b1 = p.batch_at(s)
        b2 = p.batch_at(s)
        np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
        per = cfg.global_batch // host_split
        for h in range(host_split):
            bh = p.batch_at(s, host_id=h, num_hosts=host_split)
            assert bh["tokens"].shape == (per, cfg.seq_len)
