"""Kernel micro-benchmarks: wall time of the pure-jnp reference path on
the default device (the Pallas path targets TPU; interpret mode is a
correctness tool, not a performance path), plus an HLO-derived roofline
bound per kernel when that device is a TPU with known peaks.

The batch-axis sweep measures what same-function invocation batching
(docs/compute.md) buys at the kernel level: n concurrent invocations of
one function stack along the leading batch axis into a single launch, so
the per-invocation cost is t(n)/n and the marginal cost of each extra
member is (t(n) - t(1)) / ((n-1) * t(1)) — the measured counterpart of
the compute plane's ``batch_marginal`` model knob."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from benchmarks.common import Row
from repro.analysis.hlo_analysis import analyze_hlo_text
from repro.analysis.roofline import peaks_for

BATCH_SWEEP = (1, 2, 4, 8)


def _time(fn, *args, iters=3):
    fn(*args)  # compile
    t0 = time.perf_counter()
    for _ in range(iters):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / iters


def _roofline_note(rep) -> str:
    """The HLO's roofline bound on this chip; nothing off a TPU, where the
    HLO and the timings are another backend's."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        return ""
    p = peaks_for(dev.device_kind)
    est = max(rep.dot_flops / p.flops, rep.hbm_bytes / p.hbm_bw)
    return f" tpu_roofline_est={est * 1e6:.1f}us"


def batch_sweep(quick: bool = True):
    """Per-invocation amortization of stacking same-function invocations
    along the batch axis, for each of the three kernels. ``amort`` is
    t(n)/(n*t(1)) — perfect sharing is 1/n, no sharing is 1.0;
    ``marginal`` is the per-extra-member cost the compute plane models."""
    from repro.models.layers import decode_attention_ref, flash_attention_ref
    from repro.models.mamba2 import ssd_chunked_ref

    key = jax.random.PRNGKey(1)
    S = 256 if quick else 1024  # smaller seq: the sweep scales the batch
    L = 1024 if quick else 4096

    def flash(n):
        ks = jax.random.split(key, 3)
        q = jax.random.normal(ks[0], (n, S, 8, 64), jnp.float32)
        k = jax.random.normal(ks[1], (n, S, 2, 64), jnp.float32)
        v = jax.random.normal(ks[2], (n, S, 2, 64), jnp.float32)
        f = jax.jit(lambda q, k, v: flash_attention_ref(q, k, v, causal=True))
        return _time(f, q, k, v)

    def ssd(n):
        ks = jax.random.split(key, 5)
        x = jax.random.normal(ks[0], (n, S, 8, 64))
        dt = jax.nn.softplus(jax.random.normal(ks[1], (n, S, 8)))
        A = -jnp.exp(jax.random.normal(ks[2], (8,)) * 0.3)
        Bm = jax.random.normal(ks[3], (n, S, 128))
        Cm = jax.random.normal(ks[4], (n, S, 128))
        g = jax.jit(lambda *a: ssd_chunked_ref(*a, chunk=128)[0])
        return _time(g, x, dt, A, Bm, Cm)

    def decode(n):
        ks = jax.random.split(key, 3)
        q = jax.random.normal(ks[0], (n, 1, 16, 128))
        kc = jax.random.normal(ks[1], (n, L, 2, 128))
        vc = jax.random.normal(ks[2], (n, L, 2, 128))
        lens = jnp.full((n,), L, jnp.int32)
        h = jax.jit(lambda *a: decode_attention_ref(*a))
        return _time(h, q, kc, vc, lens)

    rows = []
    for name, bench in (("flash_attention", flash), ("ssd_scan", ssd),
                        ("decode_attention", decode)):
        t1 = bench(1)
        for n in BATCH_SWEEP:
            t = t1 if n == 1 else bench(n)
            amort = t / (n * t1)
            marginal = ((t - t1) / ((n - 1) * t1)) if n > 1 else 1.0
            rows.append(Row(f"kernel_{name}_batch{n}", t * 1e6 / n,
                            f"amort={amort:.3f} marginal={marginal:.3f}"))
    return rows


def run(quick: bool = True):
    rows = []
    key = jax.random.PRNGKey(0)
    # flash attention reference at a serving-relevant shape
    from repro.models.layers import flash_attention_ref

    B, S, Hq, Hkv, Dh = 1, 2048, 8, 2, 64
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, S, Hq, Dh), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, Hkv, Dh), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, Hkv, Dh), jnp.float32)
    f = jax.jit(lambda q, k, v: flash_attention_ref(q, k, v, causal=True))
    t = _time(f, q, k, v)
    lowered = f.lower(q, k, v).compile()
    rep = analyze_hlo_text(lowered.as_text())
    rows.append(Row("kernel_flash_attention_2k", t * 1e6,
                    f"flops={rep.dot_flops:.2e}{_roofline_note(rep)}"))

    from repro.models.mamba2 import ssd_chunked_ref

    B, S, H, P, N = 1, 2048, 8, 64, 128
    ks = jax.random.split(key, 5)
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, S, N))
    Cm = jax.random.normal(ks[4], (B, S, N))
    g = jax.jit(lambda *a: ssd_chunked_ref(*a, chunk=128)[0])
    t = _time(g, x, dt, A, Bm, Cm)
    rep = analyze_hlo_text(g.lower(x, dt, A, Bm, Cm).compile().as_text())
    rows.append(Row("kernel_ssd_scan_2k", t * 1e6,
                    f"flops={rep.dot_flops:.2e}{_roofline_note(rep)}"))

    from repro.models.layers import decode_attention_ref

    B, L, Hq, Hkv, Dh = 8, 8192, 16, 2, 128
    ks = jax.random.split(key, 4)
    q = jax.random.normal(ks[0], (B, 1, Hq, Dh))
    kc = jax.random.normal(ks[1], (B, L, Hkv, Dh))
    vc = jax.random.normal(ks[2], (B, L, Hkv, Dh))
    lens = jnp.full((B,), L, jnp.int32)
    h = jax.jit(lambda *a: decode_attention_ref(*a))
    t = _time(h, q, kc, vc, lens)
    rep = analyze_hlo_text(h.lower(q, kc, vc, lens).compile().as_text())
    rows.append(Row("kernel_decode_attention_8k", t * 1e6,
                    f"hbm={rep.hbm_bytes:.2e}B{_roofline_note(rep)}"))
    rows.extend(batch_sweep(quick))
    return rows


if __name__ == "__main__":
    import sys

    for r in run(quick="--full" not in sys.argv):
        r.print()
