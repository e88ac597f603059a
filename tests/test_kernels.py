"""Per-kernel validation: shape/dtype sweeps, interpret=True vs pure-jnp
oracle (assert_allclose), per the assignment."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.ssd_scan import ssd_scan
from repro.models.layers import decode_attention_ref, flash_attention_ref
from repro.models.mamba2 import ssd_chunked_ref

KEY = jax.random.PRNGKey(42)


def _tol(dtype):
    return 2e-2 if dtype == jnp.bfloat16 else 3e-5


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,Sq,Sk,Hq,Hkv,Dh,causal,bq,bk",
    [
        (2, 128, 128, 4, 2, 64, True, 64, 64),
        (1, 256, 256, 8, 2, 32, True, 128, 64),
        (2, 96, 96, 4, 4, 64, True, 64, 64),      # padding path
        (1, 128, 128, 4, 1, 128, False, 64, 128),  # MQA, non-causal
        (1, 64, 192, 2, 2, 64, False, 64, 64),     # cross-attention shape
        (1, 512, 512, 8, 8, 64, True, 256, 256),   # MHA larger blocks
    ],
)
def test_flash_attention_sweep(dtype, B, Sq, Sk, Hq, Hkv, Dh, causal, bq, bk):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, Sq, Hq, Dh), dtype)
    k = jax.random.normal(ks[1], (B, Sk, Hkv, Dh), dtype)
    v = jax.random.normal(ks[2], (B, Sk, Hkv, Dh), dtype)
    out = flash_attention(q, k, v, causal=causal, block_q=bq, block_k=bk,
                          interpret=True)
    ref = flash_attention_ref(q, k, v, causal=causal, block_q=bq, block_k=bk)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=_tol(dtype), rtol=_tol(dtype),
    )


def test_flash_ref_matches_plain_softmax():
    """The oracle itself vs unfused softmax attention."""
    B, S, Hq, Hkv, Dh = 2, 96, 4, 2, 32
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, Hq, Dh))
    k = jax.random.normal(ks[1], (B, S, Hkv, Dh))
    v = jax.random.normal(ks[2], (B, S, Hkv, Dh))
    G = Hq // Hkv
    qg = q.reshape(B, S, Hkv, G, Dh)
    s = jnp.einsum("bqhgd,bkhd->bqhgk", qg, k) / jnp.sqrt(Dh)
    mask = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    s = jnp.where(mask[None, :, None, None, :], s, -1e30)
    plain = jnp.einsum("bqhgk,bkhd->bqhgd", jax.nn.softmax(s, -1), v)
    ref = flash_attention_ref(q, k, v, causal=True, block_q=32, block_k=32)
    np.testing.assert_allclose(
        np.asarray(ref), np.asarray(plain.reshape(B, S, Hq, Dh)), atol=2e-5
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,L,Hq,Hkv,Dh,bk",
    [
        (2, 256, 4, 2, 64, 64),
        (3, 300, 8, 8, 32, 128),   # padding + MHA
        (1, 1024, 16, 2, 128, 256),
        (4, 128, 8, 1, 64, 128),   # MQA
    ],
)
def test_decode_attention_sweep(dtype, B, L, Hq, Hkv, Dh, bk):
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (B, 1, Hq, Dh), dtype)
    kc = jax.random.normal(ks[1], (B, L, Hkv, Dh), dtype)
    vc = jax.random.normal(ks[2], (B, L, Hkv, Dh), dtype)
    lens = jax.random.randint(ks[3], (B,), 1, L + 1)
    out = decode_attention(q, kc, vc, lens, block_k=bk, interpret=True)
    ref = decode_attention_ref(q, kc, vc, lens, block_k=bk)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=_tol(dtype), rtol=_tol(dtype),
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,S,H,P,N,chunk",
    [
        (2, 64, 4, 16, 16, 16),
        (1, 128, 2, 32, 64, 32),
        (2, 100, 3, 16, 32, 32),   # padding path
        (1, 256, 8, 64, 128, 64),  # production-ish dims
    ],
)
def test_ssd_scan_sweep(dtype, B, S, H, P, N, chunk):
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (B, S, H, P), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H))).astype(jnp.float32)
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, S, N), dtype)
    Cm = jax.random.normal(ks[4], (B, S, N), dtype)
    y, fs = ssd_scan(x, dt, A, Bm, Cm, chunk=chunk, interpret=True)
    yr, fsr = ssd_chunked_ref(x, dt, A, Bm, Cm, chunk=chunk)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-3
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(yr, np.float32), atol=tol, rtol=tol)
    np.testing.assert_allclose(np.asarray(fs), np.asarray(fsr), atol=tol, rtol=tol)


def test_ssd_chunk_invariance():
    """Chunk size must not change the result (duality correctness)."""
    B, S, H, P, N = 1, 96, 2, 16, 32
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, S, N))
    Cm = jax.random.normal(ks[4], (B, S, N))
    outs = [ssd_chunked_ref(x, dt, A, Bm, Cm, chunk=c)[0] for c in (16, 32, 96)]
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(o), atol=2e-4)


def test_ssd_step_equals_scan():
    """Recurrent decode step == one-token chunked scan continuation."""
    from repro.models.mamba2 import ssd_step_ref

    B, S, H, P, N = 2, 32, 2, 8, 16
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (B, S + 1, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S + 1, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, S + 1, N))
    Cm = jax.random.normal(ks[4], (B, S + 1, N))
    y_full, _ = ssd_chunked_ref(x, dt, A, Bm, Cm, chunk=16)
    _, state = ssd_chunked_ref(x[:, :S], dt[:, :S], A, Bm[:, :S], Cm[:, :S], chunk=16)
    y_step, _ = ssd_step_ref(state, x[:, S], dt[:, S], A, Bm[:, S], Cm[:, S])
    np.testing.assert_allclose(np.asarray(y_step), np.asarray(y_full[:, S]),
                               atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "sizes,M,K,N,tile",
    [
        ((128, 256, 128), 512, 64, 96, 128),   # every row in a group
        ((128, 0, 64), 384, 64, 96, 64),       # an empty group, rows left over
        ((32, 32, 0, 96), 256, 128, 256, 32),  # the dropless layer's small groups
    ],
)
def test_grouped_matmul_sweep(dtype, sizes, M, K, N, tile):
    """The megablox kernel in interpret mode against ``lax.ragged_dot``
    over the rows its groups cover; rows past them are undefined."""
    from repro.kernels.ops import grouped_matmul

    ks = jax.random.split(KEY, 2)
    lhs = jax.random.normal(ks[0], (M, K), dtype)
    rhs = jax.random.normal(ks[1], (len(sizes), K, N), dtype)
    gs = jnp.array(sizes, jnp.int32)
    got, want = (grouped_matmul(lhs, rhs, gs, out_dtype=jnp.float32,
                                tile_rows=tile, use_pallas=p)
                 for p in ("interpret", False))
    n = sum(sizes)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(got)[:n], np.asarray(want)[:n],
                               atol=tol * K ** 0.5, rtol=tol)
