"""Pallas TPU flash attention (causal, GQA) — the prefill/train hot spot.

Design (TPU-native, not a CUDA port):
* grid = (batch, kv_heads, n_q_blocks, n_k_blocks); the k dimension is the
  innermost, *sequential* ("arbitrary") axis so the online-softmax state
  (m, l, acc) lives in VMEM scratch across k iterations — the TPU analogue
  of a CUDA persistent-CTA loop;
* GQA is handled by giving each kv-head program its whole q-head *group*
  (block shape (G*block_q, d)) so the MXU contracts (G*bq, d) x (d, bk) —
  groups ride the sublane dimension, no head replication;
* causal blocks above the diagonal are skipped with ``pl.when`` (no MXU
  work issued), giving the exact triangular FLOP count;
* fp32 accumulation; bf16 (or input dtype) output.

Block sizes default to (256, 512). The fp32 scores of one program are
(G*block_q, block_k): 4 MiB at G=8, and the (G*block_q, 1) m/l scratch is
lane-padded to 128, 1 MiB each. At qwen2.5-3b widths (G=8, Dh=128)
(512, 512) asked for 19.26 MiB of scoped VMEM, over a v5e's 16 MiB limit;
(256, 512) compiles (tests/test_tpu_compile.py).

Validated in interpret mode against ``repro.models.layers.flash_attention_
ref`` (itself validated against plain softmax attention) — see
tests/test_kernels.py.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(
    q_ref, k_ref, v_ref,  # inputs
    o_ref,                # output
    m_scr, l_scr, acc_scr,  # VMEM scratch
    *,
    scale: float,
    block_q: int,
    block_k: int,
    causal: bool,
    seq_q: int,
    seq_k: int,
    groups: int,
):
    b, h, qi, ki = (pl.program_id(i) for i in range(4))
    n_k = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # causal: process block only if some q position >= some k position
    run = True
    if causal:
        run = (qi + 1) * block_q - 1 >= ki * block_k

    @pl.when(run)
    def _body():
        q = q_ref[...].reshape(groups * block_q, -1)  # (G*bq, d)
        k = k_ref[0, 0]                               # (bk, d)
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale                                     # (G*bq, bk)
        # mask: causal + kv validity (padding). The q position repeats per
        # GQA group along the fused (G*bq) sublane axis.
        q_pos = (
            qi * block_q
            + jax.lax.broadcasted_iota(jnp.int32, (groups, block_q, block_k), 1)
        ).reshape(groups * block_q, block_k)
        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (groups * block_q, block_k), 1
        )
        mask = k_pos < seq_k
        if causal:
            mask = jnp.logical_and(mask, q_pos >= k_pos)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + p.sum(axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_scr[...] = acc_scr[...] * corr + pv
        m_scr[...] = m_new
        l_scr[...] = l_new

    @pl.when(ki == n_k - 1)
    def _fin():
        l = l_scr[...]
        out = acc_scr[...] / jnp.maximum(l, 1e-30)
        o_ref[...] = out.reshape(o_ref.shape).astype(o_ref.dtype)


def flash_attention(
    q: jax.Array,  # (B, Sq, Hq, Dh)
    k: jax.Array,  # (B, Sk, Hkv, Dh)
    v: jax.Array,
    *,
    causal: bool = True,
    block_q: int = 256,
    block_k: int = 512,
    scale: Optional[float] = None,
    interpret: bool = False,
) -> jax.Array:
    B, Sq, Hq, Dh = q.shape
    _, Sk, Hkv, _ = k.shape
    G = Hq // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(Dh)
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)

    pad_q = (-Sq) % block_q
    pad_k = (-Sk) % block_k
    qg = q.reshape(B, Sq, Hkv, G, Dh).transpose(0, 2, 3, 1, 4)  # (B,Hkv,G,Sq,Dh)
    if pad_q:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, 0), (0, pad_q), (0, 0)))
    kt = k.transpose(0, 2, 1, 3)  # (B,Hkv,Sk,Dh)
    vt = v.transpose(0, 2, 1, 3)
    if pad_k:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    n_q = (Sq + pad_q) // block_q
    n_k = (Sk + pad_k) // block_k

    kernel = functools.partial(
        _flash_kernel,
        scale=scale, block_q=block_q, block_k=block_k, causal=causal,
        seq_q=Sq, seq_k=Sk, groups=G,
    )
    out = pl.pallas_call(
        kernel,
        grid=(B, Hkv, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, 1, G, block_q, Dh), lambda b, h, i, j: (b, h, 0, i, 0)),
            pl.BlockSpec((1, 1, block_k, Dh), lambda b, h, i, j: (b, h, j, 0)),
            pl.BlockSpec((1, 1, block_k, Dh), lambda b, h, i, j: (b, h, j, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, G, block_q, Dh), lambda b, h, i, j: (b, h, 0, i, 0)
        ),
        out_shape=jax.ShapeDtypeStruct(qg.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G * block_q, 1), jnp.float32),
            pltpu.VMEM((G * block_q, 1), jnp.float32),
            pltpu.VMEM((G * block_q, Dh), jnp.float32),
        ],
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qg, kt, vt)
    out = out.transpose(0, 3, 1, 2, 4)[:, :Sq]  # (B,Sq,Hkv,G,Dh)
    return out.reshape(B, Sq, Hq, Dh)
