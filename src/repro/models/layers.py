"""Shared model layers (pure functional JAX).

Conventions
-----------
* params are nested dicts of jnp arrays; layer stacks carry a leading
  ``num_periods`` axis and are consumed via ``lax.scan``.
* weights live in ``cfg.param_dtype``; matmuls run in ``cfg.compute_dtype``
  with fp32 softmax/norm/accumulation.
* attention is *blockwise* (flash-style, online softmax) in pure jnp so that
  32k-token prefill never materialises an (S, S) score tensor and causal
  FLOPs are exact (static python loop over query blocks -> each block attends
  only to its prefix). The Pallas kernel in ``repro.kernels`` implements the
  same contract for TPU; ``repro.kernels.ops`` picks the backend.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig

# ---------------------------------------------------------------------------
# small utilities
# ---------------------------------------------------------------------------


def cdtype(cfg: ModelConfig):
    return jnp.dtype(cfg.compute_dtype)


def pdtype(cfg: ModelConfig):
    return jnp.dtype(cfg.param_dtype)


def rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)).astype(dt)


def _uniform(key, shape, scale, dtype):
    return jax.random.uniform(key, shape, jnp.float32, -scale, scale).astype(dtype)


def dense_init(key, d_in: int, d_out: int, dtype) -> jax.Array:
    return _uniform(key, (d_in, d_out), 1.0 / math.sqrt(d_in), dtype)


# ---------------------------------------------------------------------------
# RoPE (incl. M-RoPE for qwen2-vl)
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    """(head_dim/2,) inverse frequencies, fp32."""
    half = head_dim // 2
    return 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, Dh); positions: broadcastable to (..., S)."""
    half = x.shape[-1] // 2
    freqs = rope_freqs(x.shape[-1], theta)  # (half,)
    angles = positions.astype(jnp.float32)[..., None] * freqs  # (..., S, half)
    cos = jnp.cos(angles)[..., None, :]  # (..., S, 1, half)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate([x1f * cos - x2f * sin, x2f * cos + x1f * sin], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(
    x: jax.Array, positions: jax.Array, theta: float, sections: Tuple[int, ...]
) -> jax.Array:
    """Multimodal RoPE (qwen2-vl): ``positions`` is (3, ..., S); the half-dim
    frequency bands are split into ``sections`` (t, h, w), each rotated by its
    own position stream."""
    half = x.shape[-1] // 2
    assert sum(sections) == half, (sections, half)
    freqs = rope_freqs(x.shape[-1], theta)  # (half,)
    # select which position stream drives each frequency band
    sec_id = jnp.repeat(
        jnp.arange(len(sections)), jnp.array(sections), total_repeat_length=half
    )  # (half,)
    pos = positions.astype(jnp.float32)  # (3, ..., S)
    pos_per_band = jnp.take(pos, sec_id, axis=0)  # (half, ..., S) via axis move
    pos_per_band = jnp.moveaxis(pos_per_band, 0, -1)  # (..., S, half)
    angles = pos_per_band * freqs
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate([x1f * cos - x2f * sin, x2f * cos + x1f * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Blockwise (flash-style) attention — pure jnp, exact causal FLOPs
# ---------------------------------------------------------------------------

NEG_INF = -1e30


def _attn_block(q, k, v, m, l, acc, *, scale, mask=None):
    """One online-softmax update. q:(B,cq,H,G,Dh) k,v:(B,ck,H,Dh).

    m,l: (B,cq,H,G) fp32 running max / normaliser; acc: (B,cq,H,G,Dh) fp32.
    """
    s = jnp.einsum(
        "bqhgd,bkhd->bqhgk", q, k, preferred_element_type=jnp.float32
    ) * scale  # (B,cq,H,G,ck)
    if mask is not None:
        s = jnp.where(mask, s, NEG_INF)
    m_new = jnp.maximum(m, s.max(axis=-1))
    p = jnp.exp(s - m_new[..., None])
    corr = jnp.exp(m - m_new)
    l_new = l * corr + p.sum(axis=-1)
    pv = jnp.einsum("bqhgk,bkhd->bqhgd", p.astype(v.dtype), v,
                    preferred_element_type=jnp.float32)
    acc_new = acc * corr[..., None] + pv
    return m_new, l_new, acc_new


def _softmax_partial(qg, kj, vj, *, scale, mask=None):
    """Dense softmax partial over one kv span. qg:(B,cq,H,G,Dh),
    kj/vj:(B,ck,H,Dh) -> (m, l, acc) fp32."""
    s = jnp.einsum(
        "bqhgd,bkhd->bqhgk", qg, kj, preferred_element_type=jnp.float32
    ) * scale
    if mask is not None:
        s = jnp.where(mask, s, NEG_INF)
    m = s.max(axis=-1)
    p = jnp.exp(s - m[..., None])
    l = p.sum(axis=-1)
    acc = jnp.einsum(
        "bqhgk,bkhd->bqhgd", p.astype(vj.dtype), vj,
        preferred_element_type=jnp.float32,
    )
    return m, l, acc


def _combine_partials(parts):
    """Merge online-softmax partials [(m,l,acc), ...] -> output fp32."""
    m = parts[0][0]
    for mp_, _, _ in parts[1:]:
        m = jnp.maximum(m, mp_)
    l = jnp.zeros_like(m)
    acc = jnp.zeros(parts[0][2].shape, jnp.float32)
    for mi, li, ai in parts:
        c = jnp.exp(mi - m)
        l = l + li * c
        acc = acc + ai * c[..., None]
    return acc / jnp.maximum(l, 1e-30)[..., None]


def flash_attention_ref(
    q: jax.Array,  # (B, Sq, Hq, Dh)
    k: jax.Array,  # (B, Sk, Hkv, Dh)
    v: jax.Array,  # (B, Sk, Hkv, Dh)
    *,
    causal: bool,
    q_offset: int = 0,
    block_q: int = 512,
    block_k: int = 512,
    scale: Optional[float] = None,
) -> jax.Array:
    """Blockwise attention with online softmax; GQA folded into a group dim.

    Causal mode uses a *static* python loop over query blocks; each block is
    decomposed into a mask-free *prefix rectangle* (one dense matmul over
    kv[0 : i*block_q]) plus a masked *diagonal block*, combined with one
    2-way online-softmax merge. The lowered HLO carries the exact triangular
    FLOP count (matters for the roofline, EXPERIMENTS.md §Perf) and — unlike
    per-block variable-length scans — never tickles the XLA SPMD
    partitioner's dynamic-slice/transpose bug at 256+ devices.

    Non-causal mode scans fixed-size kv blocks with online softmax (memory
    O(Sq x block_k)).
    """
    B, Sq, Hq, Dh = q.shape
    _, Sk, Hkv, _ = k.shape
    G = Hq // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(Dh)
    qg = q.reshape(B, Sq, Hkv, G, Dh)

    if not causal:
        return _attention_scan_kv(qg, k, v, scale=scale, block_k=block_k
                                  ).reshape(B, Sq, Hq, Dh).astype(q.dtype)

    block_q = min(block_q, Sq)
    pad_q = (-Sq) % block_q
    if pad_q:
        qg = jnp.pad(qg, ((0, 0), (0, pad_q), (0, 0), (0, 0), (0, 0)))
    n_q = (Sq + pad_q) // block_q

    outs = []
    for i in range(n_q):  # static loop: exact causal prefix per q block
        qi = qg[:, i * block_q : (i + 1) * block_q]
        lo = q_offset + i * block_q           # first q position of the block
        hi = lo + block_q                     # one past last q position
        parts = []
        if lo > 0:  # prefix rectangle: fully visible, no mask needed
            parts.append(_softmax_partial(qi, k[:, :lo], v[:, :lo], scale=scale))
        # diagonal block: causal mask within [lo, min(hi, Sk))
        d_hi = min(hi, Sk)
        if d_hi > lo:
            kd, vd = k[:, lo:d_hi], v[:, lo:d_hi]
            q_pos = lo + jnp.arange(block_q)
            kv_pos = lo + jnp.arange(d_hi - lo)
            mask = q_pos[None, :, None, None, None] >= kv_pos[None, None, None, None, :]
            parts.append(_softmax_partial(qi, kd, vd, scale=scale, mask=mask))
        out = _combine_partials(parts)
        outs.append(out)
    out = jnp.concatenate(outs, axis=1)[:, :Sq]
    return out.reshape(B, Sq, Hq, Dh).astype(q.dtype)


def _attention_scan_kv(qg, k, v, *, scale, block_k):
    """Non-causal: fixed-length scan over kv blocks with online softmax."""
    B, Sq, Hkv, G, Dh = qg.shape
    Sk = k.shape[1]
    block_k = min(block_k, Sk)
    pad_k = (-Sk) % block_k
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    n_k = (Sk + pad_k) // block_k
    kb = k.reshape(B, n_k, block_k, Hkv, Dh).swapaxes(0, 1)
    vb = v.reshape(B, n_k, block_k, Hkv, Dh).swapaxes(0, 1)
    k_valid = (jnp.arange(n_k * block_k) < Sk).reshape(n_k, block_k)

    m = jnp.full((B, Sq, Hkv, G), NEG_INF, jnp.float32)
    l = jnp.zeros((B, Sq, Hkv, G), jnp.float32)
    acc = jnp.zeros((B, Sq, Hkv, G, Dh), jnp.float32)

    def body(carry, xs):
        m, l, acc = carry
        kj, vj, valid = xs
        mask = valid[None, None, None, None, :]
        m, l, acc = _attn_block(qg, kj, vj, m, l, acc, scale=scale, mask=mask)
        return (m, l, acc), None

    (m, l, acc), _ = lax.scan(body, (m, l, acc), (kb, vb, k_valid))
    return acc / jnp.maximum(l, 1e-30)[..., None]


def decode_attention_ref(
    q: jax.Array,        # (B, 1, Hq, Dh)
    k_cache: jax.Array,  # (B, L, Hkv, Dh)
    v_cache: jax.Array,  # (B, L, Hkv, Dh)
    lengths: jax.Array,  # (B,) valid cache length per sequence (incl. new token)
    *,
    scale: Optional[float] = None,
    block_k: int = 1024,
) -> jax.Array:
    """Single-token flash-decode: online softmax over KV blocks with per-seq
    length masking. Returns (B, 1, Hq, Dh).

    §Perf note (EXPERIMENTS.md §Perf, iterations 1-2): with the cache
    sequence-sharded over the ``model`` axis, any block-scan that
    ``dynamic_slice``s the L dimension forces the SPMD partitioner into
    involuntary full rematerialization — it *replicates the entire cache
    per layer* ("[SPMD] Involuntary full rematerialization" warnings; the
    HLO roofline showed 60x decode HBM inflation). For Sq=1 the fp32 score
    tensor is only (B, Hq, L) ~ 2 MB/shard, so the optimal XLA formulation
    is one dense masked pass: scores stay L-sharded, the softmax reduce and
    the p@V contraction partial-reduce over shards (flash-decode across
    devices for free). The VMEM-blocked structure lives in the Pallas
    kernel (``repro.kernels.decode_attention``), where it belongs.
    ``block_k`` is kept for API compatibility (the Pallas kernel uses it).
    """
    B, _, Hq, Dh = q.shape
    _, L, Hkv, _ = k_cache.shape
    G = Hq // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(Dh)
    qg = q.reshape(B, 1, Hkv, G, Dh)

    s = jnp.einsum(
        "bqhgd,bkhd->bqhgk", qg, k_cache, preferred_element_type=jnp.float32
    ) * scale  # (B,1,Hkv,G,L) fp32, L stays sharded
    mask = jnp.arange(L)[None, None, None, None, :] < \
        lengths[:, None, None, None, None]
    s = jnp.where(mask, s, NEG_INF)
    m = s.max(axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = p.sum(axis=-1)
    acc = jnp.einsum(
        "bqhgk,bkhd->bqhgd", p.astype(v_cache.dtype), v_cache,
        preferred_element_type=jnp.float32,
    )
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(B, 1, Hq, Dh).astype(q.dtype)


# ---------------------------------------------------------------------------
# Attention layer (projections + rope + cache plumbing)
# ---------------------------------------------------------------------------


def init_attention(cfg: ModelConfig, key, *, cross: bool = False) -> dict:
    dt = pdtype(cfg)
    d, dh = cfg.d_model, cfg.head_dim
    nq, nkv = cfg.num_heads, cfg.num_kv_heads
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], d, nq * dh, dt),
        "wk": dense_init(ks[1], d, nkv * dh, dt),
        "wv": dense_init(ks[2], d, nkv * dh, dt),
        "wo": dense_init(ks[3], nq * dh, d, dt),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((nq * dh,), dt)
        p["bk"] = jnp.zeros((nkv * dh,), dt)
        p["bv"] = jnp.zeros((nkv * dh,), dt)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((dh,), dt)
        p["k_norm"] = jnp.ones((dh,), dt)
    return p


def _project_qkv(cfg: ModelConfig, p: dict, x: jax.Array, kv_x: jax.Array):
    B, S, _ = x.shape
    Skv = kv_x.shape[1]
    nq, nkv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ct = cdtype(cfg)
    q = jnp.einsum("bsd,de->bse", x.astype(ct), p["wq"].astype(ct))
    k = jnp.einsum("bsd,de->bse", kv_x.astype(ct), p["wk"].astype(ct))
    v = jnp.einsum("bsd,de->bse", kv_x.astype(ct), p["wv"].astype(ct))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(ct)
        k = k + p["bk"].astype(ct)
        v = v + p["bv"].astype(ct)
    q = q.reshape(B, S, nq, dh)
    k = k.reshape(B, Skv, nkv, dh)
    v = v.reshape(B, Skv, nkv, dh)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.rmsnorm_eps)
        k = rms_norm(k, p["k_norm"], cfg.rmsnorm_eps)
    return q, k, v


def _rope_qk(cfg: ModelConfig, q, k, positions):
    if cfg.mrope_sections:
        q = apply_mrope(q, positions, cfg.rope_theta, cfg.mrope_sections)
        k = apply_mrope(k, positions, cfg.rope_theta, cfg.mrope_sections)
    else:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    return q, k


def attention_forward(
    cfg: ModelConfig,
    p: dict,
    x: jax.Array,
    positions: jax.Array,
    *,
    causal: bool = True,
    return_kv: bool = False,
):
    """Full-sequence attention (train / prefill). positions: (B, S) or
    (3, B, S) for M-RoPE."""
    q, k, v = _project_qkv(cfg, p, x, x)
    if cfg.rope:
        q, k = _rope_qk(cfg, q, k, positions)
    out = flash_attention_ref(q, k, v, causal=causal, scale=cfg.attn_scale or None)
    y = _out_proj(cfg, p, out)
    if return_kv:
        return y, (k, v)
    return y


def _out_proj(cfg: ModelConfig, p: dict, out: jax.Array) -> jax.Array:
    B, S = out.shape[:2]
    ct = cdtype(cfg)
    flat = out.reshape(B, S, cfg.num_heads * cfg.head_dim).astype(ct)
    return jnp.einsum("bse,ed->bsd", flat, p["wo"].astype(ct))


def cross_attention_forward(
    cfg: ModelConfig, p: dict, x: jax.Array, enc: jax.Array
) -> jax.Array:
    """Cross attention (whisper decoder): queries from x, kv from encoder
    output. No RoPE on cross path."""
    q, k, v = _project_qkv(cfg, p, x, enc)
    out = flash_attention_ref(q, k, v, causal=False)
    return _out_proj(cfg, p, out)


def attention_decode(
    cfg: ModelConfig,
    p: dict,
    x: jax.Array,          # (B, 1, D)
    positions: jax.Array,  # (B,) or (3, B) for mrope
    k_cache: jax.Array,    # (B, L, Hkv, Dh)
    v_cache: jax.Array,
):
    """One-token decode: rope at ``positions``, scatter new kv into the cache
    at ``positions``, flash-decode against the cache."""
    B = x.shape[0]
    if cfg.mrope_sections:
        pos_rope = positions[..., None]  # (3, B, 1)
        scatter_pos = positions[0]
    else:
        pos_rope = positions[:, None]  # (B, 1)
        scatter_pos = positions
    q, k, v = _project_qkv(cfg, p, x, x)
    if cfg.rope:
        q, k = _rope_qk(cfg, q, k, pos_rope)
    # scatter the new token's kv at per-sequence positions
    bidx = jnp.arange(B)
    k_cache = k_cache.at[bidx, scatter_pos].set(k[:, 0].astype(k_cache.dtype))
    v_cache = v_cache.at[bidx, scatter_pos].set(v[:, 0].astype(v_cache.dtype))
    lengths = scatter_pos + 1
    out = decode_attention_ref(q, k_cache, v_cache, lengths,
                               scale=cfg.attn_scale or None)
    return _out_proj(cfg, p, out), k_cache, v_cache


# ---------------------------------------------------------------------------
# SwiGLU MLP
# ---------------------------------------------------------------------------


def init_mlp(cfg: ModelConfig, key, d_ff: Optional[int] = None) -> dict:
    dt = pdtype(cfg)
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    return {
        "wg": dense_init(ks[0], d, f, dt),
        "wu": dense_init(ks[1], d, f, dt),
        "wd": dense_init(ks[2], f, d, dt),
    }


def mlp_forward(cfg: ModelConfig, p: dict, x: jax.Array) -> jax.Array:
    ct = cdtype(cfg)
    x = x.astype(ct)
    g = jnp.einsum("bsd,df->bsf", x, p["wg"].astype(ct))
    u = jnp.einsum("bsd,df->bsf", x, p["wu"].astype(ct))
    h = jax.nn.silu(g.astype(jnp.float32)).astype(ct) * u
    return jnp.einsum("bsf,fd->bsd", h, p["wd"].astype(ct))


# ---------------------------------------------------------------------------
# Mixture of Experts (GShard-style capacity-bounded einsum dispatch)
# ---------------------------------------------------------------------------


def init_moe(cfg: ModelConfig, key) -> dict:
    """The router over all ``num_experts``; the weights of the experts this
    device holds (``moe_held``); the shared expert, if any."""
    dt = pdtype(cfg)
    d, f, e = cfg.d_model, cfg.moe_d_ff, cfg.moe_held
    ks = jax.random.split(key, 5)
    scale = 1.0 / math.sqrt(d)
    p = {
        "router": dense_init(ks[0], d, cfg.num_experts, jnp.float32),  # router kept fp32
        "wg": _uniform(ks[1], (e, d, f), scale, dt),
        "wu": _uniform(ks[2], (e, d, f), scale, dt),
        "wd": _uniform(ks[3], (e, f, d), 1.0 / math.sqrt(f), dt),
    }
    if cfg.moe_shared_expert:
        p["shared"] = init_mlp(cfg, ks[4], cfg.moe_shared_d_ff)
    return p


def moe_forward(
    cfg: ModelConfig,
    p: dict,
    x: jax.Array,  # (B, S, D)
    *,
    capacity_factor: Optional[float] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Token-choice top-k routing with per-(batch-row) expert capacity.

    Returns (y, aux_loss). Dispatch/combine are one-hot einsums (GShard
    pattern) — TPU-friendly: everything is dense matmul on the MXU and the
    (B, S, E, C) dispatch tensor shards over E on the model axis.
    """
    B, S, D = x.shape
    E, K = cfg.num_experts, cfg.experts_per_token
    cf = capacity_factor if capacity_factor is not None else cfg.moe_capacity_factor
    C = max(K, int(math.ceil(K * S * cf / E)))
    ct = cdtype(cfg)

    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)  # (B,S,E) fp32
    gate_vals, gate_idx = lax.top_k(probs, K)  # (B,S,K)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    # expert one-hot over the K choices: (B,S,K,E)
    onehot = jax.nn.one_hot(gate_idx, E, dtype=jnp.float32)
    # position of each (token, choice) in its expert's buffer, counting over
    # (s, k) in order: cumulative sum over flattened (S*K) per batch row.
    flat = onehot.reshape(B, S * K, E)
    pos = jnp.cumsum(flat, axis=1) - flat  # (B, S*K, E) position before me
    pos = pos.reshape(B, S, K, E)
    in_cap = (pos < C) & (onehot > 0)
    pos_c = jnp.sum(pos * onehot, axis=-1)  # (B,S,K) my slot id
    kept = jnp.any(in_cap, axis=-1)  # (B,S,K)

    # dispatch: (B,S,E,C) — built in compute dtype: the (B,S,E,C) tensors are
    # the largest MoE intermediates and bf16 halves their HBM traffic
    # (EXPERIMENTS.md §Perf jamba iteration); routing decisions (top-k,
    # positions) stay fp32/int above.
    cap_onehot = jax.nn.one_hot(pos_c, C, dtype=ct) * kept[..., None].astype(ct)
    onehot_ct = onehot.astype(ct)
    dispatch = jnp.einsum("bske,bskc->bsec", onehot_ct, cap_onehot)
    combine = jnp.einsum(
        "bske,bskc,bsk->bsec", onehot_ct, cap_onehot, gate_vals.astype(ct)
    )

    xin = jnp.einsum("bsec,bsd->ebcd", dispatch, x.astype(ct))
    g = jnp.einsum("ebcd,edf->ebcf", xin, p["wg"].astype(ct))
    u = jnp.einsum("ebcd,edf->ebcf", xin, p["wu"].astype(ct))
    h = jax.nn.silu(g.astype(jnp.float32)).astype(ct) * u
    eout = jnp.einsum("ebcf,efd->ebcd", h, p["wd"].astype(ct))
    y = jnp.einsum("bsec,ebcd->bsd", combine, eout)

    if cfg.moe_shared_expert:
        y = y + mlp_forward(cfg, p["shared"], x)

    # load-balancing aux loss (Switch-style)
    frac_tokens = jnp.mean(onehot.sum(2), axis=(0, 1))  # (E,) fraction routed
    frac_probs = jnp.mean(probs, axis=(0, 1))
    aux = E * jnp.sum(frac_tokens * frac_probs) / K
    return y.astype(x.dtype), aux.astype(jnp.float32)


#: rows of one tile of the held experts' grouped products: each expert's
#: rows start a tile, so a kernel that visits only the tiles of its groups
#: reads each expert's weights once per tile of its rows
EXPERT_ROW_TILE = 128


def moe_dropless_forward(
    cfg: ModelConfig, p: dict, x: jax.Array  # (B, S, D)
) -> Tuple[jax.Array, jax.Array]:
    """Dropless token-choice top-k over ``num_experts`` experts, of which this
    device holds ``moe_held``, numbered from ``expert_offset``: one rank of
    an expert-parallel layer, run without its exchange.

    The router (float32 at full precision, no bias) scores every expert;
    each token takes its ``experts_per_token`` highest logits and weighs
    them by a softmax over those alone. The (token, expert) pairs of held
    experts are sorted by expert and run as grouped products, each held
    expert over exactly its own rows; the pairs of experts held elsewhere
    contribute nothing here. The shared expert, if any, runs on every token.
    The products run over a buffer of rows sized for the most the held
    experts can take (every token picking ``min(K, moe_held)`` of them), so
    nothing is dropped; only the groups' own tiles are computed.

    Returns (y, counters): counters is int32 (2,), the rows the held experts
    computed and the rows of the busiest held expert.
    """
    from repro.kernels import ops  # imports this module: not at the top

    with jax.named_scope("sage.moe"):  # names its ops in a device trace
        B, S, D = x.shape
        K, H, tile = cfg.experts_per_token, cfg.moe_held, EXPERT_ROW_TILE
        T = B * S
        ct = cdtype(cfg)
        xf = x.reshape(T, D)
        logits = jnp.dot(xf.astype(jnp.float32), p["router"],
                         precision=lax.Precision.HIGHEST)  # (T, E)
        top_logits, top_idx = lax.top_k(logits, K)  # (T, K)
        gates = jax.nn.softmax(top_logits, axis=-1)
        local = (top_idx - cfg.expert_offset).reshape(T * K)
        held = (local >= 0) & (local < H)
        # each held (token, expert) pair's row: its expert's rows start a tile
        onehot = jax.nn.one_hot(jnp.where(held, local, H), H, dtype=jnp.int32)
        sizes = onehot.sum(0)  # (H,) rows of each held expert
        padded = (sizes + tile - 1) // tile * tile
        rank = (jnp.cumsum(onehot, 0) * onehot).sum(1) - 1
        # the most rows the groups can take, each group's last tile padded
        M = -(-(T * min(K, H) + H * (tile - 1)) // tile) * tile
        row = jnp.where(held, (jnp.cumsum(padded) - padded)[local % H] + rank, M)
        token = jnp.full((M,), T, jnp.int32).at[row].set(
            jnp.arange(T * K, dtype=jnp.int32) // K, mode="drop")
        rows = jnp.concatenate([xf.astype(ct), jnp.zeros((1, D), ct)])[token]

        def gmm(lhs, w):
            return ops.grouped_matmul(lhs, w.astype(ct), padded, out_dtype=ct,
                                      tile_rows=tile)

        h = jax.nn.silu(gmm(rows, p["wg"]).astype(jnp.float32)).astype(ct) \
            * gmm(rows, p["wu"])
        out = gmm(h, p["wd"])  # (M, D); rows past the groups are undefined
        back = out[jnp.minimum(row, M - 1)].reshape(T, K, D).astype(jnp.float32)
        back = jnp.where(held.reshape(T, K, 1), back, 0.0)
        y = (gates[..., None] * back).sum(axis=1).reshape(B, S, D)
        if cfg.moe_shared_expert:
            y = y + mlp_forward(cfg, p["shared"], x).astype(jnp.float32)
        counters = jnp.stack([sizes.sum(), sizes.max()])
        return y.astype(x.dtype), counters


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------


def init_embed(cfg: ModelConfig, key) -> dict:
    dt = pdtype(cfg)
    ks = jax.random.split(key, 2)
    p = {"embedding": _uniform(ks[0], (cfg.vocab_size, cfg.d_model), 0.02, dt)}
    if not cfg.tie_embeddings:
        p["lm_head"] = dense_init(ks[1], cfg.d_model, cfg.vocab_size, dt)
    return p


def embed(cfg: ModelConfig, p: dict, tokens: jax.Array) -> jax.Array:
    h = jnp.take(p["embedding"], tokens, axis=0).astype(cdtype(cfg))
    if cfg.embedding_multiplier != 1.0:
        h = h * cfg.embedding_multiplier
    return h


def unembed(cfg: ModelConfig, p: dict, x: jax.Array) -> jax.Array:
    ct = cdtype(cfg)
    if cfg.tie_embeddings:
        w = p["embedding"].astype(ct).T
    else:
        w = p["lm_head"].astype(ct)
    logits = jnp.einsum("bsd,dv->bsv", x.astype(ct), w).astype(jnp.float32)
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    return logits
