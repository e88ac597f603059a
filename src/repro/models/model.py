"""Model facade: init / forward / prefill / decode for every assigned family.

Layer stacks are scanned over *periods* (see ``configs.base``): params and
decode caches carry a leading ``num_periods`` axis and are consumed with
``lax.scan``, so the HLO is depth-independent (fast 512-device AOT compiles)
and XLA can overlap the FSDP all-gather of period *i+1* with compute of
period *i*.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig, SublayerSpec
from repro.distributed.act_sharding import constrain
from repro.models import layers as L
from repro.models import mamba2 as M

Params = Dict[str, Any]
Cache = Dict[str, Any]


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _init_sublayer(cfg: ModelConfig, spec: SublayerSpec, key, *, cross: bool) -> dict:
    ks = jax.random.split(key, 4)
    dt = L.pdtype(cfg)
    p: dict = {"norm1": jnp.ones((cfg.d_model,), dt)}
    if spec.mixer == "attn":
        p["mixer"] = L.init_attention(cfg, ks[0])
    else:
        p["mixer"] = M.init_mamba(cfg, ks[0])
    if cross:
        p["cross"] = L.init_attention(cfg, ks[1])
        p["norm_cross"] = jnp.ones((cfg.d_model,), dt)
    if spec.ffn == "dense":
        p["norm2"] = jnp.ones((cfg.d_model,), dt)
        p["ffn"] = L.init_mlp(cfg, ks[2])
    elif spec.ffn == "moe":
        p["norm2"] = jnp.ones((cfg.d_model,), dt)
        p["ffn"] = L.init_moe(cfg, ks[3])
    return p


def _stack(trees):
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees)


def _init_stack(cfg: ModelConfig, key, num_periods: int, specs, *, cross: bool) -> dict:
    """Stacked per-period params: {'sub{i}': pytree with leading period axis}."""
    out = {}
    for i, spec in enumerate(specs):
        ks = jax.random.split(jax.random.fold_in(key, i), num_periods)
        out[f"sub{i}"] = _stack([_init_sublayer(cfg, spec, k, cross=cross) for k in ks])
    return out


def init_params(cfg: ModelConfig, key) -> Params:
    ks = jax.random.split(key, 4)
    dt = L.pdtype(cfg)
    params: Params = {"embed": L.init_embed(cfg, ks[0])}
    params["layers"] = _init_stack(
        cfg, ks[1], cfg.num_periods, cfg.period_spec(), cross=cfg.is_encoder_decoder
    )
    params["final_norm"] = jnp.ones((cfg.d_model,), dt)
    if cfg.is_encoder_decoder:
        enc_spec = (SublayerSpec(mixer="attn", ffn="dense"),)
        params["enc_layers"] = _init_stack(cfg, ks[2], cfg.encoder_layers, enc_spec, cross=False)
        params["enc_final_norm"] = jnp.ones((cfg.d_model,), dt)
    return params


# ---------------------------------------------------------------------------
# sublayer application
# ---------------------------------------------------------------------------


def _residual(cfg: ModelConfig, y: jax.Array) -> jax.Array:
    """A sublayer's output as it joins the residual stream."""
    if cfg.residual_multiplier != 1.0:
        y = y * cfg.residual_multiplier
    return y


def _ffn(cfg: ModelConfig, spec: SublayerSpec, p: dict, x: jax.Array):
    """The sublayer's FFN on its normed input: (y, aux, expert counters or
    None)."""
    if spec.ffn == "dense":
        return L.mlp_forward(cfg, p, x), jnp.zeros((), jnp.float32), None
    if cfg.moe_dropless:
        y, cnt = L.moe_dropless_forward(cfg, p, x)
        return y, jnp.zeros((), jnp.float32), cnt
    y, aux = L.moe_forward(cfg, p, x)
    return y, aux, None


def _merge_counters(a, b):
    """Expert counters over layers: rows summed, the busiest expert's rows
    the largest. None where no layer has dropless experts."""
    if b is None:
        return a
    return jnp.stack([a[0] + b[0], jnp.maximum(a[1], b[1])])


def _apply_sublayer_full(
    cfg: ModelConfig,
    spec: SublayerSpec,
    p: dict,
    h: jax.Array,
    *,
    positions,
    causal: bool,
    enc: Optional[jax.Array],
    cache: Optional[dict],
    mode: str,  # 'train' | 'prefill'
):
    """Full-sequence sublayer. Returns (h, new_cache_entry, aux, expert
    counters or None)."""
    aux = jnp.zeros((), jnp.float32)
    cnt = None
    new_cache = {}
    x = L.rms_norm(h, p["norm1"], cfg.rmsnorm_eps)
    if spec.mixer == "attn":
        if mode == "prefill" and cache is not None:
            y, (k, v) = L.attention_forward(cfg, p["mixer"], x, positions, causal=causal, return_kv=True)
            S = x.shape[1]
            new_cache["k"] = lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype), (0, 0, 0, 0))
            new_cache["v"] = lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype), (0, 0, 0, 0))
        else:
            y = L.attention_forward(cfg, p["mixer"], x, positions, causal=causal)
    else:
        if mode == "prefill" and cache is not None:
            y, fs, conv_tail = M.mamba_forward(cfg, p["mixer"], x, return_state=True)
            new_cache["ssm"] = fs.astype(cache["ssm"].dtype)
            new_cache["conv"] = conv_tail.astype(cache["conv"].dtype)
        else:
            y = M.mamba_forward(cfg, p["mixer"], x)
    h = h + _residual(cfg, y.astype(h.dtype))

    if "cross" in p and enc is not None:
        xc = L.rms_norm(h, p["norm_cross"], cfg.rmsnorm_eps)
        if mode == "prefill" and cache is not None:
            yc, (ck, cv) = _cross_with_kv(cfg, p["cross"], xc, enc)
            new_cache["ck"] = ck.astype(cache["ck"].dtype)
            new_cache["cv"] = cv.astype(cache["cv"].dtype)
        else:
            yc = L.cross_attention_forward(cfg, p["cross"], xc, enc)
        h = h + _residual(cfg, yc.astype(h.dtype))

    if spec.ffn != "none":
        x2 = L.rms_norm(h, p["norm2"], cfg.rmsnorm_eps)
        f, aux, cnt = _ffn(cfg, spec, p["ffn"], x2)
        h = h + _residual(cfg, f.astype(h.dtype))
    return h, new_cache, aux, cnt


def _cross_with_kv(cfg, p, x, enc):
    q, k, v = L._project_qkv(cfg, p, x, enc)
    out = L.flash_attention_ref(q, k, v, causal=False)
    return L._out_proj(cfg, p, out), (k, v)


def _apply_sublayer_step(
    cfg: ModelConfig,
    spec: SublayerSpec,
    p: dict,
    h: jax.Array,          # (B, 1, D)
    cache: dict,
    *,
    positions: jax.Array,  # (B,)
):
    """One-token decode sublayer. Returns (h, new_cache_entry, aux)."""
    aux = jnp.zeros((), jnp.float32)
    new_cache = dict(cache)
    x = L.rms_norm(h, p["norm1"], cfg.rmsnorm_eps)
    if spec.mixer == "attn":
        pos = jnp.broadcast_to(positions, (3,) + positions.shape) if cfg.mrope_sections else positions
        y, nk, nv = L.attention_decode(cfg, p["mixer"], x, pos, cache["k"], cache["v"])
        new_cache["k"], new_cache["v"] = nk, nv
    else:
        y, ns, ncv = M.mamba_decode(cfg, p["mixer"], x, cache["ssm"], cache["conv"])
        new_cache["ssm"], new_cache["conv"] = ns.astype(cache["ssm"].dtype), ncv
    h = h + _residual(cfg, y.astype(h.dtype))

    if "cross" in p:
        xc = L.rms_norm(h, p["norm_cross"], cfg.rmsnorm_eps)
        q, _, _ = L._project_qkv(cfg, p["cross"], xc, xc)
        out = L.decode_attention_ref(
            q,
            cache["ck"],
            cache["cv"],
            jnp.full((h.shape[0],), cache["ck"].shape[1], jnp.int32),
        )
        h = h + _residual(cfg, L._out_proj(cfg, p["cross"], out).astype(h.dtype))

    if spec.ffn != "none":
        x2 = L.rms_norm(h, p["norm2"], cfg.rmsnorm_eps)
        if spec.ffn == "dense" or cfg.moe_dropless:
            f, _, _ = _ffn(cfg, spec, p["ffn"], x2)
        else:
            B = x2.shape[0]
            f, aux = L.moe_forward(
                cfg, p["ffn"], x2.reshape(1, B, -1), capacity_factor=4.0
            )
            f = f.reshape(B, 1, -1)
        h = h + _residual(cfg, f.astype(h.dtype))
    return h, new_cache, aux


# ---------------------------------------------------------------------------
# remat policy
# ---------------------------------------------------------------------------


def _maybe_remat(cfg: ModelConfig, fn):
    if cfg.remat_policy == "none":
        return fn
    if cfg.remat_policy == "dots_saveable":
        return jax.checkpoint(fn, policy=jax.checkpoint_policies.dots_saveable)
    return jax.checkpoint(fn, policy=jax.checkpoint_policies.nothing_saveable)


# ---------------------------------------------------------------------------
# stacks
# ---------------------------------------------------------------------------


def _run_stack_full(
    cfg: ModelConfig,
    stack_params: dict,
    specs,
    h: jax.Array,
    *,
    positions,
    causal: bool,
    enc: Optional[jax.Array] = None,
    cache_stack: Optional[dict] = None,
    mode: str = "train",
):
    """Scan the period stack over a full sequence. Returns (h, new_cache,
    aux, expert counters): the counters are int32 (2,) where the stack has
    dropless experts (``_merge_counters``), else None."""

    def period_fn(carry, xs):
        h, aux, cnt = carry
        if cache_stack is not None:
            pp, cc = xs
        else:
            pp, cc = xs, None
        new_cc = {}
        for i, spec in enumerate(specs):
            ci = cc[f"sub{i}"] if cc is not None else None
            h, nci, a, c = _apply_sublayer_full(
                cfg, spec, pp[f"sub{i}"], h,
                positions=positions, causal=causal, enc=enc, cache=ci, mode=mode,
            )
            aux = aux + a
            cnt = _merge_counters(cnt, c)
            if cc is not None:
                new_cc[f"sub{i}"] = {**ci, **nci}
        h = constrain(h, "residual")  # scan-carry layout (saved for backward)
        return (h, aux, cnt), new_cc if cache_stack is not None else 0

    period_fn = _maybe_remat(cfg, period_fn)
    xs = (stack_params, cache_stack) if cache_stack is not None else stack_params
    cnt0 = jnp.zeros((2,), jnp.int32) if cfg.moe_dropless else None
    (h, aux, cnt), new_cache = lax.scan(
        period_fn, (h, jnp.zeros((), jnp.float32), cnt0), xs)
    return h, (new_cache if cache_stack is not None else None), aux, cnt


def _run_stack_step(cfg: ModelConfig, stack_params: dict, specs, h, cache_stack, *, positions):
    def period_fn(carry, xs):
        h, aux = carry
        pp, cc = xs
        new_cc = {}
        for i, spec in enumerate(specs):
            h, nci, a = _apply_sublayer_step(
                cfg, spec, pp[f"sub{i}"], h, cc[f"sub{i}"], positions=positions
            )
            aux = aux + a
            new_cc[f"sub{i}"] = nci
        return (h, aux), new_cc

    (h, aux), new_cache = lax.scan(
        period_fn, (h, jnp.zeros((), jnp.float32)), (stack_params, cache_stack)
    )
    return h, new_cache, aux


# ---------------------------------------------------------------------------
# public entry points
# ---------------------------------------------------------------------------


def _encode(cfg: ModelConfig, params: Params, enc_embeds: jax.Array) -> jax.Array:
    """Whisper encoder: non-causal self-attention over frame embeddings."""
    S = enc_embeds.shape[1]
    pos = jnp.arange(S)[None, :]
    enc_spec = (SublayerSpec(mixer="attn", ffn="dense"),)
    h = enc_embeds.astype(L.cdtype(cfg))
    h, _, _, _ = _run_stack_full(
        cfg, params["enc_layers"], enc_spec, h, positions=pos, causal=cfg.encoder_causal
    )
    return L.rms_norm(h, params["enc_final_norm"], cfg.rmsnorm_eps)


def forward(cfg: ModelConfig, params: Params, batch: Dict[str, jax.Array],
            *, counters: bool = False):
    """Training/eval forward. Returns (logits (B,S,V) fp32, aux_loss), and
    with ``counters`` the expert counters too: int32 (2,), the (token,
    expert) rows the held experts computed summed over layers, and the rows
    of the busiest held expert, the largest over layers (None without
    dropless experts)."""
    tokens = batch["tokens"]
    B, S = tokens.shape
    if cfg.mrope_sections:
        positions = batch.get("mrope_positions")
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(S)[None, None], (3, B, S))
    else:
        positions = jnp.arange(S)[None, :]
    enc = None
    if cfg.is_encoder_decoder:
        enc = _encode(cfg, params, batch["encoder_embeds"])
    h = L.embed(cfg, params["embed"], tokens)
    h, _, aux, cnt = _run_stack_full(
        cfg, params["layers"], cfg.period_spec(), h,
        positions=positions, causal=cfg.causal, enc=enc,
    )
    h = L.rms_norm(h, params["final_norm"], cfg.rmsnorm_eps)
    logits = L.unembed(cfg, params["embed"], h)
    return (logits, aux, cnt) if counters else (logits, aux)


def init_cache(cfg: ModelConfig, batch: int, max_len: int, enc_len: int = 0) -> Cache:
    """Concrete zero-filled decode cache (leading num_periods axis per leaf)."""
    P = cfg.num_periods
    dt = L.cdtype(cfg)
    cache: Cache = {}
    for i, spec in enumerate(cfg.period_spec()):
        entry: dict = {}
        if spec.mixer == "attn":
            kv = (P, batch, max_len, cfg.num_kv_heads, cfg.head_dim)
            entry["k"] = jnp.zeros(kv, dt)
            entry["v"] = jnp.zeros(kv, dt)
        else:
            entry["ssm"] = jnp.zeros(
                (P, batch, cfg.ssm_nheads, cfg.ssm_headdim, cfg.ssm_state), jnp.float32
            )
            entry["conv"] = jnp.zeros((P, batch, cfg.ssm_conv - 1, cfg.ssm_conv_dim), dt)
        if cfg.is_encoder_decoder:
            ckv = (P, batch, enc_len, cfg.num_kv_heads, cfg.head_dim)
            entry["ck"] = jnp.zeros(ckv, dt)
            entry["cv"] = jnp.zeros(ckv, dt)
        cache[f"sub{i}"] = entry
    return cache


def prefill(cfg: ModelConfig, params: Params, batch: Dict[str, jax.Array], cache: Cache):
    """Run the prompt through the model, filling the cache.

    Returns (last-position logits (B,V), cache, aux).
    """
    tokens = batch["tokens"]
    B, S = tokens.shape
    if cfg.mrope_sections:
        positions = batch.get("mrope_positions")
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(S)[None, None], (3, B, S))
    else:
        positions = jnp.arange(S)[None, :]
    enc = None
    if cfg.is_encoder_decoder:
        enc = _encode(cfg, params, batch["encoder_embeds"])
    h = L.embed(cfg, params["embed"], tokens)
    h, cache, aux, _ = _run_stack_full(
        cfg, params["layers"], cfg.period_spec(), h,
        positions=positions, causal=cfg.causal, enc=enc,
        cache_stack=cache, mode="prefill",
    )
    h = L.rms_norm(h[:, -1:], params["final_norm"], cfg.rmsnorm_eps)
    logits = L.unembed(cfg, params["embed"], h)[:, 0]
    return logits, cache, aux


def decode_step(
    cfg: ModelConfig,
    params: Params,
    tokens: jax.Array,     # (B, 1)
    positions: jax.Array,  # (B,) absolute position of the new token
    cache: Cache,
):
    """One decode step. Returns (logits (B,V) fp32, new cache)."""
    h = L.embed(cfg, params["embed"], tokens)
    h, cache, _ = _run_stack_step(
        cfg, params["layers"], cfg.period_spec(), h, cache, positions=positions
    )
    h = L.rms_norm(h, params["final_norm"], cfg.rmsnorm_eps)
    logits = L.unembed(cfg, params["embed"], h)[:, 0]
    return logits, cache
