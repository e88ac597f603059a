"""Qwen2 (dense decoder, grouped-query attention with q/k/v biases,
SwiGLU MLP, RoPE, RMSNorm, tied embeddings): the parameter layout the
program serves, how the benchmark draws it, what one forward costs, and a
plain reference forward written from the published description
(huggingface ``Qwen2ForCausalLM``).

The configuration is a dict with Hugging Face's key names.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32

# the leaf that differs from one function to the next: the tied embedding,
# which changes every logit
DISTINCT_LEAF = ("embed", "embedding")


def _dims(cfg):
    d = cfg["hidden_size"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return d, hq, hkv, d // hq, cfg["intermediate_size"], cfg["vocab_size"]


def param_shapes(cfg) -> dict:
    """The program's parameter tree: layers stacked on a leading axis."""
    d, hq, hkv, dh, f, v = _dims(cfg)
    n = cfg["num_hidden_layers"]
    dt = jnp.dtype(cfg["torch_dtype"])  # the type served
    return {
        "embed": {"embedding": ((v, d), dt)},
        "layers": {"sub0": {
            "norm1": ((n, d), dt),
            "mixer": {
                "wq": ((n, d, hq * dh), dt), "wk": ((n, d, hkv * dh), dt),
                "wv": ((n, d, hkv * dh), dt), "wo": ((n, hq * dh, d), dt),
                "bq": ((n, hq * dh), dt), "bk": ((n, hkv * dh), dt),
                "bv": ((n, hkv * dh), dt),
            },
            "norm2": ((n, d), dt),
            "ffn": {"wg": ((n, d, f), dt), "wu": ((n, d, f), dt),
                    "wd": ((n, f, d), dt)},
        }},
        "final_norm": ((d,), dt),
    }


def draw(path, shape, key):
    """One leaf from ``key``, in float32 (the caller casts)."""
    name = path[-1]
    if name in ("norm1", "norm2", "final_norm"):
        return jax.random.uniform(key, shape, F32, 0.8, 1.2)
    if name in ("bq", "bk", "bv"):
        return jax.random.uniform(key, shape, F32, -0.1, 0.1)
    if name == "embedding":
        return jax.random.uniform(key, shape, F32, -0.02, 0.02)
    bound = 1.0 / math.sqrt(shape[-2])  # fan-in of a (.., in, out) matrix
    return jax.random.uniform(key, shape, F32, -bound, bound)


def check_program(cfg, mc) -> None:
    """Raise if the program's ``ModelConfig`` serves another model than
    the configuration file states."""
    d, hq, hkv, dh, f, v = _dims(cfg)
    want = {
        "family": "dense", "num_layers": cfg["num_hidden_layers"],
        "d_model": d, "num_heads": hq, "num_kv_heads": hkv, "head_dim": dh,
        "d_ff": f, "vocab_size": v, "qkv_bias": True, "qk_norm": False,
        "rope_theta": cfg["rope_theta"], "rmsnorm_eps": cfg["rms_norm_eps"],
        "tie_embeddings": cfg["tie_word_embeddings"], "num_experts": 0,
        "param_dtype": cfg["torch_dtype"], "compute_dtype": cfg["torch_dtype"],
        "mrope_sections": (),
    }
    got = {k: getattr(mc, k) for k in want}
    if got != want:
        bad = {k: (got[k], want[k]) for k in want if got[k] != want[k]}
        raise ValueError(f"program config departs from the file: {bad}")


def counts(cfg, batch: int, seq: int) -> dict:
    """FLOPs and HBM bytes of one served forward: every projection, the
    causal half of attention's two products, the unembedding at every
    position (the program returns all of them); bytes are each weight read
    once and the float32 logits written once."""
    d, hq, hkv, dh, f, v = _dims(cfg)
    n = cfg["num_hidden_layers"]
    t = batch * seq
    per_layer = d * hq * dh * 2 + d * hkv * dh * 2 + 3 * d * f
    matmul = 2 * t * (n * per_layer + v * d)
    attn = n * 2 * 2 * batch * hq * dh * seq * (seq + 1) / 2
    params = n * (per_layer + 2 * d + (hq + 2 * hkv) * dh) + v * d + d
    weight_bytes = 2 * params
    out_bytes = 4 * t * v
    return {"flops": float(matmul + attn), "weight_bytes": float(weight_bytes),
            "output_bytes": float(out_bytes),
            "bytes": float(weight_bytes + out_bytes),
            "params": float(params)}


def _rms(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def last_logits(cfg, p, tokens, num):
    """Logits of the last position, (B, V) float32. ``num`` supplies the
    arithmetic: ``num.w`` (a weight as used), ``num.dot`` (a product)."""
    d, hq, hkv, dh, f, v = _dims(cfg)
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    b, s = tokens.shape
    emb = num.w(p["embed"]["embedding"])
    h = jnp.take(emb, tokens, axis=0)
    inv = theta ** (-jnp.arange(0, dh, 2, dtype=F32) / dh)
    ang = jnp.arange(s, dtype=F32)[:, None] * inv  # (S, dh/2)
    cos = jnp.cos(jnp.concatenate([ang, ang], -1))[None, :, None, :]
    sin = jnp.sin(jnp.concatenate([ang, ang], -1))[None, :, None, :]

    def rope(x):
        x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
        return x * cos + jnp.concatenate([-x2, x1], -1) * sin

    causal = jnp.tril(jnp.ones((s, s), bool))

    def layer(h, lp):
        lp = jax.tree_util.tree_map(num.w, lp)
        m = lp["mixer"]
        x = _rms(h, lp["norm1"], eps)
        q = (num.dot("bsd,de->bse", x, m["wq"]) + m["bq"]).reshape(b, s, hq, dh)
        k = (num.dot("bsd,de->bse", x, m["wk"]) + m["bk"]).reshape(b, s, hkv, dh)
        vv = (num.dot("bsd,de->bse", x, m["wv"]) + m["bv"]).reshape(b, s, hkv, dh)
        q, k = rope(q), rope(k)
        k = jnp.repeat(k, hq // hkv, axis=2)
        vv = jnp.repeat(vv, hq // hkv, axis=2)
        sc = num.dot("bqhd,bkhd->bhqk", q, k) / math.sqrt(dh)
        sc = jnp.where(causal, sc, -jnp.inf)
        pr = jax.nn.softmax(sc, axis=-1)
        o = num.dot("bhqk,bkhd->bqhd", pr, vv).reshape(b, s, hq * dh)
        h = h + num.dot("bse,ed->bsd", o, m["wo"])
        x = _rms(h, lp["norm2"], eps)
        fp = lp["ffn"]
        g = jax.nn.silu(num.dot("bsd,df->bsf", x, fp["wg"]))
        u = num.dot("bsd,df->bsf", x, fp["wu"])
        h = h + num.dot("bsf,fd->bsd", g * u, fp["wd"])
        return h, None

    h, _ = lax.scan(layer, h, p["layers"]["sub0"])
    x = _rms(h[:, -1], num.w(p["final_norm"]), eps)
    return num.dot("bd,vd->bv", x, emb)
