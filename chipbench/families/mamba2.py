"""Mamba2 (pure state-space stack: RMSNorm, in-projection to z, x, B, C and
dt, a causal depthwise convolution with SiLU over x, B and C, the selective
state-space recurrence with a scalar decay per head, a gated RMSNorm, the
out-projection; tied embeddings): the parameter layout the program serves,
how the benchmark draws it, what one forward costs, and a plain reference
forward written from the published description (arXiv:2405.21060 and
``mamba_ssm``'s ``Mamba2`` layer with ``ngroups = 1``).

The reference runs the recurrence one position at a time, not the chunked
algorithm the program uses, so the two agree only through the mathematics.
The configuration is a dict with ``mamba_ssm``'s key names, the layer's
settings flattened to the top level.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32

DISTINCT_LEAF = ("embed", "embedding")


def padded_vocab(cfg) -> int:
    m = cfg["pad_vocab_size_multiple"]
    return -(-cfg["vocab_size"] // m) * m


def _dims(cfg):
    d = cfg["d_model"]
    di = cfg["expand"] * d
    n, p = cfg["d_state"], cfg["headdim"]
    return d, di, n, p, di // p, cfg["d_conv"], padded_vocab(cfg)


def param_shapes(cfg) -> dict:
    d, di, n, _, h, w, v = _dims(cfg)
    L = cfg["n_layer"]
    conv = di + 2 * n
    dt = jnp.dtype(cfg["dtype"])  # the type served
    return {
        "embed": {"embedding": ((v, d), dt)},
        "layers": {"sub0": {
            "norm1": ((L, d), dt),
            "mixer": {
                "in_proj": ((L, d, 2 * di + 2 * n + h), dt),
                "conv_w": ((L, w, conv), dt), "conv_b": ((L, conv), dt),
                "A_log": ((L, h), F32), "D": ((L, h), F32),
                "dt_bias": ((L, h), F32), "norm": ((L, di), dt),
                "out_proj": ((L, di, d), dt),
            },
        }},
        "final_norm": ((d,), dt),
    }


def draw(path, shape, key):
    name = path[-1]
    if name in ("norm1", "norm", "final_norm"):
        return jax.random.uniform(key, shape, F32, 0.8, 1.2)
    if name == "conv_b":
        return jax.random.uniform(key, shape, F32, -0.1, 0.1)
    if name == "A_log":  # A = -exp(A_log) in [-16, -1], as mamba_ssm draws it
        return jnp.log(jax.random.uniform(key, shape, F32, 1.0, 16.0))
    if name == "D":
        return jax.random.uniform(key, shape, F32, 0.5, 1.5)
    if name == "dt_bias":  # softplus(dt_bias) log-uniform in [1e-3, 1e-1]
        dt = jnp.exp(jax.random.uniform(key, shape, F32,
                                        math.log(1e-3), math.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))
    if name == "embedding":
        return jax.random.uniform(key, shape, F32, -0.02, 0.02)
    bound = 1.0 / math.sqrt(shape[-2])  # conv: its width; else fan-in
    return jax.random.uniform(key, shape, F32, -bound, bound)


def check_program(cfg, mc) -> None:
    d, di, n, p, h, w, v = _dims(cfg)
    want = {
        "family": "ssm", "num_layers": cfg["n_layer"], "d_model": d,
        "d_ff": cfg["d_intermediate"], "vocab_size": v, "ssm_state": n,
        "ssm_headdim": p, "ssm_expand": cfg["expand"], "ssm_conv": w,
        "ssm_chunk": cfg["chunk_size"], "rmsnorm_eps": cfg["norm_epsilon"],
        "tie_embeddings": cfg["tie_embeddings"], "attn_every": 0,
        "num_experts": 0, "param_dtype": cfg["dtype"],
        "compute_dtype": cfg["dtype"],
    }
    got = {k: getattr(mc, k) for k in want}
    if got != want:
        bad = {k: (got[k], want[k]) for k in want if got[k] != want[k]}
        raise ValueError(f"program config departs from the file: {bad}")
    if cfg["ngroups"] != 1:
        raise ValueError("the program shares B and C across heads (ngroups 1)")


def counts(cfg, batch: int, seq: int) -> dict:
    """FLOPs and HBM bytes of one served forward: the projections, the
    unembedding at every position, the convolution and the state-space
    recurrence (an update and a read-out of each head's P x N state per
    position); bytes are each weight read once and the float32 logits
    written once."""
    d, di, n, p, h, w, v = _dims(cfg)
    L = cfg["n_layer"]
    t = batch * seq
    proj = d * (2 * di + 2 * n + h) + di * d
    conv = di + 2 * n
    matmul = 2 * t * (L * proj + v * d)
    scan = L * t * (2 * w * conv + 2 * 2 * h * p * n)
    params = L * (proj + w * conv + conv + 3 * h + di + d) + v * d + d
    f32_leaves = L * 3 * h  # A_log, D and dt_bias are float32
    weight_bytes = 2 * params + 2 * f32_leaves
    out_bytes = 4 * t * v
    return {"flops": float(matmul + scan), "weight_bytes": float(weight_bytes),
            "output_bytes": float(out_bytes),
            "bytes": float(weight_bytes + out_bytes),
            "params": float(params)}


def _rms(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def last_logits(cfg, p, tokens, num):
    d, di, n, hp, h, w, v = _dims(cfg)
    eps = cfg["norm_epsilon"]
    b, s = tokens.shape
    emb = num.w(p["embed"]["embedding"])
    x0 = jnp.take(emb, tokens, axis=0)

    def layer(hs, lp):
        lp = jax.tree_util.tree_map(num.w, lp)
        m = lp["mixer"]
        x = _rms(hs, lp["norm1"], eps)
        zxbcdt = num.dot("bsd,de->bse", x, m["in_proj"])
        z = zxbcdt[..., :di]
        xbc = zxbcdt[..., di:2 * di + 2 * n]
        dt = zxbcdt[..., 2 * di + 2 * n:]
        # causal depthwise convolution: out[t] = sum_k w[k] x[t - (W-1) + k]
        pad = jnp.pad(xbc, ((0, 0), (w - 1, 0), (0, 0)))
        conv = sum(pad[:, k:k + s] * m["conv_w"][k] for k in range(w))
        xbc = jax.nn.silu(conv + m["conv_b"])
        xs = xbc[..., :di].reshape(b, s, h, hp)
        bm, cm = xbc[..., di:di + n], xbc[..., di + n:]
        dt = jax.nn.softplus(dt + m["dt_bias"])  # (B, S, H)
        a = -jnp.exp(m["A_log"])

        def step(state, inp):
            x_t, dt_t, b_t, c_t = inp
            state = (state * jnp.exp(dt_t * a)[:, :, None, None]
                     + (dt_t[:, :, None] * x_t)[..., None] * b_t[:, None, None])
            y_t = jnp.einsum("bhpn,bn->bhp", state, c_t,
                             precision=lax.Precision.HIGHEST)
            return state, y_t

        st0 = jnp.zeros((b, h, hp, n), F32)
        _, ys = lax.scan(step, st0, (xs.swapaxes(0, 1), dt.swapaxes(0, 1),
                                     bm.swapaxes(0, 1), cm.swapaxes(0, 1)))
        y = ys.swapaxes(0, 1) + xs * m["D"][:, None]
        y = y.reshape(b, s, di) * jax.nn.silu(z)
        y = _rms(y, m["norm"], eps)
        return hs + num.dot("bse,ed->bsd", y, m["out_proj"]), None

    hs, _ = lax.scan(layer, x0, p["layers"]["sub0"])
    x = _rms(hs[:, -1], num.w(p["final_norm"]), eps)
    return num.dot("bd,vd->bv", x, emb)
