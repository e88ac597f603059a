"""GraniteMoeHybrid (granite-4.0-h: Mamba-2 and NoPE grouped-query attention
layers, each followed by a dropless top-k mixture of SwiGLU experts plus a
shared SwiGLU expert; muP multipliers on the embeddings, the residual
branches and the logits; RMSNorm; tied embeddings): the parameter layout the
program serves, how the benchmark draws it, what one forward costs, and a
plain reference forward written from the published description (Hugging
Face ``GraniteMoeHybridForCausalLM``).

Each layer is

    h = h + r * mixer(rms(h))
    x = rms(h)
    h = h + r * (sum_{e in top-k} softmax(top-k logits)_e * E_e(x) + S(x))

with the router ``x @ W_r`` (no bias), the softmax over the k picked logits
only, and ``r`` the residual multiplier. The model's input is the embedding
times the embedding multiplier, its logits ``rms(h) @ emb.T`` over the
logits scaling.

One expert-parallel rank: the router scores all ``num_experts_routed``
experts, and only the ``num_local_experts`` held here, numbered from
``held_expert_offset``, add to the result; what the others would add is left
out, in the program and in this reference alike. The reference computes
every held expert densely over every token and weighs it by its gate, zero
where the token did not pick it; it runs the Mamba-2 recurrence one
position at a time. The configuration is a dict with Hugging Face's key
names; ``layer_types`` is the published list, of which the first
``num_hidden_layers`` are served.
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
    di = cfg["mamba_expand"] * d
    return {
        "d": d, "hq": cfg["num_attention_heads"],
        "hkv": cfg["num_key_value_heads"], "dh": d // cfg["num_attention_heads"],
        "f": cfg["intermediate_size"], "fs": cfg["shared_intermediate_size"],
        "E": cfg["num_experts_routed"], "H": cfg["num_local_experts"],
        "off": cfg["held_expert_offset"], "K": cfg["num_experts_per_tok"],
        "di": di, "n": cfg["mamba_d_state"], "p": cfg["mamba_d_head"],
        "h": di // cfg["mamba_d_head"], "w": cfg["mamba_d_conv"],
        "v": cfg["vocab_size"],
    }


def kinds(cfg) -> list:
    """The served layers' mixers, 'mamba' or 'attention', in order."""
    return cfg["layer_types"][:cfg["num_hidden_layers"]]


def period(cfg) -> int:
    """The shortest repeat of the served layer pattern: the program stacks
    one parameter tree per position in it."""
    ks, n = kinds(cfg), cfg["num_hidden_layers"]
    return next(p for p in range(1, n + 1)
                if n % p == 0 and all(k == ks[i % p] for i, k in enumerate(ks)))


def param_shapes(cfg) -> dict:
    """The program's parameter tree: ``layers/sub<i>`` for each position
    of the period, stacked over the periods on a leading axis."""
    m = _dims(cfg)
    d, P = m["d"], cfg["num_hidden_layers"] // period(cfg)
    dt = jnp.dtype(cfg["torch_dtype"])  # the type served
    conv = m["di"] + 2 * m["n"]
    mamba = {
        "in_proj": ((P, d, 2 * m["di"] + 2 * m["n"] + m["h"]), dt),
        "conv_w": ((P, m["w"], conv), dt), "conv_b": ((P, conv), dt),
        "A_log": ((P, m["h"]), F32), "D": ((P, m["h"]), F32),
        "dt_bias": ((P, m["h"]), F32), "norm": ((P, m["di"]), dt),
        "out_proj": ((P, m["di"], d), dt),
    }
    attn = {
        "wq": ((P, d, m["hq"] * m["dh"]), dt),
        "wk": ((P, d, m["hkv"] * m["dh"]), dt),
        "wv": ((P, d, m["hkv"] * m["dh"]), dt),
        "wo": ((P, m["hq"] * m["dh"], d), dt),
    }
    ffn = {
        "router": ((P, d, m["E"]), F32),
        "wg": ((P, m["H"], d, m["f"]), dt), "wu": ((P, m["H"], d, m["f"]), dt),
        "wd": ((P, m["H"], m["f"], d), dt),
        "shared": {"wg": ((P, d, m["fs"]), dt), "wu": ((P, d, m["fs"]), dt),
                   "wd": ((P, m["fs"], d), dt)},
    }
    layers = {
        f"sub{i}": {"norm1": ((P, d), dt), "norm2": ((P, d), dt),
                    "mixer": mamba if k == "mamba" else attn, "ffn": ffn}
        for i, k in enumerate(kinds(cfg)[:period(cfg)])
    }
    return {"embed": {"embedding": ((m["v"], d), dt)}, "layers": layers,
            "final_norm": ((d,), dt)}


def draw(path, shape, key):
    """One leaf from ``key``, in float32 (the caller casts)."""
    name = path[-1]
    if name in ("norm1", "norm2", "norm", "final_norm"):
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
    """Raise if the program's ``ModelConfig`` serves another model than
    the configuration file states."""
    m = _dims(cfg)
    want = {
        "family": "hybrid", "num_layers": cfg["num_hidden_layers"],
        "d_model": m["d"], "num_heads": m["hq"], "num_kv_heads": m["hkv"],
        "head_dim": m["dh"], "vocab_size": m["v"],
        "qkv_bias": cfg["attention_bias"], "qk_norm": False,
        "rope": cfg["position_embedding_type"] != "nope",
        "attn_scale": cfg["attention_multiplier"],
        "num_experts": m["E"], "experts_per_token": m["K"],
        "moe_held": m["H"], "expert_offset": m["off"], "moe_dropless": True,
        "moe_every": 1, "moe_shared_expert": True, "moe_d_ff": m["f"],
        "moe_shared_d_ff": m["fs"], "ssm_state": m["n"],
        "ssm_headdim": m["p"], "ssm_nheads": cfg["mamba_n_heads"],
        "ssm_expand": cfg["mamba_expand"], "ssm_conv": m["w"],
        "ssm_chunk": cfg["mamba_chunk_size"],
        "rmsnorm_eps": cfg["rms_norm_eps"],
        "tie_embeddings": cfg["tie_word_embeddings"],
        "embedding_multiplier": cfg["embedding_multiplier"],
        "residual_multiplier": cfg["residual_multiplier"],
        "logits_scaling": cfg["logits_scaling"],
        "param_dtype": cfg["torch_dtype"], "compute_dtype": cfg["torch_dtype"],
    }
    got = {k: getattr(mc, k) for k in want}
    if got != want:
        bad = {k: (got[k], want[k]) for k in want if got[k] != want[k]}
        raise ValueError(f"program config departs from the file: {bad}")
    mixers = ["attention" if s.mixer == "attn" else "mamba"
              for s in mc.period_spec()] * mc.num_periods
    if mixers != kinds(cfg) or any(s.ffn != "moe" for s in mc.period_spec()):
        raise ValueError(f"program layer pattern departs from the file: {mixers}")
    if cfg["mamba_n_groups"] != 1 or not cfg["mamba_conv_bias"] \
            or cfg["mamba_proj_bias"]:
        raise ValueError("the program's Mamba-2 has one group, a convolution "
                         "bias and no projection bias")


def _expert_row_flops(cfg) -> float:
    """FLOPs of one (token, expert) row through a SwiGLU expert."""
    m = _dims(cfg)
    return 2.0 * 3 * m["d"] * m["f"]


def counts(cfg, batch: int, seq: int) -> dict:
    """FLOPs and HBM bytes of one served forward: the projections, the
    convolution and the state-space recurrence of each Mamba-2 layer (an
    update and a read-out of each head's P x N state per position), the
    projections and the causal half of attention's two products, the router,
    the shared expert, the held experts at the uniform expectation of
    ``t K H / E`` rows a layer, and the unembedding at every position; bytes
    are each weight read once and the float32 logits written once.
    ``expert_rows`` (that expectation, summed over layers) and
    ``expert_row_flops`` let a reader put the measured rows in its place;
    ``expert_groups`` is the held experts summed over layers,
    ``expert_weight_bytes`` their weights, ``expert_row_bytes`` one row in
    and one row out of an expert."""
    m = _dims(cfg)
    d, t = m["d"], batch * seq
    ks = kinds(cfg)
    n_mamba, n_attn = ks.count("mamba"), ks.count("attention")
    mamba = d * (2 * m["di"] + 2 * m["n"] + m["h"]) + m["di"] * d
    conv = m["di"] + 2 * m["n"]
    attn = 2 * d * m["hq"] * m["dh"] + 2 * d * m["hkv"] * m["dh"]
    shared = 3 * d * m["fs"]
    router = d * m["E"]
    experts = m["H"] * 3 * d * m["f"]
    rows = len(ks) * t * m["K"] * m["H"] / m["E"]
    flops = (2 * t * (n_mamba * mamba + n_attn * attn
                      + len(ks) * (shared + router) + m["v"] * d)
             + n_mamba * t * (2 * m["w"] * conv + 2 * 2 * m["h"] * m["p"] * m["n"])
             + n_attn * 2 * 2 * batch * m["hq"] * m["dh"] * seq * (seq + 1) / 2
             + rows * _expert_row_flops(cfg))
    mamba_params = mamba + m["w"] * conv + conv + m["di"]
    params = (n_mamba * mamba_params + n_attn * attn
              + len(ks) * (shared + experts + 2 * d) + m["v"] * d + d)
    f32_params = len(ks) * router + n_mamba * 3 * m["h"]  # router, A_log, D, dt_bias
    size = jnp.dtype(cfg["torch_dtype"]).itemsize
    weight_bytes = size * params + 4 * f32_params
    out_bytes = 4 * t * m["v"]
    return {"flops": float(flops), "weight_bytes": float(weight_bytes),
            "output_bytes": float(out_bytes),
            "bytes": float(weight_bytes + out_bytes),
            "params": float(params + f32_params),
            "expert_rows": float(rows),
            "expert_row_flops": _expert_row_flops(cfg),
            "expert_groups": float(len(ks) * m["H"]),
            "expert_weight_bytes": float(size * len(ks) * experts),
            "expert_row_bytes": float(2 * size * d)}


def _rms(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _mamba(cfg, m, x, num):
    """The Mamba-2 mixer, its recurrence one position at a time."""
    mm = _dims(cfg)
    di, n, hp, h, w = mm["di"], mm["n"], mm["p"], mm["h"], mm["w"]
    b, s, _ = x.shape
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
    y = _rms(y, m["norm"], cfg["rms_norm_eps"])
    return num.dot("bse,ed->bsd", y, m["out_proj"])


def _attention(cfg, m, x, num):
    """Causal grouped-query attention, no position embedding, scores
    scaled by the attention multiplier."""
    mm = _dims(cfg)
    hq, hkv, dh = mm["hq"], mm["hkv"], mm["dh"]
    b, s, _ = x.shape
    q = num.dot("bsd,de->bse", x, m["wq"]).reshape(b, s, hq, dh)
    k = num.dot("bsd,de->bse", x, m["wk"]).reshape(b, s, hkv, dh)
    v = num.dot("bsd,de->bse", x, m["wv"]).reshape(b, s, hkv, dh)
    k = jnp.repeat(k, hq // hkv, axis=2)
    v = jnp.repeat(v, hq // hkv, axis=2)
    sc = num.dot("bqhd,bkhd->bhqk", q, k) * cfg["attention_multiplier"]
    sc = jnp.where(jnp.tril(jnp.ones((s, s), bool)), sc, -jnp.inf)
    o = num.dot("bhqk,bkhd->bqhd", jax.nn.softmax(sc, axis=-1), v)
    return num.dot("bse,ed->bsd", o.reshape(b, s, hq * dh), m["wo"])


def swiglu(x, wg, wu, wd, num):
    g = jax.nn.silu(num.dot("bsd,df->bsf", x, wg))
    return num.dot("bsf,fd->bsd", g * num.dot("bsd,df->bsf", x, wu), wd)


def gates(cfg, router, x, num):
    """(B, S, E) weight of each expert for each token: the softmax over the
    token's top-k router logits where the expert is picked, else zero."""
    mm = _dims(cfg)
    logits = num.dot("bsd,de->bse", x, router)
    top, idx = lax.top_k(logits, mm["K"])
    picked = jax.nn.one_hot(idx, mm["E"], dtype=F32)  # (B, S, K, E)
    return jnp.einsum("bsk,bske->bse", jax.nn.softmax(top, axis=-1), picked)


def _weighed(wts, f, x, num):
    """Each expert of ``f`` over every token, weighed by ``wts`` (B, S, n)."""
    out = 0.0
    for e in range(wts.shape[-1]):
        out = out + wts[..., e:e + 1] * swiglu(x, f["wg"][e], f["wu"][e],
                                                 f["wd"][e], num)
    return out


def routed(cfg, f, x, num, first: int, held: int):
    """What experts ``first .. first + held - 1`` add for each token;
    ``f`` holds those experts' weights, one per row of its leading axis."""
    return _weighed(gates(cfg, f["router"], x, num)[..., first:first + held],
                    f, x, num)


def _forward(cfg, p, tokens, num):
    """(last-position logits (B, V), held-expert rows per layer (L, H))."""
    mm = _dims(cfg)
    eps, r = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    per = period(cfg)
    emb = num.w(p["embed"]["embedding"])
    h = jnp.take(emb, tokens, axis=0) * cfg["embedding_multiplier"]
    loads = []
    for i, kind in enumerate(kinds(cfg)):
        lp = jax.tree_util.tree_map(lambda a: a[i // per],
                                    p["layers"][f"sub{i % per}"])
        # this layer's weights become float32 only once the last layer is done
        h, lp = lax.optimization_barrier((h, lp))
        lp = jax.tree_util.tree_map(num.w, lp)
        x = _rms(h, lp["norm1"], eps)
        mix = _mamba if kind == "mamba" else _attention
        h = h + r * mix(cfg, lp["mixer"], x, num)
        x = _rms(h, lp["norm2"], eps)
        f, sh = lp["ffn"], lp["ffn"]["shared"]
        wts = gates(cfg, f["router"], x, num)[..., mm["off"]:mm["off"] + mm["H"]]
        y = _weighed(wts, f, x, num) + swiglu(x, sh["wg"], sh["wu"], sh["wd"], num)
        h = h + r * y
        loads.append((wts > 0).sum((0, 1)))
    x = _rms(h[:, -1], num.w(p["final_norm"]), eps)
    return (num.dot("bd,vd->bv", x, emb) / cfg["logits_scaling"],
            jnp.stack(loads))


def last_logits(cfg, p, tokens, num):
    """Logits of the last position, (B, V) float32. ``num`` supplies the
    arithmetic: ``num.w`` (a weight as used), ``num.dot`` (a product)."""
    return _forward(cfg, p, tokens, num)[0]


def held_loads(cfg, p, tokens, num):
    """(L, H): how many (token, expert) rows each held expert takes in each
    layer, by the reference's own routing."""
    return _forward(cfg, p, tokens, num)[1]
