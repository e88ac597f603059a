"""granite-4.0-h-small's one-chip share served at the program's reduced
width on the CPU, against the family's plain reference: through
``make_model_function`` and ``Gateway`` with a hot expert that a capacity
layer would overflow, the expert counters against the reference's own
routing, four shares of the experts against the uncut layer, and each of
the mechanisms the configuration adds (NoPE, the attention scale, the muP
multipliers) shown by a case in which the program leaves it out."""
import dataclasses
import json
import math
from dataclasses import dataclass, field
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _paths
import harness
import reference
import weights

SEED = 2**31 + 917
with open(_paths.DATA / "granite-tiny.json") as _f:
    CFG = json.load(_f)
FAM = harness.load_module(_paths.BENCH / "families" / "granitemoehybrid.py")
LIMIT = CFG["logit_err_limit"]  # float32 against float32


def _program():
    from repro.core.functions import model_config

    return model_config(CFG["program_arch"], CFG["full_width"])


def _weights(hot: bool):
    """The family's draw; ``hot``: every token's embedding shares a
    direction that the router favours for held expert 0, as a trained
    router's popular expert."""
    (tree,) = weights.make(FAM.param_shapes(CFG), FAM.draw, FAM.DISTINCT_LEAF,
                           SEED, 1)
    if hot:
        tree["embed"]["embedding"] = tree["embed"]["embedding"] + 0.02
        for sub in tree["layers"].values():
            r = np.array(sub["ffn"]["router"])
            r[..., 0] = np.abs(r[..., 0])
            sub["ffn"]["router"] = r
    return tree


def _tokens(seed):  # the program's make_request draw for ``seed``
    return np.random.default_rng(seed).integers(0, 256, (1, 256), dtype=np.int32)


def _reference(tree, seeds):
    rows = reference.last_rows(FAM, CFG, [tree], [_tokens(s) for s in seeds],
                               [0] * len(seeds), control=False, block=1,
                               device=jax.devices()[0])
    loads = [np.asarray(FAM.held_loads(CFG, tree, _tokens(s), reference.Num()))
             for s in seeds]
    return rows, loads


def _serve(tree, seeds):
    """Records and returned logits of ``seeds`` through Gateway."""
    from repro.api import FunctionSpec, Gateway

    @dataclass(frozen=True)
    class Spec(FunctionSpec):
        weights_tree: Any = field(default=None, compare=False, repr=False)

        def host_params(self):
            return self.weights_tree

    with Gateway(backend="runtime", policy="sage", time_scale=0.0) as gw:
        gw.register(Spec(name="g", arch=CFG["program_arch"],
                         full_width=CFG["full_width"], batch=1, seq=256,
                         weights_tree=tree))
        recs = [gw.invoke("g", seed=s) for s in seeds]
        db = getattr(gw.runtime, "nodes", [gw.runtime])[0].db
        got = [np.asarray(db.fetch(r.result)) for r in recs]
    return recs, got


@pytest.fixture(scope="module")
def served_hot():
    tree = _weights(hot=True)
    seeds = [11, 12]
    recs, got = _serve(tree, seeds)
    rows, loads = _reference(tree, seeds)
    return recs, got, rows, loads


def test_served_share_matches_the_reference_where_capacity_would_drop(served_hot):
    recs, got, rows, loads = served_hot
    mc = _program()
    # what the capacity layer would keep of one expert for a batch row
    cap = max(mc.experts_per_token, math.ceil(
        mc.experts_per_token * 256 * mc.moe_capacity_factor / mc.num_experts))
    assert all(l.max() > cap for l in loads), [l.max() for l in loads]
    assert all(r.error is None for r in recs)
    assert reference.logit_err([g[0] for g in got], rows) <= LIMIT


def test_counters_equal_the_reference_routing(served_hot):
    recs, _, _, loads = served_hot
    for r, l in zip(recs, loads):
        assert (r.expert_rows, r.expert_rows_max) == (int(l.sum()), int(l.max()))


def test_four_shares_and_the_shared_expert_add_up_to_the_uncut_layer():
    from repro.models.layers import mlp_forward, moe_dropless_forward

    mc = _program()
    held, E = mc.moe_held, mc.num_experts
    (tree,) = weights.make(FAM.param_shapes(CFG), FAM.draw, FAM.DISTINCT_LEAF,
                           SEED, 1)
    f = jax.tree_util.tree_map(lambda a: jnp.asarray(a[0]),
                               tree["layers"]["sub0"]["ffn"])
    k = jax.random.split(jax.random.PRNGKey(3), 4)
    full = {n: jax.random.uniform(k[i], (E,) + f[n].shape[1:], jnp.float32,
                                  -0.1, 0.1) for i, n in enumerate(("wg", "wu", "wd"))}
    x = jax.random.normal(k[3], (1, 256, mc.d_model), jnp.float32)
    total = 0.0
    for r in range(E // held):
        share = dataclasses.replace(mc, expert_offset=r * held)
        part = {**f, **{n: w[r * held:(r + 1) * held] for n, w in full.items()}}
        total = total + moe_dropless_forward(share, part, x)[0]
    shared = mlp_forward(mc, f["shared"], x)
    got = total - (E // held - 1) * shared
    num = reference.Num()
    uncut = FAM.routed({**CFG, "num_local_experts": E}, {**f, **full}, x, num,
                       0, E)
    sh = f["shared"]
    uncut = uncut + FAM.swiglu(x, sh["wg"], sh["wu"], sh["wd"], num)
    np.testing.assert_allclose(np.asarray(got), np.asarray(uncut),
                               rtol=1e-4, atol=1e-5)


@pytest.fixture(scope="module")
def sharp_attention():
    """Weights whose attention scores are large enough for positions to
    matter, and the reference's logits of one request."""
    tree = _weights(hot=False)
    for sub in tree["layers"].values():
        if "wq" in sub["mixer"]:
            sub["mixer"]["wq"] = sub["mixer"]["wq"] * 8
            sub["mixer"]["wk"] = sub["mixer"]["wk"] * 8
    rows, _ = _reference(tree, [21])
    return tree, rows


@pytest.mark.parametrize("dropped", [
    None, {"rope": True}, {"attn_scale": 0.0}, {"embedding_multiplier": 1.0},
    {"residual_multiplier": 1.0}, {"logits_scaling": 1.0}])
def test_each_mechanism_shows_in_the_logits(sharp_attention, dropped):
    """The program as configured is within the limit; the program with one
    mechanism dropped is not."""
    from repro.core.functions import served_logits

    tree, rows = sharp_attention
    mc = dataclasses.replace(_program(), **(dropped or {}))
    logits, _ = jax.jit(served_logits(mc))(tree, _tokens(21))
    err = reference.logit_err([np.asarray(logits[0, -1, :8])], rows)
    assert (err <= LIMIT) == (dropped is None), err


def test_counts_of_the_one_chip_share():
    with open(_paths.BENCH / "configs" / "granite-4.0-h-small.json") as f:
        cfg = json.load(f)
    c = FAM.counts(cfg, 1, 256)
    layout = sum(int(np.prod(s)) * d.itemsize
                 for _, s, d in weights.leaves(FAM.param_shapes(cfg)))
    assert c["weight_bytes"] == layout
    assert c["params"] == pytest.approx(3.264e9, rel=1e-3)
    # 256 x 10 x 18 / 72 = 640 rows a layer through the held experts
    assert c["expert_rows"] == 640 * 10 and c["expert_groups"] == 180
    assert c["expert_weight_bytes"] == pytest.approx(3.397e9, rel=1e-3)
    # bound by bytes at the v5e's peaks: 8.1 ms against 4.7 ms of FLOPs
    assert c["bytes"] / 819e9 == pytest.approx(8.10e-3, rel=0.01)
    assert c["flops"] / 197e12 == pytest.approx(4.73e-3, rel=0.01)
