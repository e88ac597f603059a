#!/usr/bin/env python3
"""Serve qwen2.5-3b at its published width through ``Gateway`` on the TPU.

Usage:
  python chip_smoke.py            # one chip: a cold invocation, three warm
                                  # ones, then a small Poisson replay
  python chip_smoke.py --chips 4  # four nodes behind one Gateway, each on
                                  # its own chip, two or more requests each

The weights are random, made from ``--seed``. The script checks that no
invocation failed, that warm invocations share the resident read-only
weights, that those weights live on each node's own device, and that every
returned logit matches a plain ``jax.jit(forward)`` on the same parameters,
run outside the runtime. It prints the device first, then the stage spans
of this one run (a smoke run, not a benchmark), and as its last line
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
It exits non-zero when JAX finds no TPU or when any check fails.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "qwen2.5-3b"
FN = "qwen"
STAGES = ("container_create", "cpu_ctx", "gpu_ctx", "gpu_data", "compute")


def device_info() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _check(ok: bool, what) -> None:
    """A smoke check; unlike ``assert`` it also runs under ``python -O``."""
    if not ok:
        raise AssertionError(what)


def _log_record(log, label: str, rec) -> None:
    spans = " ".join(f"{s}={rec.stages.get(s, 0.0) * 1e3:.3f}ms"
                     for s in STAGES)
    log(f"  {label:<8} node={rec.node_id} warm_stage={rec.warm_stage} "
        f"e2e={rec.e2e * 1e3:.3f}ms {spans}")


def serve(n_nodes: int = 1, *, full_width: bool = True, warm: int = 3,
          replay: int = 8, per_node: int = 2, seed: int = 0,
          log=print) -> dict:
    """Drive ``Gateway(backend="runtime", policy="sage")`` through one
    function and check what it returns. One node: a cold invocation,
    ``warm`` warm ones and a ``replay``-request Poisson replay. Several
    nodes: invocations until every node has served ``per_node``. Raises
    ``AssertionError`` on a failed check; returns a summary."""
    import jax
    import numpy as np

    from repro.api import FunctionSpec, Gateway, PoissonWorkload
    from repro.core.daemon import Tier
    from repro.core.functions import model_config, served_logits

    cfg = model_config(ARCH, full_width)
    spec = FunctionSpec(name=FN, arch=ARCH, full_width=full_width, seed=seed)
    log(f"model {cfg.name}: {cfg.param_count() / 1e9:.3f} B params, "
        f"{cfg.param_dtype}, {cfg.num_layers} layers, d_model {cfg.d_model}, "
        f"vocab {cfg.vocab_size}")
    # time_scale=0: the runtime's modeled sleeps (broker transfers,
    # container and CPU-context stages) take no time; what is left is
    # the compile, the device_put to HBM and the forward pass
    with Gateway(backend="runtime", policy="sage", n_nodes=n_nodes,
                 time_scale=0.0, seed=seed) as gw:
        nodes = getattr(gw.runtime, "nodes", [gw.runtime])
        by_id = {n.node_id: n for n in nodes}
        t0 = time.perf_counter()
        gw.register(spec)
        log(f"register (host weights + per-node setup): "
            f"{time.perf_counter() - t0:.3f}s")
        log(f"smoke run, {n_nodes} node(s); one run's spans, not a "
            f"benchmark:")
        records = []
        if n_nodes == 1:
            records.append(gw.invoke(FN, seed=seed))
            _log_record(log, "cold", records[-1])
            for i in range(warm):
                records.append(gw.invoke(FN, seed=seed + 1 + i))
                _log_record(log, "warm", records[-1])
            tel = gw.replay(PoissonWorkload(FN, rate_per_s=4.0,
                                            duration_s=60.0, seed=seed,
                                            max_events=replay),
                            seed=seed + 1000)
            done = {r.request_id for r in records}
            for rec in tel.snapshot():
                if rec.request_id not in done:
                    records.append(rec)
                    _log_record(log, "replay", rec)
            _check(len(records) == 1 + warm + replay, len(records))
        else:
            served = {n.node_id: 0 for n in nodes}
            while min(served.values()) < per_node:
                _check(len(records) < 16 * n_nodes * per_node, served)
                records.append(gw.invoke(FN, seed=seed + len(records)))
                served[records[-1].node_id] += 1
                _log_record(log, "invoke", records[-1])

        errors = [r.error for r in records if r.error is not None]
        _check(not errors, errors)
        _check(records[0].warm_stage is None, "first invocation was not cold")
        if n_nodes == 1:
            _check(all(r.warm_stage is not None for r in records[1:warm + 1]),
                   "a warm invocation was cold")

        # each node: warm invocations attach to the resident weights, which
        # live on that node's own device, and its context computes there
        devices = [n.device for n in nodes]
        _check(len(set(devices)) == min(n_nodes, len(jax.devices())), devices)
        weights_bytes = 0
        for node in nodes:
            count = sum(r.node_id == node.node_id for r in records)
            hits = node.daemon.stats["shared_hits"]
            _check(hits >= count - 1, (node.node_id, hits, count))
            entry = next(e for e in node.daemon.function_entries(FN)
                         if e.read_only)
            _check(entry.tier is Tier.DEVICE, entry.tier)
            leaves = jax.tree_util.tree_leaves(entry.dev_obj)
            on = {d for x in leaves for d in x.devices()}
            _check(on == {node.device}, (node.node_id, on))
            weights_bytes = sum(x.nbytes for x in leaves)
            ctx = node.engines[FN].instances[0].gpu_ctx
            out_on = {d for s in jax.tree_util.tree_leaves(ctx.output_shardings)
                      for d in s.device_set}
            _check(out_on == {node.device}, (node.node_id, out_on))
            log(f"node {node.node_id}: {count} invocations, shared_hits="
                f"{hits}, weights {weights_bytes / 2**30:.3f} GiB resident "
                f"on {node.device}, outputs on {out_on.pop()}")

        # reference: a plain jit of the same forward on the same weights,
        # on the first device, outside the runtime
        dev = jax.devices()[0]
        params = nodes[0].db.fetch(f"{FN}/weights")
        ref_params = jax.device_put(params, dev)
        ref = jax.jit(served_logits(cfg))
        worst = 0.0
        for rec in records:
            db = by_id[rec.node_id].db
            tokens = db.fetch(f"{FN}/in/{rec.request_id}")
            got = db.fetch(rec.result)
            want = np.asarray(ref(ref_params, jax.device_put(tokens, dev))
                              [:, -1, :8])
            _check(np.isfinite(got).all(), got)
            np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-3)
            worst = max(worst, float(np.max(np.abs(got - want))))
        del ref_params
        log(f"reference check: {len(records)} outputs of shape {got.shape}, "
            f"max |runtime - jit(forward)| = {worst!r}")
    return {"records": len(records), "max_abs_diff": worst,
            "weights_bytes": weights_bytes}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the four-node phase, one node per chip")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.launch.cache import enable_compile_cache

    info = device_info()
    print(json.dumps({"device": info}), flush=True)
    if info["platform"] != "tpu":
        print(f"no TPU: JAX found {info['platform']!r}", file=sys.stderr)
        return 1
    if info["count"] < args.chips:
        print(f"--chips {args.chips} needs {args.chips} chips, found "
              f"{info['count']}", file=sys.stderr)
        return 1
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    serve(args.chips, seed=args.seed,
          log=lambda s: print(s, flush=True))
    print(json.dumps({"ok": True, "device": info}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
