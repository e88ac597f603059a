"""Paper Fig 17 (§7.8): 4-node cluster, random dispatch — SAGE's node-level
gains survive cluster scheduling.

Extended (docs/cluster.md): sharing-aware dispatch. The same contended
multi-function trace is replayed under ``dispatch="random"`` and
``dispatch="locality"`` on BOTH backends; locality routes repeat traffic to
the node where the function's read-only data already sits, so it must
strictly beat random on p50 invocation duration AND total ``bytes_loaded``
at a fixed node count (asserted in tests/test_dispatch.py, reported here).
"""
from __future__ import annotations

import time
from typing import Dict

import numpy as np

from benchmarks.common import NAMES, Row, data_plane_function, replay
from repro.api import FunctionSpec, Gateway, MAFWorkload, TraceWorkload
from repro.core.profiles import MB


def run_fig17(quick: bool = True):
    # 4x the single-node load over 4 nodes
    workload = MAFWorkload(NAMES, 600.0, seed=7, mean_rpm=100)
    stats = {}
    for system in ("fixedgsl", "dgsf", "sage"):
        gw = replay(system, workload, n_nodes=4, until_pad=6000.0)
        inwin = sum(1 for r in gw.telemetry.records if r.end_t <= 600.0)
        stats[system] = (gw.telemetry.mean_e2e(), inwin / 600.0)
    e2e = {s: v[0] for s, v in stats.items()}
    thr = {s: v[1] for s, v in stats.items()}
    return [
        Row("fig17_4node_sage_vs_fixedgsl", e2e["sage"] * 1e6,
            f"speedup={e2e['fixedgsl']/e2e['sage']:.1f}x (paper: 207.1x)"),
        Row("fig17_4node_sage_vs_dgsf", e2e["sage"] * 1e6,
            f"speedup={e2e['dgsf']/e2e['sage']:.1f}x (paper: 12.5x)"),
        Row("fig17_4node_throughput_vs_fixedgsl", 1e6 / max(thr["sage"], 1e-9),
            f"ratio={thr['sage']/max(thr['fixedgsl'],1e-9):.2f}x (paper: 10.3x)"),
    ]


# ---------------------------------------------------------------------------
# random vs locality dispatch (both backends)
# ---------------------------------------------------------------------------

def _dispatch_trace(n_fns: int, repeats: int, *, gap_s: float = 4.0,
                    stagger_s: float = 0.05) -> TraceWorkload:
    """``repeats`` rounds of all ``n_fns`` functions, rounds close enough
    that warm state survives between them (contended: every round lands the
    whole function set on the loader pools at once)."""
    return TraceWorkload([
        (r * gap_s + i * stagger_s, f"fn{i}")
        for r in range(repeats) for i in range(n_fns)
    ])


def dispatch_comparison_sim(policy: str, *, n_fns: int = 8, repeats: int = 6,
                            n_nodes: int = 4, seed: int = 5) -> Dict[str, float]:
    """Replay the contended multi-function trace on the virtual-time twin
    under ``policy``; returns p50 duration / total db bytes / hit rate."""
    gw = Gateway(backend="sim", policy="sage", n_nodes=n_nodes,
                 dispatch=policy, loader_threads=2, seed=seed)
    for i in range(n_fns):
        gw.register(FunctionSpec(
            name=f"fn{i}", read_only_bytes=96 * MB, writable_bytes=8 * MB,
            context_bytes=64 * MB, compute_ms=20.0))
    tel = gw.replay(_dispatch_trace(n_fns, repeats), until_pad=600.0)
    assert tel.error_count() == 0, tel.errors()[0].error
    return {
        "p50_duration": tel.p50_duration(),
        "bytes_loaded": float(sum(n.bytes_loaded for n in gw.sim.nodes)),
        "hit_rate": tel.dispatch_hit_rate(),
        "n": float(len(tel.records)),
    }


def dispatch_comparison_runtime(policy: str, *, n_fns: int = 6,
                                repeats: int = 5, n_nodes: int = 4,
                                seed: int = 5, ro_mb: int = 24,
                                stagger_s: float = 0.02) -> Dict[str, float]:
    """The same shape on the REAL threaded cluster: synthetic functions
    (no jit compile — the comparison is about the data plane) whose handler
    waits on the daemon-prepared handles, one shared database."""
    from repro.core.request import Data, DataType, Request
    from repro.core.runtime import ClusterRuntime
    from repro.data.database import Database

    db = Database()
    cluster = ClusterRuntime(n_nodes=n_nodes, seed=seed, dispatch=policy,
                             database=db, loader_threads=2,
                             serialize_compute=False)
    cluster.sage_init()
    names = [f"fn{i}" for i in range(n_fns)]
    for name in names:
        db.put(f"{name}/weights", np.zeros(1, np.uint8), size=ro_mb * MB)
        cluster.register_function(
            lambda i, name=name: data_plane_function(name))

    try:
        futs = []
        for r in range(repeats):
            for name in names:
                req = Request(function_name=name)
                wkey = f"{name}/in/{r}"
                db.put(wkey, np.zeros(1, np.uint8), size=2 * MB)
                req.in_data = [
                    Data(key=f"{name}/weights", size=ro_mb * MB,
                         dtype=DataType.READ_ONLY),
                    Data(key=wkey, size=2 * MB, dtype=DataType.WRITABLE),
                ]
                futs.append(cluster.submit(req))
                # small stagger so residency from the previous submits is
                # visible to the next dispatch decision (open-loop-ish trace)
                time.sleep(stagger_s)
        for f in futs:
            f.result(timeout=120)
        tel = cluster.telemetry
        out = {
            "p50_duration": tel.p50_duration(),
            "bytes_loaded": float(sum(n.daemon.stats["bytes_loaded"]
                                      for n in cluster.nodes)),
            "hit_rate": tel.dispatch_hit_rate(),
            "n": float(len(tel.records)),
        }
        assert tel.error_count() == 0, tel.errors()[0].error
        return out
    finally:
        cluster.shutdown()


def run_dispatch(quick: bool = True):
    rows = []
    for backend, compare in (("sim", dispatch_comparison_sim),
                             ("runtime", dispatch_comparison_runtime)):
        res = {p: compare(p) for p in ("random", "locality")}
        rnd, loc = res["random"], res["locality"]
        rows.append(Row(
            f"dispatch_{backend}_p50_random", rnd["p50_duration"] * 1e6,
            f"hit_rate={rnd['hit_rate']:.2f};n={int(rnd['n'])}"))
        rows.append(Row(
            f"dispatch_{backend}_p50_locality", loc["p50_duration"] * 1e6,
            f"hit_rate={loc['hit_rate']:.2f};"
            f"speedup={rnd['p50_duration']/max(loc['p50_duration'],1e-9):.1f}x"))
        rows.append(Row(
            f"dispatch_{backend}_bytes_saved_pct",
            (1.0 - loc["bytes_loaded"] / max(rnd["bytes_loaded"], 1.0)) * 100.0,
            f"random={rnd['bytes_loaded']/MB:.0f}MB;"
            f"locality={loc['bytes_loaded']/MB:.0f}MB"))
    return rows


def run(quick: bool = True):
    return run_fig17(quick) + run_dispatch(quick)


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    for r in run():
        r.print()
