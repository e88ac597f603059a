"""Tail-tolerance benchmark: gray failures, hedging & quarantine
(docs/resilience.md, "Gray failures").

One seeded gray-fault trace — a :class:`SlowNode` dragging every stage of
one node, heavy-tailed :class:`LoaderJitter` on the tight class, and a
:class:`MemoryLeak` creeping up a second node — is replayed against a
mixed tight/loose workload twice per driver:

* **baseline**: eviction on (the PR-7 hardened config) but no
  tail-tolerance — dispatch keeps feeding the slow-but-alive node and the
  tight class's p99 rides the straggler;
* **tail-tolerant**: the same config plus ``hedging=True`` and
  ``quarantine=True`` — straggling invocations launch one speculative
  twin on the best non-suspect node (first completion wins, the loser is
  cancelled byte-exactly), and the sustained suspect is drained, probed
  with canaries, and readmitted or retired.

The headline is the tight-class p99: the tail-tolerant config must
STRICTLY beat the baseline on BOTH drivers with the identical fault
schedule from the same seed. ``python -m benchmarks.tail_tolerance``
prints both tables and exits non-zero if the gate or the zero-leak
accounting check fails.
"""
from __future__ import annotations

import sys
from typing import Dict, Optional, Tuple

from repro.api.gateway import Gateway
from repro.api.spec import FunctionSpec
from repro.api.workload import ChaosWorkload
from repro.core.faults import FaultPlan, LoaderJitter, MemoryLeak, SlowNode
from repro.core.profiles import FunctionProfile
from repro.core.simulator import SimFunction, Simulator

DEFAULT_SEED = 31
N_NODES = 3

# {function: (rate_per_s, deadline_s, priority)} — the tight class is the
# one the tail-tolerance layer protects; loose rides along to keep the
# fleet median honest (a one-class trace would let the straggler drag
# the baseline it is judged against)
CLASSES: Dict[str, Tuple[float, Optional[float], int]] = {
    "tight": (6.0, 0.5, 2),
    "loose": (4.0, 5.0, 0),
}


def tail_plan(duration_s: float, factor: float,
              seed: int = DEFAULT_SEED) -> FaultPlan:
    """The seeded gray-fault schedule, scaled to the workload duration:
    gpu1 turns gray-slow early and stays slow, the tight class's loads
    pick up a Pareto-tailed jitter mid-window, and gpu2 leaks device
    memory over a bounded window (reclaimed at leak_off — the accounting
    asserts below check the books balance)."""
    d = duration_s
    return FaultPlan([
        SlowNode("gpu1", at_s=0.15 * d, factor=factor),
        LoaderJitter("tight", scale_s=0.05, alpha=1.5,
                     start_s=0.40 * d, end_s=0.70 * d),
        MemoryLeak("gpu2", at_s=0.30 * d, rate_bps=2 << 20,
                   duration_s=0.25 * d),
    ], seed=seed)


def _summary(t, stats) -> Dict[str, object]:
    recs = [r for r in t.snapshot() if not r.dropped]
    hedged = [r for r in t.snapshot()
              if r.dropped and r.error_class == "hedged"]
    return {
        "arrivals": len(recs),
        "completed": sum(1 for r in recs if r.error is None),
        "tight_p99": round(t.p99_duration("tight"), 4),
        "loose_p99": round(t.p99_duration("loose"), 4),
        "hedged_drops": len(hedged),
        "resilience": {k: v for k, v in stats.items()
                       if k in ("hedges_launched", "hedges_won",
                                "hedges_wasted", "quarantines", "readmits",
                                "redispatches")},
    }


# ----------------------------------------------------------------------
# drivers
# ----------------------------------------------------------------------
def run_sim(tolerant: bool, quick: bool = False,
            seed: int = DEFAULT_SEED) -> Dict[str, object]:
    duration = 40.0 if quick else 120.0
    kw: Dict[str, object] = {"faults": tail_plan(duration, 10.0, seed),
                             "eviction": True, "dispatch": "random"}
    if tolerant:
        kw.update(hedging=True, quarantine=True)
    sim = Simulator("sage", n_nodes=N_NODES, seed=seed, **kw)
    for name in sorted(CLASSES):
        sim.register(SimFunction(FunctionProfile(
            name, "tail", context_mb=64.0, read_only_mb=24.0,
            writable_mb=4.0, compute_ms=15.0)))
    wl = ChaosWorkload(CLASSES, duration, seed=seed)
    for i, a in enumerate(wl.events()):
        sim.submit(a.function, a.t, deadline_s=a.deadline_s,
                   priority=a.priority, request_id=f"t{i}-{a.function}")
    sim.run(duration + 120.0)
    out = _summary(sim.telemetry, sim.resilience_stats())
    # accounting must be exact after every hedge cancel/quarantine drain
    for n in sim.nodes:
        assert 0 <= n.used <= n.capacity and n.host_used >= 0, (
            f"{n.name}: used={n.used} host_used={n.host_used}")
        assert n.inflight_loads == 0, f"{n.name} leaked loader slots"
    return out


def run_runtime(tolerant: bool, quick: bool = False,
                seed: int = DEFAULT_SEED) -> Dict[str, object]:
    duration = 8.0 if quick else 15.0
    # the threaded runtime serves invocations concurrently (no queueing
    # on a slow node), so the straggler needs a harder factor than the
    # sim's to dominate the tail the same way
    kw: Dict[str, object] = {"faults": tail_plan(duration, 30.0, seed),
                             "eviction": True, "dispatch": "random"}
    if tolerant:
        # eager hedge thresholds: the wall-clock window is short, so the
        # estimate must arm before quarantine already drained the suspect
        kw.update(hedging=dict(min_samples=6, hedge_quantile=0.9),
                  quarantine=True)
    gw = Gateway(backend="runtime", policy="sage", n_nodes=N_NODES,
                 seed=seed, **kw)
    try:
        for name in sorted(CLASSES):
            gw.register(FunctionSpec(
                name=name, read_only_bytes=24 << 20, writable_bytes=4 << 20,
                context_bytes=16 << 20, compute_ms=10.0))
        # rates scale up as the window scales down: same arrival-count
        # intent as the sim scenario, wall-clock kept benchmark-friendly
        scale = 120.0 / duration / 4.0
        classes = {f: (r * scale, dl, pr)
                   for f, (r, dl, pr) in CLASSES.items()}
        wl = ChaosWorkload(classes, duration, seed=seed)
        t = gw.replay(wl, pace=1.0, timeout=120.0)
        out = _summary(t, gw.resilience_stats())
        for n in gw._nodes:
            mu = n.memory_usage()
            assert all(v >= 0 for v in mu.values()), f"{n.node_id}: {mu}"
            assert n.daemon.leaked_bytes == 0, (
                f"{n.node_id} kept {n.daemon.leaked_bytes} leaked bytes "
                "after leak_off reclaim")
        return out
    finally:
        gw.shutdown()


# ----------------------------------------------------------------------
# entry points
# ----------------------------------------------------------------------
def bench_section(quick: bool = False) -> Dict[str, object]:
    """The ``tail`` section of BENCH_*.json: the sim driver's baseline vs
    tail-tolerant tight-class p99 under the seeded gray-fault trace (the
    runtime driver is covered by the CI tail smoke, not the artifact)."""
    baseline = run_sim(False, quick)
    tolerant = run_sim(True, quick)
    ratio = (baseline["tight_p99"] / tolerant["tight_p99"]
             if tolerant["tight_p99"] else float("inf"))
    return {
        "seed": DEFAULT_SEED,
        "baseline": baseline,
        "tolerant": tolerant,
        "tight_p99_ratio": round(ratio, 3),
        "beats": tolerant["tight_p99"] < baseline["tight_p99"],
    }


def run(quick: bool = True):
    """CSV-harness adapter (benchmarks/run.py): one row per config."""
    from benchmarks.common import Row

    for label, tolerant in (("baseline", False), ("tolerant", True)):
        r = run_sim(tolerant, quick)
        res = r["resilience"]
        yield Row(f"tail/sim_{label}", 0.0,
                  f"tight_p99={r['tight_p99']};completed={r['completed']};"
                  f"hedges={res['hedges_launched']};"
                  f"quarantines={res['quarantines']}")


def main(quick: bool = False) -> int:
    ok = True
    for driver, fn in (("sim", run_sim), ("runtime", run_runtime)):
        baseline = fn(False, quick)
        tolerant = fn(True, quick)
        beats = tolerant["tight_p99"] < baseline["tight_p99"]
        launched = tolerant["resilience"]["hedges_launched"]
        status = "PASS" if beats and launched > 0 else "FAIL"
        ok &= beats and launched > 0
        print(f"[{driver}] baseline tight p99={baseline['tight_p99']}s "
              f"tolerant tight p99={tolerant['tight_p99']}s -> {status}")
        print(f"  baseline : {baseline['resilience']}")
        print(f"  tolerant : {tolerant['resilience']} "
              f"hedged_drops={tolerant['hedged_drops']}")
    return 0 if ok else 1


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    sys.exit(main(quick="--quick" in sys.argv))
