"""Chaos benchmark: one seeded fault trace, two resilience configs,
both drivers (docs/resilience.md).

A :func:`chaos_plan` schedule — three node crashes (one restarting), a
db-bandwidth brownout, a short db flap, and a 50%-poisoned loader for the
``flaky`` function — is replayed against a mixed-priority
:class:`~repro.api.workload.ChaosWorkload` twice per driver:

* **naive**: faults on, control layer off (`eviction`/`breaker`/
  `shedding` all default) — dispatch keeps feeding dead nodes and every
  in-flight invocation on a crashed node is a hard loss;
* **hardened**: eviction drains crashed nodes, crash-lost invocations
  re-dispatch within their retry budget, the ``flaky`` breaker cuts
  doomed loads, and watermark shedding sacrifices the loose class first.

The headline is the goodput ratio: the hardened config must hold >= 2x
the naive goodput on BOTH drivers, with the *identical* fault schedule
from the same seed (tests/test_faults.py and the CI chaos smoke assert
this). ``python -m benchmarks.chaos`` prints both tables and exits
non-zero if the ratio or the zero-leak accounting check fails.
"""
from __future__ import annotations

import sys
from typing import Dict, Optional, Tuple

from repro.api.gateway import Gateway
from repro.api.spec import FunctionSpec
from repro.api.workload import ChaosWorkload
from repro.core.faults import (
    BreakerConfig,
    DbFlap,
    FaultPlan,
    LinkDegradation,
    LoaderFault,
    NodeCrash,
    SheddingConfig,
)
from repro.core.profiles import FunctionProfile
from repro.core.simulator import SimFunction, Simulator

DEFAULT_SEED = 29
N_NODES = 4

# hardened-config control knobs (docs/resilience.md has the reference)
BREAKER = BreakerConfig(failure_threshold=0.5, window=16, min_requests=8,
                        cooldown_s=5.0, half_open_probes=2)
SHEDDING = SheddingConfig(watermark=0.75, hard_watermark=0.97,
                          loose_priority_max=0, saturation=8.0)

# {function: (rate_per_s, deadline_s, priority)} — the tight class is what
# the control layer protects; flaky carries no deadline so its poisoned
# loads burn capacity without moving goodput directly
CLASSES: Dict[str, Tuple[float, Optional[float], int]] = {
    "tight": (6.0, 3.0, 2),
    "loose": (6.0, 20.0, 0),
    "flaky": (1.0, None, 0),
}


def chaos_plan(duration_s: float, seed: int = DEFAULT_SEED) -> FaultPlan:
    """The seeded fault schedule, scaled to the workload duration: 3 of 4
    nodes crash early (gpu1 rejoins near the end), the db link browns out
    mid-window, gpu0's db flaps briefly at warmup, and the ``flaky``
    function's db leg fails half the time."""
    d = duration_s
    return FaultPlan([
        NodeCrash("gpu1", at_s=0.08 * d, restart_after_s=0.87 * d),
        NodeCrash("gpu2", at_s=0.10 * d),
        NodeCrash("gpu3", at_s=0.12 * d),
        LoaderFault("flaky", probability=0.5),
        LinkDegradation(at_s=0.30 * d, duration_s=0.20 * d, factor=0.5,
                        link="db"),
        DbFlap(at_s=0.02 * d, duration_s=0.02 * d, node="gpu0"),
    ], seed=seed)


def _workload(duration_s: float, seed: int = DEFAULT_SEED) -> ChaosWorkload:
    return ChaosWorkload(CLASSES, duration_s, seed=seed)


def _summary(t, stats) -> Dict[str, object]:
    recs = [r for r in t.snapshot() if not r.dropped]
    return {
        "arrivals": len(recs),
        "completed": sum(1 for r in recs if r.error is None),
        "goodput": round(1.0 - t.slo_miss_rate(), 4),
        "error_counts": t.error_counts(),
        "slo_by_priority": {p: round(c["attainment"], 4)
                            for p, c in sorted(t.slo_by_priority().items())},
        "resilience": stats,
    }


# ----------------------------------------------------------------------
# drivers
# ----------------------------------------------------------------------
def run_sim(hardened: bool, quick: bool = False,
            seed: int = DEFAULT_SEED) -> Dict[str, object]:
    duration = 40.0 if quick else 120.0
    kw: Dict[str, object] = {"faults": chaos_plan(duration, seed)}
    if hardened:
        kw.update(eviction=True, breaker=BREAKER, shedding=SHEDDING)
    sim = Simulator("sage", n_nodes=N_NODES, seed=seed, **kw)
    for name, (_, _, _) in sorted(CLASSES.items()):
        sim.register(SimFunction(FunctionProfile(
            name, "chaos", context_mb=414.0, read_only_mb=96.0,
            writable_mb=8.0, compute_ms=15.0)))
    for i, a in enumerate(_workload(duration, seed).events()):
        sim.submit(a.function, a.t, deadline_s=a.deadline_s,
                   priority=a.priority, request_id=f"c{i}-{a.function}")
    sim.run(duration + 120.0)
    out = _summary(sim.telemetry, sim.resilience_stats())
    # accounting must be exact after every crash/evict/redispatch
    for n in sim.nodes:
        assert 0 <= n.used <= n.capacity and n.host_used >= 0, (
            f"{n.name}: used={n.used} host_used={n.host_used}")
        assert n.inflight_loads == 0, f"{n.name} leaked loader slots"
    return out


def run_runtime(hardened: bool, quick: bool = False,
                seed: int = DEFAULT_SEED) -> Dict[str, object]:
    duration = 5.0 if quick else 8.0
    kw: Dict[str, object] = {"faults": chaos_plan(duration, seed)}
    if hardened:
        kw.update(eviction=True, breaker=BREAKER, shedding=SHEDDING)
    gw = Gateway(backend="runtime", n_nodes=N_NODES, seed=seed, **kw)
    try:
        for name in sorted(CLASSES):
            gw.register(FunctionSpec(
                name=name, read_only_bytes=24 << 20, writable_bytes=4 << 20,
                context_bytes=16 << 20, compute_ms=10.0))
        # rates scale up as the window scales down: same arrival count
        # intent as the sim scenario, wall-clock kept benchmark-friendly
        scale = 120.0 / duration / 10.0
        classes = {f: (r * scale, dl, pr)
                   for f, (r, dl, pr) in CLASSES.items()}
        wl = ChaosWorkload(classes, duration, seed=seed)
        t = gw.replay(wl, pace=1.0, timeout=120.0)
        out = _summary(t, gw.resilience_stats())
        for n in gw._nodes:
            mu = n.memory_usage()
            assert all(v >= 0 for v in mu.values()), f"{n.node_id}: {mu}"
            if not n.healthy:  # a dead node holds nothing
                assert mu["device_used"] == 0 and mu["host_used"] == 0, (
                    f"{n.node_id} leaked accounting after crash: {mu}")
        return out
    finally:
        gw.shutdown()


# ----------------------------------------------------------------------
# entry points
# ----------------------------------------------------------------------
def bench_section(quick: bool = False) -> Dict[str, object]:
    """The ``chaos`` section of BENCH_*.json: the sim driver's naive vs
    hardened goodput under the seeded fault trace (the runtime driver is
    covered by the CI chaos smoke, not the recorded perf artifact)."""
    naive = run_sim(False, quick)
    hardened = run_sim(True, quick)
    ratio = (hardened["goodput"] / naive["goodput"]
             if naive["goodput"] else float("inf"))
    return {
        "seed": DEFAULT_SEED,
        "naive": naive,
        "hardened": hardened,
        "goodput_ratio": round(ratio, 3),
    }


def run(quick: bool = True):
    """CSV-harness adapter (benchmarks/run.py): one row per config."""
    from benchmarks.common import Row

    for label, hardened in (("naive", False), ("hardened", True)):
        r = run_sim(hardened, quick)
        yield Row(f"chaos/sim_{label}", 0.0,
                  f"goodput={r['goodput']};completed={r['completed']};"
                  f"errors={sum(r['error_counts'].values())}")


def main(quick: bool = False) -> int:
    ok = True
    for driver, fn in (("sim", run_sim), ("runtime", run_runtime)):
        naive = fn(False, quick)
        hardened = fn(True, quick)
        ratio = (hardened["goodput"] / naive["goodput"]
                 if naive["goodput"] else float("inf"))
        status = "PASS" if ratio >= 2.0 else "FAIL"
        ok &= ratio >= 2.0
        print(f"[{driver}] naive goodput={naive['goodput']} "
              f"hardened goodput={hardened['goodput']} ratio={ratio:.2f}x "
              f"-> {status}")
        print(f"  naive    : {naive['error_counts']} "
              f"{naive['resilience']}")
        print(f"  hardened : {hardened['error_counts']} "
              f"{hardened['resilience']}")
        print(f"  hardened per-priority SLO attainment: "
              f"{hardened['slo_by_priority']}")
    return 0 if ok else 1


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    sys.exit(main(quick="--quick" in sys.argv))
