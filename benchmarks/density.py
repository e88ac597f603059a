"""Function-density benchmark: shared compute plane vs the exclusive seed
(docs/compute.md).

The paper's headline cluster result is a 1.22x function-density win from
fast setup alone. This benchmark measures the density the *compute* plane
adds on top: a contended multi-small-function trace is replayed twice per
driver —

* **exclusive**: the seed's one-kernel-at-a-time compute FIFO (the paper's
  ``Throughput_theo = T_period / T_comp`` model) — small functions
  serialize behind each other even though each needs a fraction of the SMs;
* **shared**: ``compute="shared"`` with same-function batching — each small
  function takes its auto-derived slice of the SM budget, co-runs with the
  others, and concurrent invocations of one function coalesce into a
  single stacked kernel launch (amortization pinned by
  ``benchmarks/kernel_bench.py``'s batch-axis sweep).

Function density is completions per node-second over the trace's makespan.
The gate: shared must beat exclusive by MORE than the paper's 1.22x on
BOTH drivers, with tight-class SLO attainment no worse under EDF (the
batch collector never holds a member past its EDF slack, so batching must
not buy throughput with tight-class misses). ``python -m
benchmarks.density`` prints both tables and exits non-zero on a miss.
"""
from __future__ import annotations

import sys
import time
from typing import Dict, Optional, Tuple

from repro.api.workload import ChaosWorkload
from repro.core.profiles import FunctionProfile
from repro.core.simulator import SimFunction, Simulator

DEFAULT_SEED = 47
N_NODES = 2
#: the paper's headline function-density ratio — the bar to beat
PAPER_DENSITY_X = 1.22

# the shared-plane config under test: auto slice sizing + batching
SHARED = {"max_batch": 4, "batch_window_s": 0.005}

# {function: (rate_per_s, deadline_s, priority)} — six small functions
# whose aggregate compute demand oversubscribes the exclusive FIFO on
# N_NODES (each needs ~3/8 of a node's SMs, so the shared plane packs
# ~2.7 of them per node instead of 1)
CLASSES: Dict[str, Tuple[float, Optional[float], int]] = {
    "tight0": (30.0, 0.5, 2),
    "tight1": (30.0, 0.5, 2),
    "tight2": (30.0, 0.5, 2),
    "loose0": (30.0, 5.0, 0),
    "loose1": (30.0, 5.0, 0),
    "loose2": (30.0, 5.0, 0),
}
COMPUTE_MS = 15.0


def _density_summary(t, n_nodes: int) -> Dict[str, object]:
    recs = [r for r in t.snapshot() if not r.dropped and r.error is None]
    if not recs:
        return {"completed": 0, "density_per_node_s": 0.0,
                "tight_attainment": 0.0, "makespan_s": 0.0,
                "mean_batch": 1.0}
    makespan = max(r.end_t for r in recs) - min(r.arrival_t for r in recs)
    tight = [r for r in recs if r.function.startswith("tight")]
    attained = sum(1 for r in tight if not r.slo_miss)
    return {
        "completed": len(recs),
        "makespan_s": round(makespan, 3),
        "density_per_node_s": round(len(recs) / (n_nodes * makespan), 3),
        "tight_attainment": round(attained / max(1, len(tight)), 4),
        "mean_batch": round(sum(r.batch_size for r in recs) / len(recs), 3),
    }


# ----------------------------------------------------------------------
# sim driver: EDF + locality, contended six-function trace
# ----------------------------------------------------------------------
def run_sim(compute, quick: bool = False,
            seed: int = DEFAULT_SEED) -> Dict[str, object]:
    duration = 15.0 if quick else 60.0
    sim = Simulator("sage", n_nodes=N_NODES, seed=seed,
                    scheduler="edf", dispatch="locality", compute=compute)
    for name in sorted(CLASSES):
        sim.register(SimFunction(FunctionProfile(
            name, "density", context_mb=64.0, read_only_mb=24.0,
            writable_mb=4.0, compute_ms=COMPUTE_MS)))
    wl = ChaosWorkload(CLASSES, duration, seed=seed)
    for i, a in enumerate(wl.events()):
        sim.submit(a.function, a.t, deadline_s=a.deadline_s,
                   priority=a.priority, request_id=f"d{i}-{a.function}")
    sim.run()  # drain fully: density is judged on the true makespan
    out = _density_summary(sim.telemetry, N_NODES)
    out["compute"] = sim.compute_stats()
    # the plane must leave the books exactly as the seed path does
    for n in sim.nodes:
        assert 0 <= n.used <= n.capacity, f"{n.name}: used={n.used}"
        assert n.inflight_loads == 0, f"{n.name} leaked loader slots"
    return out


# ----------------------------------------------------------------------
# runtime driver: real threads, sleep-modeled kernels, one node
# ----------------------------------------------------------------------
def run_runtime(compute, quick: bool = False,
                seed: int = DEFAULT_SEED) -> Dict[str, object]:
    from repro.core.engine import GPUFunction
    from repro.core.request import Request
    from repro.core.runtime import SageRuntime

    compute_s = 0.010
    per_fn = 8 if quick else 16
    fn_names = ["d0", "d1", "d2"]
    rt = SageRuntime("sage", max_workers=64, serialize_compute=True,
                     compute=compute)
    rt.sage_init()
    try:
        for name in fn_names:

            def handler(shim, request, _c=compute_s):
                # the modeled kernel is a synchronous launch: under the
                # exclusive lock it holds the node for its whole span
                shim.launch_kernel(time.sleep, _c)

            rt.register_function(GPUFunction(
                name=name, handler=handler,
                context_builder=lambda: object(),
                context_bytes=1 << 20, container_s=0.0, cpu_ctx_s=0.0,
                compute_s_hint=compute_s))
        t0 = rt.clock.now()
        futs = []
        # round-robin burst: concurrent same-function arrivals exist for
        # the batch collector, and all three functions contend at once
        for i in range(per_fn):
            for name in fn_names:
                futs.append(rt.submit(Request(
                    function_name=name, deadline_s=0.3, priority=2)))
        for f in futs:
            f.result(timeout=120.0)
        makespan = rt.clock.now() - t0
        recs = [r for r in rt.telemetry.snapshot() if r.error is None]
        attained = sum(1 for r in recs if not r.slo_miss)
        out = {
            "completed": len(recs),
            "makespan_s": round(makespan, 3),
            "density_per_node_s": round(len(recs) / makespan, 3),
            "tight_attainment": round(attained / max(1, len(recs)), 4),
            "mean_batch": round(sum(r.batch_size for r in recs)
                                / max(1, len(recs)), 3),
            "compute": rt.compute_stats(),
        }
        mu = rt.memory_usage()
        assert all(v >= 0 for v in mu.values()), f"memory books: {mu}"
        assert rt.daemon.leaked_bytes == 0, (
            f"{rt.daemon.leaked_bytes} leaked bytes after the burst")
        return out
    finally:
        rt.shutdown()


# ----------------------------------------------------------------------
# entry points
# ----------------------------------------------------------------------
def _compare(exclusive: Dict, shared: Dict) -> Dict[str, object]:
    dx = exclusive["density_per_node_s"]
    ds = shared["density_per_node_s"]
    return {
        "exclusive": exclusive,
        "shared": shared,
        "density_ratio": round(ds / dx, 3) if dx else float("inf"),
        "beats": (ds > dx * PAPER_DENSITY_X
                  and shared["tight_attainment"]
                  >= exclusive["tight_attainment"]),
    }


def bench_section(quick: bool = False) -> Dict[str, object]:
    """The ``density`` section of BENCH_*.json: the sim driver's exclusive
    vs shared density under the contended trace (the runtime driver is
    covered by the CI density smoke, not the artifact)."""
    out = _compare(run_sim(None, quick), run_sim(SHARED, quick))
    out["seed"] = DEFAULT_SEED
    out["paper_density_x"] = PAPER_DENSITY_X
    return out


def run(quick: bool = True):
    """CSV-harness adapter (benchmarks/run.py): one row per config."""
    from benchmarks.common import Row

    for label, compute in (("exclusive", None), ("shared", SHARED)):
        r = run_sim(compute, quick)
        yield Row(f"density/sim_{label}", 0.0,
                  f"density={r['density_per_node_s']}/node/s;"
                  f"tight_slo={r['tight_attainment']};"
                  f"mean_batch={r['mean_batch']}")


def main(quick: bool = False) -> int:
    ok = True
    for driver, fn in (("sim", run_sim), ("runtime", run_runtime)):
        cmp = _compare(fn(None, quick), fn(SHARED, quick))
        status = "PASS" if cmp["beats"] else "FAIL"
        ok &= cmp["beats"]
        ex, sh = cmp["exclusive"], cmp["shared"]
        print(f"[{driver}] exclusive {ex['density_per_node_s']}/node/s "
              f"(tight SLO {ex['tight_attainment']}) vs shared "
              f"{sh['density_per_node_s']}/node/s "
              f"(tight SLO {sh['tight_attainment']}, "
              f"mean_batch {sh['mean_batch']}) -> "
              f"{cmp['density_ratio']}x (bar {PAPER_DENSITY_X}x) {status}")
        print(f"  shared compute: {sh['compute']}")
    return 0 if ok else 1


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    sys.exit(main(quick="--quick" in sys.argv))
