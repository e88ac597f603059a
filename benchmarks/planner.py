"""Planner benchmark: planned dispatch + predictive autoscaling vs a
fixed locality pool on a churning diurnal trace (docs/planner.md).

Six functions with phase-staggered :class:`~repro.api.workload
.DiurnalWorkload` rates rotate the hot set through the day, churning the
residency map. The baseline provisions ``locality`` dispatch a static
pool sized for the peak; the planned config starts at the autoscaler
floor and lets the control plane follow the forecast — planner homes for
warm routing, work stealing over saturated homes, drains through the
exact eviction teardown on the way down.

The headline is the strictly-beats contract (asserted here, in
tests/test_planner.py, and recorded in the BENCH artifact's ``planner``
section): planned+autoscale must deliver **equal-or-better per-class SLO
attainment at strictly lower node-seconds** than the locality pool, on
BOTH drivers. ``python -m benchmarks.planner`` prints both tables and
exits non-zero if either driver misses it; ``--quick`` shrinks the trace
for the CI smoke job.
"""
from __future__ import annotations

import sys
import time
from typing import Dict, List, Tuple

import numpy as np

from benchmarks.common import Row, data_plane_function
from repro.api import FunctionSpec, Gateway
from repro.api.workload import Arrival, DiurnalWorkload
from repro.core.placement import AutoscaleConfig
from repro.core.profiles import MB

DEFAULT_SEED = 11
N_MAX = 8   # the static pool locality gets; the autoscaler's cap (sim)
N_MIN = 2   # autoscaler floor = the planned run's starting pool (sim)

# {function: (base_rate_per_s, deadline_s, priority)} — two SLO classes
# over six functions; the diurnal phases below stagger their peaks
CLASSES: Dict[str, Tuple[float, float, int]] = {
    "fn0": (1.5, 5.0, 2),
    "fn1": (1.5, 5.0, 2),
    "fn2": (1.5, 30.0, 0),
    "fn3": (1.5, 30.0, 0),
    "fn4": (1.5, 30.0, 0),
    "fn5": (1.5, 30.0, 0),
}

AUTOSCALE_SIM = AutoscaleConfig(
    min_nodes=N_MIN, max_nodes=N_MAX, node_rate_per_s=2.5, tick_s=5.0,
    ewma_alpha=0.35, headroom=1.3, up_ticks=1, down_ticks=3)


def _diurnal_arrivals(classes: Dict[str, Tuple[float, float, int]],
                      duration_s: float, period_s: float,
                      seed: int) -> List[Arrival]:
    """Phase-staggered per-function diurnal traces, merged and sorted —
    the hot set rotates as each function's peak comes around."""
    events: List[Arrival] = []
    for i, (fn, (rate, dl, pr)) in enumerate(sorted(classes.items())):
        wl = DiurnalWorkload(fn, rate, duration_s, amplitude=0.9,
                             period_s=period_s, phase_s=i * period_s / 8.0,
                             seed=seed, deadline_s=dl, priority=pr)
        events.extend(wl.events())
    events.sort(key=lambda a: a.t)
    return events


def _slo(t) -> Dict[int, float]:
    return {p: round(c["attainment"], 4)
            for p, c in sorted(t.slo_by_priority().items())}


def _beats(planned: Dict, baseline: Dict) -> bool:
    """The strictly-beats contract: every priority class at least as well
    served, strictly fewer node-seconds."""
    if planned["node_seconds"] >= baseline["node_seconds"]:
        return False
    return all(planned["slo"].get(p, 0.0) >= att
               for p, att in baseline["slo"].items())


# ----------------------------------------------------------------------
# sim driver
# ----------------------------------------------------------------------
def run_sim(planned: bool, quick: bool = False,
            seed: int = DEFAULT_SEED) -> Dict[str, object]:
    duration = 240.0 if quick else 720.0
    period = duration / 2.0
    horizon = duration + 60.0
    kw: Dict[str, object] = dict(backend="sim", policy="sage", seed=seed,
                                 loader_threads=2)
    if planned:
        gw = Gateway(n_nodes=N_MIN, dispatch="planned",
                     autoscale=AUTOSCALE_SIM, **kw)
    else:
        gw = Gateway(n_nodes=N_MAX, dispatch="locality", **kw)
    for fn, (rate, dl, pr) in sorted(CLASSES.items()):
        gw.register(FunctionSpec(
            name=fn, read_only_bytes=96 * MB, writable_bytes=8 * MB,
            context_bytes=64 * MB, compute_ms=20.0,
            deadline_s=dl, priority=pr))
    t = gw.replay(_diurnal_arrivals(CLASSES, duration, period, seed),
                  until=horizon)
    assert t.error_count() == 0, t.errors()[0].error
    ps = gw.placement_stats()
    node_seconds = (ps["node_seconds"] if ps is not None
                    else N_MAX * horizon)
    out: Dict[str, object] = {
        "config": "planned+autoscale" if planned else "locality",
        "arrivals": len(t.snapshot()),
        "slo": _slo(t),
        "node_seconds": round(float(node_seconds), 3),
        "p99_e2e_s": round(t.p99_e2e(), 4),
    }
    if ps is not None:
        out["placement"] = {k: ps[k] for k in (
            "planned_hits", "planned_misses", "hit_rate", "replans",
            "boards", "steals", "scale_ups", "scale_downs")}
        out["node_timeline"] = [(round(at, 1), n)
                                for at, n in ps["node_timeline"]]
    return out


# ----------------------------------------------------------------------
# runtime driver (real threaded cluster, small scale)
# ----------------------------------------------------------------------
def run_runtime(planned: bool, quick: bool = False,
                seed: int = DEFAULT_SEED) -> Dict[str, object]:
    from repro.core.request import Data, DataType, Request
    from repro.core.runtime import ClusterRuntime
    from repro.data.database import Database

    duration = 10.0 if quick else 16.0
    n_max, n_min, ro_mb = 4, 2, 24
    names = [f"fn{i}" for i in range(4)]
    classes = {fn: (2.5, 3.0 if i < 2 else 10.0, 2 if i < 2 else 0)
               for i, fn in enumerate(names)}
    db = Database()
    kw: Dict[str, object] = dict(database=db, loader_threads=2,
                                 serialize_compute=False)
    if planned:
        auto = AutoscaleConfig(
            min_nodes=n_min, max_nodes=n_max, node_rate_per_s=4.0,
            tick_s=0.5, ewma_alpha=0.4, headroom=1.3,
            up_ticks=1, down_ticks=2)
        cluster = ClusterRuntime(n_nodes=n_min, seed=seed,
                                 dispatch="planned", autoscale=auto, **kw)
    else:
        cluster = ClusterRuntime(n_nodes=n_max, seed=seed,
                                 dispatch="locality", **kw)
    cluster.sage_init()
    clk = cluster.nodes[0].clock
    t0 = clk.now()
    for name in names:
        db.put(f"{name}/weights", np.zeros(1, np.uint8), size=ro_mb * MB)
        cluster.register_function(
            lambda i, name=name: data_plane_function(name))
    events = _diurnal_arrivals(classes, duration, duration, seed)
    try:
        futs = []
        start = time.monotonic()
        for k, a in enumerate(events):
            lag = start + a.t - time.monotonic()
            if lag > 0:
                time.sleep(lag)
            wkey = f"{a.function}/in/{k}"
            db.put(wkey, np.zeros(1, np.uint8), size=2 * MB)
            req = Request(function_name=a.function)
            req.in_data = [
                Data(key=f"{a.function}/weights", size=ro_mb * MB,
                     dtype=DataType.READ_ONLY),
                Data(key=wkey, size=2 * MB, dtype=DataType.WRITABLE),
            ]
            req.deadline_s, req.priority = a.deadline_s, a.priority
            futs.append(cluster.submit(req))
        for f in futs:
            f.result(timeout=120)
        t1 = clk.now()
        tel = cluster.telemetry
        assert tel.error_count() == 0, tel.errors()[0].error
        ps = cluster.placement_stats()
        node_seconds = (ps["node_seconds"] if ps is not None
                        else n_max * (t1 - t0))
        out: Dict[str, object] = {
            "config": "planned+autoscale" if planned else "locality",
            "arrivals": len(tel.snapshot()),
            "slo": _slo(tel),
            "node_seconds": round(float(node_seconds), 3),
            "p99_e2e_s": round(tel.p99_e2e(), 4),
        }
        if ps is not None:
            out["placement"] = {k: ps[k] for k in (
                "planned_hits", "hit_rate", "scale_ups", "scale_downs")}
        return out
    finally:
        cluster.shutdown()


# ----------------------------------------------------------------------
# entry points
# ----------------------------------------------------------------------
def bench_section(quick: bool = False) -> Dict[str, object]:
    """The ``planner`` section of BENCH_*.json: locality pool vs
    planned+autoscale on the sim driver (the runtime driver is covered
    by the CI planner smoke, not the recorded artifact)."""
    baseline = run_sim(False, quick)
    planned = run_sim(True, quick)
    ratio = planned["node_seconds"] / baseline["node_seconds"]
    return {
        "seed": DEFAULT_SEED,
        "locality": baseline,
        "planned": planned,
        "node_seconds_ratio": round(ratio, 4),
        "beats": _beats(planned, baseline),
    }


def run(quick: bool = True):
    """CSV-harness adapter (benchmarks/run.py): one row per config."""
    baseline = run_sim(False, quick)
    planned = run_sim(True, quick)
    for r in (baseline, planned):
        yield Row(f"planner/sim_{r['config']}", 0.0,
                  f"node_seconds={r['node_seconds']};slo={r['slo']};"
                  f"p99={r['p99_e2e_s']}")
    yield Row("planner/sim_node_seconds_ratio",
              planned["node_seconds"] / baseline["node_seconds"] * 100.0,
              f"beats={_beats(planned, baseline)}")


def main(quick: bool = False) -> int:
    ok = True
    for driver, fn in (("sim", run_sim), ("runtime", run_runtime)):
        baseline = fn(False, quick)
        planned = fn(True, quick)
        beats = _beats(planned, baseline)
        ok &= beats
        ratio = planned["node_seconds"] / baseline["node_seconds"]
        print(f"[{driver}] locality node_seconds={baseline['node_seconds']} "
              f"slo={baseline['slo']} | planned "
              f"node_seconds={planned['node_seconds']} slo={planned['slo']} "
              f"ratio={ratio:.2f}x -> {'PASS' if beats else 'FAIL'}")
        if "placement" in planned:
            print(f"  placement: {planned['placement']}")
        if "node_timeline" in planned:
            print(f"  timeline : {planned['node_timeline']}")
    return 0 if ok else 1


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    sys.exit(main(quick="--quick" in sys.argv))
