"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (scaffold contract). Heavy trace
experiments run on the virtual-clock simulator (deterministic); kernel rows
measure the real CPU reference path and derive TPU roofline estimates; the
roofline rows read the dry-run artifacts when present.

Run:  PYTHONPATH=src python -m benchmarks.run [--full]

``--bench-json`` switches to the recorded perf trajectory instead: it
replays the simulator-scale scenarios (benchmarks/sim_scale.py — the
headline drives >=1M invocations across 64 nodes) plus the chaos
resilience scenario (benchmarks/chaos.py), the planner placement
scenario (benchmarks/planner.py), the gray-failure tail scenario
(benchmarks/tail_tolerance.py), and the shared-compute density
scenario (benchmarks/density.py) and writes ``BENCH_10.json``
(schema: docs/simulator.md). ``--quick`` shrinks the scenario durations
~20x for the CI smoke job; ``--min-events-per-s`` turns the run into an
anti-regression gate.
"""
import argparse
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

REPO_ROOT = Path(__file__).resolve().parents[1]


def bench_json_main(args) -> None:
    from benchmarks import chaos, density, planner, sim_scale, tail_tolerance

    doc = sim_scale.bench_json(quick=args.quick)
    # the resilience headline rides next to the perf scenarios: naive vs
    # hardened goodput under the seeded chaos fault trace (sim driver)
    doc["chaos"] = chaos.bench_section(quick=args.quick)
    # the placement headline: planned dispatch + predictive autoscaling
    # must strictly beat the fixed locality pool (docs/planner.md)
    doc["planner"] = planner.bench_section(quick=args.quick)
    # the tail headline: hedging + quarantine must strictly beat the
    # eviction-only config on tight-class p99 under gray faults
    doc["tail"] = tail_tolerance.bench_section(quick=args.quick)
    # the density headline: the shared compute plane (fractional SM
    # slices + same-function batching) must beat the exclusive FIFO by
    # more than the paper's 1.22x with tight-class SLO no worse
    doc["density"] = density.bench_section(quick=args.quick)
    out = Path(args.bench_out) if args.bench_out else (
        REPO_ROOT / f"BENCH_{sim_scale.BENCH_ID}.json")
    out.write_text(json.dumps(doc, indent=2, sort_keys=True) + "\n")
    head = doc["headline"]
    print(f"wrote {out}: {head['invocations']:,} invocations on "
          f"{head['nodes']} nodes in {head['wall_s']:.1f}s "
          f"({head['events_per_s']:,.0f} events/s); chaos goodput ratio "
          f"{doc['chaos']['goodput_ratio']}x; planner node-seconds ratio "
          f"{doc['planner']['node_seconds_ratio']}x; tail tight-p99 ratio "
          f"{doc['tail']['tight_p99_ratio']}x; density ratio "
          f"{doc['density']['density_ratio']}x")
    if doc["chaos"]["goodput_ratio"] < 2.0:
        print("FAIL: hardened config below 2x naive goodput under faults")
        sys.exit(1)
    if not doc["planner"]["beats"]:
        print("FAIL: planned+autoscale did not strictly beat the "
              "locality pool (equal-or-better SLO at lower node-seconds)")
        sys.exit(1)
    if not doc["tail"]["beats"]:
        print("FAIL: hedging+quarantine did not strictly beat the "
              "eviction-only config on tight-class p99 under gray faults")
        sys.exit(1)
    if not doc["density"]["beats"]:
        print("FAIL: shared compute plane did not beat the exclusive "
              f"FIFO by more than {doc['density']['paper_density_x']}x "
              "function density with tight-class SLO no worse")
        sys.exit(1)
    if args.min_events_per_s and head["events_per_s"] < args.min_events_per_s:
        print(f"FAIL: headline events/s {head['events_per_s']:,.0f} below "
              f"floor {args.min_events_per_s:,.0f}")
        sys.exit(1)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale durations (slower)")
    ap.add_argument("--only", help="comma-separated module names")
    ap.add_argument("--bench-json", action="store_true",
                    help="replay the sim-scale scenarios and write BENCH_*.json")
    ap.add_argument("--quick", action="store_true",
                    help="with --bench-json: ~20x shorter scenario durations")
    ap.add_argument("--bench-out",
                    help="with --bench-json: output path (default BENCH_10.json)")
    ap.add_argument("--min-events-per-s", type=float, default=0.0,
                    help="with --bench-json: exit 1 if the headline replay "
                         "falls below this events/s floor")
    args = ap.parse_args()
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    if args.bench_json:
        bench_json_main(args)
        return
    quick = not args.full

    from benchmarks import (
        chaos, contention, density, duration_breakdown, end_to_end,
        kernel_bench, many_functions, multistage, planner, preemption,
        roofline, scaleout, sharing_ablation, sim_scale, slo_scheduling,
        tail_tolerance, throughput,
    )

    modules = {
        "duration_breakdown": duration_breakdown,  # Fig 2 / Fig 15
        "throughput": throughput,                  # Fig 3 / Fig 13
        "contention": contention,                  # Fig 4
        "end_to_end": end_to_end,                  # Fig 10 / 11 / 12
        "many_functions": many_functions,          # Fig 14
        "multistage": multistage,                  # Table 4
        "sharing_ablation": sharing_ablation,      # Fig 16
        "scaleout": scaleout,                      # Fig 17
        "slo_scheduling": slo_scheduling,          # EDF vs FIFO SLO report
        "preemption": preemption,                  # preemptive transfer vs RTC
        "kernel_bench": kernel_bench,              # Pallas kernel roofs
        "roofline": roofline,                      # §Roofline table
        "sim_scale": sim_scale,                    # kernel replay throughput
        "chaos": chaos,                            # resilience under faults
        "planner": planner,                        # placement vs static pool
        "tail_tolerance": tail_tolerance,          # gray failures / hedging
        "density": density,                        # shared compute plane
    }
    if args.only:
        keep = set(args.only.split(","))
        modules = {k: v for k, v in modules.items() if k in keep}

    print("name,us_per_call,derived")
    failed = []
    for name, mod in modules.items():
        try:
            for row in mod.run(quick=quick):
                row.print()
        except Exception as e:  # a failing table must not hide the others
            print(f"{name},-1,ERROR:{type(e).__name__}:{e}")
            failed.append(name)
    if failed:
        sys.exit(f"failed tables: {', '.join(failed)}")


if __name__ == "__main__":
    main()
