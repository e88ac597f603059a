"""Find the highest rate an open-loop cell sustains: one set-up, then one
window per rate, each at the cell's own mix.

    python3 chipbench/sweep.py --workload <name> --seed <n> --seconds <s> --rates 4,8,12

A rate is sustained when no invocation fails and no backlog grows through
the window: the 95th-percentile latency of the window's last third stays
within twice that of its first third (a median can sit between a mix's
warm and cold modes and swing with no backlog). Prints one JSON line per
rate. The cell's traffic file keeps the rates swept and the one chosen, at
four fifths of the highest rate sustained.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

from run import CACHE_DIR, ROOT  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT / "chipbench"), str(ROOT / "src")]
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    import harness
    from readers import latency_ms
    from stats import percentile

    cell = harness.load_cell(ROOT, args.workload)
    run = harness.Run(cell, seed=args.seed, seconds=args.seconds, trace=False,
                      t_start=T_START)
    try:
        harness.device_info(cell.chips, True)
    except harness.NoChip as e:
        print(e, file=sys.stderr)
        return 2
    run._setup()
    run._warm()
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        run.tr = {**cell.traffic, "rate_per_s": rate}
        run.seed = args.seed + i
        run._window()
        data = harness.RunData(cell=cell, seconds=args.seconds, setup_s=0.0,
                               t_open=run.t_open, invs=run.invs,
                               counters=run.counters, counts={}, peaks=None)
        third = args.seconds / 3
        early = [x.latency_ms for x in run.invs if x.due < run.t_open + third]
        late = [x.latency_ms for x in run.invs if x.due >= run.t_open + 2 * third]
        failed = sum(not x.ok for x in run.invs)
        weight_loads = (run.counters["loads"] + run.counters["host_promotions"]
                        - len(data.served))
        row = {"rate_per_s": rate, "attempted": len(run.invs), "failed": failed,
               "p50_ms": latency_ms(data, 50), "p95_ms": latency_ms(data, 95),
               "p95_first_third_ms": percentile(early, 95),
               "p95_last_third_ms": percentile(late, 95),
               "cold_share_pct": 100.0 * weight_loads / len(run.invs),
               "counters": run.counters}
        row["sustained"] = (failed == 0 and row["p95_last_third_ms"]
                            <= 2 * row["p95_first_third_ms"])
        print(json.dumps(row), flush=True)
    run.gw.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
