"""Readings the limit of ``correct`` is set from, on the chip at the cell's
own size: for each seed, one short run of the cell (set-up, a window at the
cell's load, the check) that also computes the control, the reference in
float8, on the same invocations. All seeds run in one process.

    python3 chipbench/calibrate.py --workload <name> --seconds 5 --seeds 11,12,13

Prints one JSON line per seed: the program's ``logit_err`` and the
control's. A limit lies above every program reading and below the
control's; the control has to read three times the program's or more.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402

from run import CACHE_DIR, ROOT  # noqa: E402


def readings(cell, seeds, seconds, *, require_tpu=True, log=None):
    """(seed, program logit_err, control logit_err) for each seed."""
    import harness

    out = []
    for seed in seeds:
        kw = {} if log is None else {"log": log}
        res = harness.Run(cell, seed=seed, seconds=seconds, trace=False,
                          t_start=time.perf_counter(), require_tpu=require_tpu,
                          control=True, **kw).result()
        c = res["checks"]
        out.append((seed, c["logit_err"]["value"],
                    c["control_logit_err"]["value"]))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT / "chipbench"), str(ROOT / "src")]
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    import harness

    cell = harness.load_cell(ROOT, args.workload)
    try:
        harness.device_info(cell.chips, True)
    except harness.NoChip as e:
        print(e, file=sys.stderr)
        return 2
    for seed, prog, ctrl in readings(
            cell, [int(s) for s in args.seeds.split(",")], args.seconds):
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "logit_err": prog, "control_logit_err": ctrl}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
