"""End-to-end serving driver: one Workload replayed against real (reduced)
models through the gateway, comparing all systems under identical open-loop
load — the serving counterpart of the paper's §7.2, with per-request SLO
deadlines recorded end-to-end.

Run:  PYTHONPATH=src python examples/serve_workload.py [--requests 24]
"""
import argparse
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

from repro.api import FunctionSpec, Gateway, PoissonWorkload
from repro.launch.cache import enable_compile_cache

SPECS = [
    FunctionSpec(name="qwen2.5-3b-fn", arch="qwen2.5-3b", profile="resnet50",
                 deadline_s=2.0),
    FunctionSpec(name="qwen3-8b-fn", arch="qwen3-8b", profile="bert",
                 deadline_s=2.0),
    FunctionSpec(name="mamba2-780m-fn", arch="mamba2-780m", profile="seq2seq",
                 deadline_s=2.0),
]


def drive(system: str, requests: int, rate: float, seed: int = 0):
    gw = Gateway(backend="runtime", policy=system, time_scale=0.05,
                 exit_ttl=3.0)
    for spec in SPECS:
        gw.register(spec)
    # open-loop Poisson over the three functions, truncated at `requests`
    # (duration oversized so the count is always reached)
    workload = PoissonWorkload([s.name for s in SPECS], rate,
                               duration_s=4.0 * requests / rate, seed=seed,
                               max_events=requests)
    t0 = time.monotonic()
    tel = gw.replay(workload)
    wall = time.monotonic() - t0
    print(f"{system:10s} {len(workload)} reqs {wall:6.2f}s "
          f"({len(workload)/wall:5.2f}/s) mean={tel.mean_e2e()*1e3:8.1f}ms "
          f"p99={tel.p99_e2e()*1e3:8.1f}ms warm%={tel.warm_fraction()*100:5.1f} "
          f"slo_miss%={tel.slo_miss_rate()*100:5.1f} "
          f"shared={gw.runtime.daemon.stats['shared_hits']:3d} "
          f"mem={gw.memory_usage()['device_used']/2**20:6.0f}MB")
    mean = tel.mean_e2e()
    gw.shutdown()
    return mean


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--rate", type=float, default=6.0)
    args = ap.parse_args()
    enable_compile_cache()
    print("system     load                mean        p99      warm   slo   sharing  memory")
    base = drive("fixedgsl", args.requests, args.rate)
    sage = drive("sage", args.requests, args.rate)
    print(f"\nSAGE speedup vs FixedGSL on this box: {base/sage:.1f}x")


if __name__ == "__main__":
    main()
