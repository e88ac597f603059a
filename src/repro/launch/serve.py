"""Serving launcher: the unified gateway fronting real (reduced) models —
the serving-side end-to-end driver.

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b \
      --system sage --requests 32 --rate 8
"""
from __future__ import annotations

import argparse
import time

from repro.api import FunctionSpec, Gateway, PoissonWorkload
from repro.launch.cache import enable_compile_cache


def serve(
    arch: str = "qwen2.5-3b",
    system: str = "sage",
    *,
    requests: int = 32,
    rate: float = 8.0,
    profile: str = "resnet50",
    time_scale: float = 0.2,
    seed: int = 0,
):
    gw = Gateway(backend="runtime", policy=system, time_scale=time_scale,
                 exit_ttl=5.0)
    gw.register(FunctionSpec(name=f"{arch}-fn", arch=arch, profile=profile))
    workload = PoissonWorkload(f"{arch}-fn", rate,
                               duration_s=4.0 * requests / rate, seed=seed,
                               max_events=requests)
    t0 = time.monotonic()
    tel = gw.replay(workload, seed=seed)
    wall = time.monotonic() - t0
    n = len(workload)
    print(f"[serve:{system}] {n} requests in {wall:.2f}s "
          f"({n/wall:.2f}/s) mean={tel.mean_e2e()*1e3:.1f}ms "
          f"p99={tel.p99_e2e()*1e3:.1f}ms warm%={tel.warm_fraction()*100:.0f} "
          f"shared_hits={gw.runtime.daemon.stats['shared_hits']}")
    gw.shutdown()
    return tel


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b")
    ap.add_argument("--system", default="sage")
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--rate", type=float, default=8.0)
    ap.add_argument("--profile", default="resnet50")
    args = ap.parse_args()
    enable_compile_cache()
    serve(args.arch, args.system, requests=args.requests, rate=args.rate,
          profile=args.profile)


if __name__ == "__main__":
    main()
