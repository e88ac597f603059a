"""Multi-stage resource exit, live (paper §6.3 / Table 4): invoke through
the gateway, then watch the ladder demote resources stage by stage; hit
each stage with a new request and see which setup phases it skips.

Run:  PYTHONPATH=src python examples/multistage_demo.py
"""
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

from repro.api import FunctionSpec, Gateway
from repro.launch.cache import enable_compile_cache

TTL = 0.6  # compressed 30 s -> 0.6 s per stage for the demo


def mem(gw):
    u = gw.memory_usage()
    return f"device={u['device_used']/2**20:6.0f}MB ctx={u['context_bytes']/2**20:4.0f}MB host={u['host_used']/2**20:6.0f}MB"


def main():
    enable_compile_cache()
    gw = Gateway(backend="runtime", policy="sage", time_scale=0.05,
                 exit_ttl=TTL)
    gw.register(FunctionSpec(name="f", arch="qwen2.5-3b", profile="resnet50"))

    print("cold invocation:")
    r = gw.invoke("f", seed=0)
    print(f"  e2e={r.e2e*1e3:7.1f}ms  {mem(gw)}")

    # each warm hit resets the ladder, so the wait before hit k must span
    # k-1 full stage TTLs to land in stage k (the ladder advance is a
    # mechanism-layer peek; load itself goes through the gateway)
    for stage, wait in ((1, 0.5 * TTL), (2, 1.5 * TTL), (3, 2.5 * TTL),
                        (4, 3.5 * TTL)):
        time.sleep(wait)
        gw.runtime.engines["f"]._advance_ladders()
        print(f"after stage-{stage} window: {mem(gw)}")
        r = gw.invoke("f", seed=stage)
        print(f"  warm hit at stage {r.warm_stage}: e2e={r.e2e*1e3:7.1f}ms "
              f"(gpu_ctx={r.stages.get('gpu_ctx', 0)*1e3:.1f}ms "
              f"gpu_data={r.stages.get('gpu_data', 0)*1e3:.1f}ms)")
    gw.shutdown()


if __name__ == "__main__":
    main()
