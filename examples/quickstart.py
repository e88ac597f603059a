"""Quickstart: declare a GPU function with the unified API and invoke it.

Shows the whole paper in 40 lines: one ``FunctionSpec`` describes the
function (the knowability property), the ``Gateway`` lowers it onto the
real runtime where the daemon preloads while the engine compiles (the
parallelized setup), and the second invocation hits shared read-only
weights and a live context (sharing-based memory management + multi-stage
exit). Swap ``backend="sim"`` to replay the same spec on the virtual-time
twin.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

from repro.api import FunctionSpec, Gateway
from repro.launch.cache import enable_compile_cache


def main():
    enable_compile_cache()
    # one gateway per node: SageInit + one memory daemon per device
    gw = Gateway(backend="runtime", policy="sage", time_scale=0.2,
                 exit_ttl=30.0)

    # a real (reduced) qwen2.5 model becomes a serverless GPU function;
    # declared sizes come from the paper's resnet50 profile (Table 2)
    gw.register(FunctionSpec(name="demo-llm", arch="qwen2.5-3b",
                             profile="resnet50"))

    print("cold invocation (compile + load in parallel)...")
    cold = gw.invoke("demo-llm", seed=0)
    print(f"  -> {cold.result}  e2e={cold.e2e*1e3:.1f}ms  stages="
          f"{ {k: round(v*1e3, 1) for k, v in cold.stages.items()} }")

    print("warm invocation (shared weights + live context)...")
    warm = gw.invoke("demo-llm", seed=1)
    print(f"  -> e2e={warm.e2e*1e3:.1f}ms  warm_stage={warm.warm_stage}")
    print(f"speedup: {cold.e2e/warm.e2e:.1f}x | shared hits: "
          f"{gw.runtime.daemon.stats['shared_hits']} | device mem: "
          f"{gw.memory_usage()['device_used']/2**20:.0f} MB")
    gw.shutdown()


if __name__ == "__main__":
    main()
