"""Preemptive transfer scheduling: chunked, preemptible streams vs atomic
run-to-completion transfers under a contended mixed-size / mixed-deadline
trace (docs/dataplane.md, "Transfer scheduling"; FaaSTube arXiv:2411.01830).

One narrow loader pool (one worker) serves two classes: loose-deadline
batch functions with large working sets, and a tight-deadline
latency-critical function with a small one. ``scheduler="edf"`` is on for
BOTH arms, so queued work is already deadline-ordered — the only varied
knob is ``transfer``. Under ``run_to_completion`` a tight load arriving
mid-way through a loose 800 MB stream still waits the stream out; under
``preemptive`` the in-flight stream pauses between chunks and yields the
link, so the tight class's p99 duration collapses while the batch class
pays only the chunk-granularity stall. Rows report both backends (the
strictly-beats contract is asserted in tests/test_transfer.py).
"""
from __future__ import annotations

import time

import numpy as np

from benchmarks.common import Row, data_plane_function
from repro.api import FunctionSpec, Gateway, MixWorkload
from repro.core.profiles import MB

TIGHT_DEADLINE_S = 1.2
BATCH_DEADLINE_S = 60.0


# ---------------------------------------------------------------------------
# virtual-time twin
# ---------------------------------------------------------------------------

def _sim_stats(transfer: str, duration_s: float):
    gw = Gateway(backend="sim", policy="sage", scheduler="edf",
                 transfer=transfer, loader_threads=1, seed=11)
    rates = {}
    for i in range(3):
        name = f"batch{i}"
        gw.register(FunctionSpec(
            name=name, read_only_bytes=0, writable_bytes=800 * MB,
            context_bytes=MB, compute_ms=10.0,
            deadline_s=BATCH_DEADLINE_S, priority=0))
        rates[name] = 0.3
    gw.register(FunctionSpec(
        name="tight", read_only_bytes=0, writable_bytes=24 * MB,
        context_bytes=MB, compute_ms=5.0,
        deadline_s=TIGHT_DEADLINE_S, priority=1))
    rates["tight"] = 1.0
    tel = gw.replay(MixWorkload(rates, duration_s, seed=11), until_pad=600.0)
    return {
        "tight_p99": tel.p99_duration("tight"),
        "tight_miss": (tel.slo_by_priority().get(1, {}) or {}).get("miss_rate", 0.0),
        "preemptions": float(gw.sim.preemption_count()),
        "stalled_s": tel.transfer_wait(),
        "n": float(len(tel.records)),
    }


# ---------------------------------------------------------------------------
# threaded runtime (synthetic functions: the comparison is the data plane)
# ---------------------------------------------------------------------------

def _runtime_stats(transfer: str, rounds: int):
    from repro.core.request import Data, DataType, Request
    from repro.core.runtime import SageRuntime

    rt = SageRuntime("sage", loader_threads=1, scheduler="edf",
                     transfer=transfer, serialize_compute=False)
    rt.sage_init()
    for i in range(2):
        rt.register_function(data_plane_function(f"batch{i}", wait_s=60.0))
    rt.register_function(data_plane_function("tight", wait_s=60.0))

    def req(fn, mb, deadline_s, priority, tag):
        r = Request(function_name=fn)
        key = f"{fn}/in/{tag}"
        rt.db.put(key, np.zeros(1, np.uint8), size=mb * MB)
        r.in_data = [Data(key=key, size=mb * MB, dtype=DataType.WRITABLE)]
        r.deadline_s, r.priority = deadline_s, priority
        return r

    try:
        futs = []
        for rnd in range(rounds):
            for i in range(2):  # loose 400 MB loads own the single worker
                futs.append(rt.submit(req(f"batch{i}", 400, BATCH_DEADLINE_S,
                                          0, f"{rnd}-{i}")))
            time.sleep(0.08)  # tight arrives mid-way through a batch stream
            futs.append(rt.submit(req("tight", 16, TIGHT_DEADLINE_S, 1,
                                      str(rnd))))
            time.sleep(0.4)  # drain most of the round before the next burst
        for f in futs:
            f.result(timeout=120)
        tel = rt.telemetry
        return {
            "tight_p99": tel.p99_duration("tight"),
            "tight_miss": (tel.slo_by_priority().get(1, {}) or {}).get("miss_rate", 0.0),
            "preemptions": float(rt.daemon.stats["preemptions"]),
            "stalled_s": tel.transfer_wait(),
            "n": float(len(tel.records)),
        }
    finally:
        rt.shutdown()


# ---------------------------------------------------------------------------

def run(quick: bool = True):
    duration = 90.0 if quick else 600.0
    rounds = 3 if quick else 10
    rows = []
    for backend, stats_fn, arg in (("sim", _sim_stats, duration),
                                   ("runtime", _runtime_stats, rounds)):
        res = {mode: stats_fn(mode, arg)
               for mode in ("run_to_completion", "preemptive")}
        rtc, pre = res["run_to_completion"], res["preemptive"]
        rows.append(Row(
            f"preempt_{backend}_tight_p99_rtc", rtc["tight_p99"] * 1e6,
            f"miss_rate={rtc['tight_miss']:.3f};n={int(rtc['n'])}"))
        rows.append(Row(
            f"preempt_{backend}_tight_p99_preemptive", pre["tight_p99"] * 1e6,
            f"miss_rate={pre['tight_miss']:.3f};"
            f"speedup={rtc['tight_p99']/max(pre['tight_p99'],1e-9):.1f}x"))
        rows.append(Row(
            f"preempt_{backend}_preemptions", pre["preemptions"],
            f"stalled_s={pre['stalled_s']:.3f};"
            f"rtc_stalled_s={rtc['stalled_s']:.3f}"))
    return rows


if __name__ == "__main__":
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    for r in run():
        r.print()
