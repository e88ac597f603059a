"""Program spans and the finer durations on the invocation record: the
spans a profiler trace shows, the compute stage split into the compute
lock's queue and the forward, each weight load's timing on exactly one
record, and each invocation's data wait being its own."""
import time
from concurrent.futures import wait
from pathlib import Path

import jax
import pytest
from jax.profiler import ProfileData

from repro.api import FunctionSpec, Gateway
from repro.core import SageRuntime
from repro.core.functions import make_model_function, make_request
from repro.core.telemetry import STAGES

WORK = ("sage.prepare", "sage.ctx", "sage.forward", "sage.launch",
        "sage.release")
WAIT = ("sage.wait.compute_lock", "sage.wait.data")
LOADER = ("sage.load.fetch", "sage.wait.admit", "sage.load.h2d")
WEIGHTS = ("weights_queue", "weights_admit", "weights_h2d")


def _span_lines(trace_dir):
    """span name -> the names of the host lines (threads) it appears on."""
    (pb,) = list(Path(trace_dir).rglob("*.xplane.pb"))
    out = {}
    for plane in ProfileData.from_file(str(pb)).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("sage."):
                    out.setdefault(ev.name, set()).add(line.name)
    return out


def test_every_span_on_a_host_line_and_loads_on_loader_threads(tmp_path):
    gw = Gateway(backend="runtime", policy="sage", time_scale=0.0)
    try:
        gw.register(FunctionSpec(name="f", arch="qwen2.5-3b"))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            rec = gw.invoke("f", seed=1)  # cold: loads weights and input
        finally:
            jax.profiler.stop_trace()
    finally:
        gw.shutdown()
    assert rec.error is None
    lines = _span_lines(tmp_path)
    assert set(WORK + WAIT + LOADER) <= set(lines), sorted(lines)
    for name in LOADER:
        assert all(n.startswith("sage-loader-") for n in lines[name]), (
            name, lines[name])
    for name in WORK + WAIT:
        assert not any(n.startswith("sage-loader-") for n in lines[name])


def _runtime_with(n_functions, **kw):
    rt = SageRuntime("sage", exit_ttl=60.0, **kw)
    rt.sage_init()
    fns = [make_model_function(rt.db, f"f{i}", arch="qwen2.5-3b", seed=i)
           for i in range(n_functions)]
    for fn in fns:
        rt.register_function(fn)
    return rt, fns


def _round(rt, fns, per_fn, seed0):
    futs = [rt.submit(make_request(rt.db, fn, seed=seed0 + 10 * i + k))
            for k in range(per_fn) for i, fn in enumerate(fns)]
    wait(futs, timeout=120)
    for f in futs:
        f.result(timeout=0)


def test_compute_splits_and_each_weight_load_lands_on_one_record():
    rt, fns = _runtime_with(3, time_scale=0.0)
    try:
        _round(rt, fns, per_fn=3, seed0=0)   # cold: three weight loads
        _round(rt, fns, per_fn=2, seed0=100)  # warm: shared hits
        # back to host, so the next invocations promote host -> HBM
        assert rt.daemon.demote_to_host("f0") > 0
        assert rt.daemon.demote_to_host("f1") > 0
        _round(rt, fns, per_fn=2, seed0=200)
        recs = rt.telemetry.snapshot()
        stats = dict(rt.daemon.stats)
    finally:
        rt.shutdown()
    assert len(recs) == 21 and all(r.error is None for r in recs)
    for r in recs:
        assert set(r.stages) == set(STAGES)
        q, fw = r.substages["compute_queue"], r.substages["forward"]
        assert q >= 0.0 and fw >= 0.0
        assert r.stages["compute"] == q + fw
    carrying = [r for r in recs if "weights_h2d" in r.substages]
    # each invocation also loads its own input once; those are not counted
    weight_loads = stats["loads"] - len(recs)
    assert stats["host_promotions"] == 2 and weight_loads == 3
    assert len(carrying) == weight_loads + stats["host_promotions"]
    for r in carrying:
        assert all(r.substages[k] >= 0.0 for k in WEIGHTS)
    for r in recs:
        assert (set(WEIGHTS) & set(r.substages)) in (set(), set(WEIGHTS))


def test_queued_warm_invocation_is_not_charged_the_cold_ones_data_wait():
    """A cold invocation waits for a slowed weight load before it takes the
    node's compute lock: meanwhile a warm invocation of another function
    on the node runs and completes, with no queue for the lock and a data
    wait of its own (about 0)."""
    rt, (warm,) = _runtime_with(1, time_scale=1.0)
    try:
        # the slow function: 800 MiB declared, so its modeled db and PCIe
        # legs take about 0.7 s; its context is compiled by a first call
        slow = make_model_function(rt.db, "slow", arch="qwen2.5-3b",
                                   declared_ro_bytes=800 << 20)
        rt.register_function(slow)
        for fn in (warm, slow):
            rt.sage_run(make_request(rt.db, fn, seed=1))
        rt.daemon.demote_to_host("slow")
        rt.daemon.drop_host("slow")  # the next call loads from the db
        f_cold = rt.submit(make_request(rt.db, slow, seed=2))
        t0 = time.monotonic()
        while rt.daemon.residency("slow")[0] != "loading":
            assert time.monotonic() - t0 < 30, "the cold load never started"
            time.sleep(0.001)
        rt.submit(make_request(rt.db, warm, seed=3)).result(timeout=60)
        assert not f_cold.done()  # the warm call ran beside the cold load
        f_cold.result(timeout=60)
        by_fn = {r.function: r for r in rt.telemetry.snapshot()[-2:]}
    finally:
        rt.shutdown()
    c, w = by_fn["slow"], by_fn["f0"]
    assert c.stages["gpu_data"] > 0.3  # the cold one waited for its load
    assert w.stages["gpu_data"] < 0.05
    assert w.substages["compute_queue"] < 0.05
    for r in (c, w):
        assert r.stages["compute"] == pytest.approx(
            r.substages["compute_queue"] + r.substages["forward"])
