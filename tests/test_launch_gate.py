"""The node's compute lock held only across a launch's enqueue: one program
queued behind the one running, never a third; a failing handler or program
frees the lock and its place; each record says whether its program was
enqueued behind an unfinished one, and only the gated runtime says so."""
import os
import queue
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from repro.api import FunctionSpec, Gateway
from repro.core.engine import GPUFunction
from repro.core.executor import QUEUED_BEHIND_RUNNING, KernelExecutor
from repro.core.functions import make_model_function, make_request
from repro.core.request import Request
from repro.core.runtime import SageRuntime

MB = 1 << 20


class Held:
    """A program's output that becomes ready only when the test says."""

    def __init__(self, ready: bool = False):
        self.done = threading.Event()
        if ready:
            self.done.set()

    def is_ready(self) -> bool:
        return self.done.is_set()

    def block_until_ready(self):
        assert self.done.wait(30), "the test never released this output"
        return self


def _fn(name, handler):
    return GPUFunction(name=name, handler=handler,
                       context_builder=lambda: object(), context_bytes=MB,
                       container_s=0.0, cpu_ctx_s=0.0)


def _until(cond, what, timeout=30.0):
    t0 = time.monotonic()
    while not cond():
        assert time.monotonic() - t0 < timeout, what
        time.sleep(0.001)


@pytest.fixture
def rt():
    rt = SageRuntime("sage", time_scale=0.0, max_workers=8)
    rt.sage_init()
    yield rt
    rt.shutdown()


def _assert_compute_split(recs):
    for r in recs:
        assert r.stages["compute"] == (r.substages["compute_queue"]
                                       + r.substages["forward"])


def test_one_program_queued_behind_the_running_one_and_never_a_third(rt):
    enqueued = []

    def program():
        out = Held()
        enqueued.append(out)
        return out

    def handler(shim, request):
        # the fetch of the result, outside the lock
        return shim.launch_kernel(program).block_until_ready()

    rt.register_function(_fn("f", handler))
    first = rt.submit(Request(function_name="f"))
    _until(lambda: len(enqueued) == 1, "the first program was never enqueued")
    second = rt.submit(Request(function_name="f"))
    _until(lambda: len(enqueued) == 2,
           "the second program waited for the first result")
    assert not first.done()  # its result is not fetched yet
    third = rt.submit(Request(function_name="f"))
    time.sleep(0.3)
    assert len(enqueued) == 2  # no third while two are unfinished
    enqueued[0].done.set()
    _until(lambda: len(enqueued) == 3,
           "the third program never took the first one's place")
    for out in enqueued:
        out.done.set()
    for f in (first, second, third):
        f.result(timeout=30)
    recs = rt.telemetry.snapshot()
    assert len(recs) == 3 and all(r.error is None for r in recs)
    by_start = sorted(recs, key=lambda r: r.start_t)
    assert [r.launch_overlapped for r in by_start] == [False, True, True]
    # the third waited for room behind the running program, under the lock
    assert by_start[2].substages["compute_queue"] >= 0.25
    _assert_compute_split(recs)


@pytest.mark.parametrize("where", ["after_launch", "in_launch"])
def test_a_failing_handler_frees_the_lock_and_its_place(rt, where):
    held = Held()

    def failing_program():
        raise RuntimeError("the program failed")

    def bad(shim, request):
        if where == "in_launch":
            shim.launch_kernel(failing_program)
        shim.launch_kernel(lambda: held)
        raise ValueError("the handler failed after its launch")

    rt.register_function(_fn("bad", bad))
    rt.register_function(_fn("f", lambda shim, request: shim.launch_kernel(
        lambda: Held(ready=True))))
    with pytest.raises((ValueError, RuntimeError)):
        rt.submit(Request(function_name="bad")).result(timeout=30)
    gate = rt.executor.gate
    assert not gate._lock.locked()
    # the next call is enqueued at once beside the failed call's program...
    rt.submit(Request(function_name="f")).result(timeout=30)
    overlapped = rt.telemetry.snapshot()[-1].launch_overlapped
    assert overlapped is (where == "after_launch")
    # ...and once that program finishes, its place is free again
    held.done.set()
    rt.submit(Request(function_name="f")).result(timeout=30)
    assert rt.telemetry.snapshot()[-1].launch_overlapped is False
    assert not gate._inflight and not gate._lock.locked()
    recs = [r for r in rt.telemetry.snapshot() if r.error is None]
    _assert_compute_split(recs)


class Failed(Held):
    """A program's output whose computation failed on the device."""

    def block_until_ready(self):
        raise RuntimeError("the program failed on the device")


def test_a_program_failing_on_the_device_fails_no_other_launch():
    executor = KernelExecutor()
    executor.launch(Failed, (), {}, serialize=True)
    executor.launch(Held, (), {}, serialize=True)
    # waiting for room behind the failed program does not raise here
    out, waits = executor.launch(lambda: Held(ready=True), (), {},
                                 serialize=True)
    assert out.is_ready() and waits.overlapped is True


def test_many_threads_never_leave_more_than_one_program_queued():
    """Stress: more launching threads than cores, a short switch interval,
    and a device thread that finishes programs in order; at every enqueue
    at most one earlier program is unfinished, and every launch returns."""
    executor = KernelExecutor()
    device_q: "queue.Queue[Held]" = queue.Queue()
    unfinished_at_enqueue = []

    def device():
        while (out := device_q.get()) is not None:
            time.sleep(0.0002)
            out.done.set()

    def program():
        unfinished_at_enqueue.append(sum(not o.is_ready() for o in created))
        out = Held()
        created.append(out)
        device_q.put(out)
        return out

    created = []
    dev = threading.Thread(target=device)
    dev.start()
    switch = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        with ThreadPoolExecutor(max_workers=4 * (os.cpu_count() or 1)) as pool:
            futs = [pool.submit(executor.launch, program, (), {},
                                serialize=True) for _ in range(600)]
            outs = [f.result(timeout=60)[0] for f in futs]
    finally:
        sys.setswitchinterval(switch)
        device_q.put(None)
        dev.join(timeout=30)
    assert not dev.is_alive()
    assert len(unfinished_at_enqueue) == len(outs) == 600
    assert max(unfinished_at_enqueue) <= QUEUED_BEHIND_RUNNING
    assert all(o.is_ready() for o in outs)


def _served_logits(serialize_compute):
    """Six concurrent invocations of a served model: their records and
    returned logits, by request seed."""
    rt = SageRuntime("sage", time_scale=0.0, max_workers=8,
                     serialize_compute=serialize_compute)
    rt.sage_init()
    try:
        fn = make_model_function(rt.db, "f", arch="qwen2.5-3b", seed=7)
        rt.register_function(fn)
        reqs = [make_request(rt.db, fn, seed=s) for s in range(6)]
        for fut in [rt.submit(r) for r in reqs]:
            fut.result(timeout=120)
        recs = {r.request_id: r for r in rt.telemetry.snapshot()}
        return ([recs[r.uuid] for r in reqs],
                [np.asarray(rt.db.fetch(recs[r.uuid].result)) for r in reqs])
    finally:
        rt.shutdown()


def test_gated_launches_return_the_ungated_logits_bit_for_bit():
    recs, gated = _served_logits(True)
    _, ungated = _served_logits(False)
    assert all(r.error is None for r in recs)
    assert all(r.launch_overlapped in (True, False) for r in recs)
    _assert_compute_split(recs)
    for a, b in zip(gated, ungated):
        assert a.shape == (1, 8) and np.array_equal(a, b)


def _sim_record():
    gw = Gateway(backend="sim", policy="sage")
    gw.register(FunctionSpec(name="f", arch="qwen2.5-3b"))
    return gw.invoke("f")


def _runtime_record(**kw):
    rt = SageRuntime("sage", time_scale=0.0, max_workers=4, **kw)
    rt.sage_init()
    try:
        rt.register_function(_fn("f", lambda shim, request: shim.launch_kernel(
            lambda: Held(ready=True))))
        rt.submit(Request(function_name="f")).result(timeout=30)
        return rt.telemetry.snapshot()[-1]
    finally:
        rt.shutdown()


@pytest.mark.parametrize("make", [
    _sim_record,
    lambda: _runtime_record(serialize_compute=False),
    lambda: _runtime_record(compute="shared"),
], ids=["simulator", "no_lock", "compute_plane"])
def test_launch_overlapped_is_unset_off_the_gated_path(make):
    rec = make()
    assert rec.error is None
    assert rec.launch_overlapped is None
