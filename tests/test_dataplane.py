"""Hardened async data plane: bounded loader pool, failure propagation,
OOM backpressure, cancellation (no accounting leaks) — on the threaded
daemon/runtime AND the virtual-time simulator twin (docs/dataplane.md)."""
import threading
import time

import numpy as np
import pytest

from repro.core.clock import RealClock
from repro.core.daemon import DataLoadError, MemoryDaemon, Tier
from repro.core.datapath import DataPaths
from repro.core.request import Data, DataType, Request
from repro.core.simulator import SimFunction, Simulator
from repro.core.profiles import PROFILES
from repro.data.database import Database

MB = 1 << 20


def _daemon(cap_mb=1024, db=None, **kw):
    db = db or Database()
    paths = DataPaths.make(db_bw=1e12, pcie_bw=1e12)  # near-instant for tests
    return MemoryDaemon(paths, db, device_capacity=cap_mb * MB, **kw), db


def _wreq(fn="f", w_mb=8, db=None):
    """Request with one writable datum (freed fully on release)."""
    req = Request(function_name=fn)
    key = f"{fn}/in/{req.uuid}"
    if db is not None:
        db.put(key, np.zeros(1, np.uint8), size=w_mb * MB)
    req.in_data = [Data(key=key, size=w_mb * MB, dtype=DataType.WRITABLE)]
    return req


class FaultyDB(Database):
    """Database whose fetch always faults."""

    def fetch(self, key, broker=None, *, scale: float = 1.0):
        raise IOError(f"simulated database fault for {key}")


class SlowCountingDB(Database):
    """Database that tracks concurrent fetches (the db-path instrumentation
    for the loader-concurrency bound)."""

    def __init__(self, delay: float = 0.05):
        super().__init__()
        self.delay = delay
        self._c = threading.Lock()
        self.cur = 0
        self.max_concurrent = 0

    def fetch(self, key, broker=None, *, scale: float = 1.0):
        with self._c:
            self.cur += 1
            self.max_concurrent = max(self.max_concurrent, self.cur)
        try:
            time.sleep(self.delay)
            return super().fetch(key, broker, scale=scale)
        finally:
            with self._c:
                self.cur -= 1


# ---------------------------------------------------------------------------
# failure propagation
# ---------------------------------------------------------------------------


def test_db_fault_propagates_as_dataloaderror():
    d, _ = _daemon(db=FaultyDB())
    req = _wreq(db=None)
    h = d.prepare(req)[req.in_data[0].key]
    with pytest.raises(DataLoadError) as ei:
        h.wait(5)  # seed behavior: hung forever here
    assert isinstance(ei.value.cause, IOError)
    assert d.stats["load_failures"] == 1
    assert d.device_used == 0 and d.host_used == 0


def test_oom_past_deadline_fails_instead_of_hanging():
    d, db = _daemon(cap_mb=4, load_timeout_s=0.3)
    req = _wreq(w_mb=8, db=db)  # 8 MB datum can never fit in 4 MB
    h = d.prepare(req)[req.in_data[0].key]
    t0 = time.monotonic()
    with pytest.raises(DataLoadError):
        h.wait(10)
    assert time.monotonic() - t0 < 5.0
    assert d.stats["load_failures"] == 1
    assert d.device_used == 0 and d.host_used == 0
    # the failed entry is not resurrected as a shared hit
    assert h.entry.tier is Tier.FAILED


def test_failed_handle_is_not_ready():
    d, _ = _daemon(db=FaultyDB())
    req = _wreq()
    h = d.prepare(req)[req.in_data[0].key]
    h.entry.ready.wait(5)
    assert not h.is_ready()


# ---------------------------------------------------------------------------
# OOM backpressure: waiting loads are admitted when memory frees up
# ---------------------------------------------------------------------------


def test_load_blocked_on_oom_admitted_after_release():
    d, db = _daemon(cap_mb=10, load_timeout_s=5.0)
    ra = _wreq(fn="a", w_mb=8, db=db)
    ha = d.prepare(ra)[ra.in_data[0].key]
    ha.wait(5)
    assert d.device_used == 8 * MB

    rb = _wreq(fn="b", w_mb=8, db=db)
    hb = d.prepare(rb)[rb.in_data[0].key]
    # b cannot be admitted while a holds the device
    threading.Timer(0.25, lambda: d.release(ra, {ra.in_data[0].key: ha})).start()
    assert hb.wait(10) is not None  # admitted after a's release
    assert d.stats["oom_retries"] >= 1
    d.release(rb, {rb.in_data[0].key: hb})
    assert d.device_used == 0 and d.host_used == 0


# ---------------------------------------------------------------------------
# cancellation: release() of a still-loading writable entry
# ---------------------------------------------------------------------------


def test_release_while_loading_cancels_without_leak():
    db = SlowCountingDB(delay=0.2)
    d, _ = _daemon(db=db)
    req = _wreq(db=db)
    handles = d.prepare(req)
    # release immediately: the loader is still in the db fetch
    d.release(req, handles)
    h = handles[req.in_data[0].key]
    with pytest.raises(DataLoadError):
        h.wait(5)
    deadline = time.monotonic() + 5
    while (d.device_used or d.host_used) and time.monotonic() < deadline:
        time.sleep(0.01)
    assert d.device_used == 0 and d.host_used == 0
    assert d.stats["load_cancellations"] == 1


# ---------------------------------------------------------------------------
# bounded loader concurrency (db/PCIe path instrumentation)
# ---------------------------------------------------------------------------


def test_prepare_after_shutdown_resolves_synchronously():
    d, db = _daemon()
    d.shutdown()
    req = _wreq(db=db)
    h = d.prepare(req)[req.in_data[0].key]
    assert h.wait(5) is not None  # degraded to inline load, never parked


def test_unpooled_daemon_still_propagates_failures():
    # baseline platforms run with pooled=False (per-load threads); the
    # failure/cancellation contract is identical
    d, _ = _daemon(db=FaultyDB(), pooled=False)
    req = _wreq()
    h = d.prepare(req)[req.in_data[0].key]
    with pytest.raises(DataLoadError):
        h.wait(5)
    assert d.device_used == 0 and d.host_used == 0


def test_loader_concurrency_never_exceeds_pool_size():
    db = SlowCountingDB(delay=0.05)
    d, _ = _daemon(db=db, loader_threads=3)
    reqs = [_wreq(fn=f"f{i}", w_mb=1, db=db) for i in range(10)]
    handles = [d.prepare(r)[r.in_data[0].key] for r in reqs]
    for h in handles:
        h.wait(10)
    assert db.max_concurrent <= 3
    assert d.max_inflight_loads <= 3
    assert d.max_inflight_loads >= 2  # the pool actually ran concurrently


# ---------------------------------------------------------------------------
# burst stress: capacity below the working set, N concurrent submits —
# every future resolves (success after backpressure/eviction OR
# DataLoadError); accounting returns to the pre-burst baseline
# ---------------------------------------------------------------------------


def test_burst_under_capacity_no_hang_no_leak():
    db = Database()
    d, _ = _daemon(cap_mb=20, db=db, loader_threads=4, load_timeout_s=3.0)
    base_dev, base_host = d.device_used, d.host_used
    n = 12
    reqs = [_wreq(fn=f"f{i}", w_mb=8, db=db) for i in range(n)]  # 96 MB >> 20
    results = [None] * n

    def run(i):
        req = reqs[i]
        handles = d.prepare(req)
        try:
            handles[req.in_data[0].key].wait(15)
            results[i] = "ok"
        except DataLoadError:
            results[i] = "failed"
        finally:
            d.release(req, handles)

    threads = [threading.Thread(target=run, args=(i,)) for i in range(n)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
        assert not t.is_alive(), "a Handle.wait() hung past its timeout"
    assert all(r in ("ok", "failed") for r in results)
    assert results.count("ok") >= 2  # backpressure admitted at least the 2 that fit
    # cancellation/rollback may lag release by one loader checkpoint
    deadline = time.monotonic() + 10
    while (d.device_used != base_dev or d.host_used != base_host) \
            and time.monotonic() < deadline:
        time.sleep(0.02)
    assert d.device_used == base_dev
    assert d.host_used == base_host


def test_runtime_burst_errors_surface_in_telemetry():
    """Engine layer: loader failures land in InvocationRecord.error and the
    future raises — the runtime pool never deadlocks on a dead loader."""
    from repro.core.runtime import SageRuntime
    from repro.core.functions import make_model_function, make_request

    rt = SageRuntime("sage", time_scale=0.0, exit_ttl=30.0,
                     device_capacity=2048 * MB, load_timeout_s=2.0)
    rt.sage_init()
    # declared working set far above device capacity -> admission can never
    # succeed; the invocation must FAIL (typed), not hang
    fn = make_model_function(rt.db, "big", arch="qwen2.5-3b",
                             declared_ro_bytes=8192 * MB)
    rt.register_function(fn)
    fut = rt.submit(make_request(rt.db, fn))
    with pytest.raises(DataLoadError):
        fut.result(timeout=60)
    assert rt.telemetry.error_count() == 1
    assert "DataLoadError" in rt.telemetry.errors()[0].error
    rt.shutdown()


# ---------------------------------------------------------------------------
# virtual-time twin: same bound, same failure semantics
# ---------------------------------------------------------------------------


def test_simulator_loader_bound_enforced():
    sim = Simulator("sage-nr", loader_threads=2)  # NR: every load is private
    f = SimFunction(PROFILES["resnet50"])
    sim.register(f)
    for i in range(12):
        sim.submit(f.name, 0.001 * i)
    sim.run(until=600.0)
    node = sim.nodes[0]
    assert sim.completed == 12
    assert node.max_inflight_loads <= 2
    assert node.max_inflight_loads >= 2  # the gate actually saturated


def test_simulator_failure_semantics_mirror_daemon():
    # capacity below one invocation's working set: the twin must resolve
    # every arrival as completed-or-failed (error recorded), never stuck
    sim = Simulator("fixedgsl", capacity=256 << 20, load_timeout_s=1.0)
    f = SimFunction(PROFILES["bert"])  # ~1.7 GB slot >> 256 MB
    sim.register(f)
    for i in range(4):
        sim.submit(f.name, 0.001 * i)
    sim.run(until=600.0)
    assert sim.failed == 4 and sim.completed == 0
    errs = sim.telemetry.errors()
    assert len(errs) == 4
    assert all("DataLoadError" in r.error for r in errs)
    assert all(r.end_t is not None for r in errs)
    node = sim.nodes[0]
    assert node.used == 0  # failed reservations hold nothing


def test_simulator_backpressure_admits_when_memory_frees():
    # two invocations with PRIVATE working sets (NR mode), device fits one:
    # the second waits for the first's release, then completes — no failure
    sim = Simulator("sage-nr", capacity=2 << 30, exit_ttl=0.5, load_timeout_s=300.0)
    f = SimFunction(PROFILES["bert"])
    sim.register(f)
    sim.submit(f.name, 0.0)
    sim.submit(f.name, 0.01)
    sim.run(until=900.0)
    assert sim.completed == 2 and sim.failed == 0


# ---------------------------------------------------------------------------
# host-tier admission: host_capacity is enforced, not advisory
# ---------------------------------------------------------------------------


def test_host_overcommit_fails_typed_no_leak():
    d, db = _daemon(host_capacity=4 * MB)
    req = _wreq(w_mb=8, db=db)  # 8 MB can never fit the 4 MB host tier
    h = d.prepare(req)[req.in_data[0].key]
    with pytest.raises(DataLoadError, match="host admission"):
        h.wait(5)
    assert d.stats["load_failures"] == 1
    assert d.host_used == 0 and d.device_used == 0
    assert h.entry.tier is Tier.FAILED


def test_host_admission_evicts_refcount0_host_entries():
    db = Database()
    d, _ = _daemon(db=db, host_capacity=12 * MB)
    # fn a: 8 MB read-only entry, demoted to the HOST tier (refcount 0)
    ra = Request(function_name="a")
    db.put("a/w", np.zeros(1, np.uint8), size=8 * MB)
    ra.in_data = [Data(key="a/w", size=8 * MB, dtype=DataType.READ_ONLY)]
    ha = d.prepare(ra)["a/w"]
    ha.wait(5)
    d.release(ra, {"a/w": ha})
    d.demote_to_host("a")
    assert ha.entry.tier is Tier.HOST and d.host_used == 8 * MB
    # fn b needs 8 MB of host: a's idle host copy must be evicted
    rb = _wreq(fn="b", w_mb=8, db=db)
    hb = d.prepare(rb)[rb.in_data[0].key]
    assert hb.wait(5) is not None
    assert d.stats["host_evictions"] == 1
    assert ha.entry.tier is Tier.DROPPED
    assert d.host_used == 8 * MB  # only b's bytes remain
    d.release(rb, {rb.in_data[0].key: hb})
    assert d.host_used == 0 and d.device_used == 0


def test_simulator_host_admission_mirrors_daemon():
    # the twin enforces the same host ceiling on the db->host leg: a
    # working set above host_capacity fails typed, and an idle host-state
    # shared-RO copy is evicted to make room for a new load
    sim = Simulator("sage", host_capacity=1 << 30, load_timeout_s=5.0)
    f = SimFunction(PROFILES["bert"])  # 1282 MB RO > 1 GiB host tier
    sim.register(f)
    sim.submit(f.name, 0.0)
    sim.run(until=600.0)
    assert sim.failed == 1
    assert "DataLoadError" in sim.telemetry.errors()[0].error
    assert sim.nodes[0].host_used == 0
    sim.nodes[0]._advance_ladders()  # walk the warm ctx off the exit ladder
    assert sim.nodes[0].used == 0

    # eviction: resnet50's host copy (demoted at stage 2) is dropped when
    # bert needs the room (bert peak host ~1343 MB + resnet's 98 MB > 1400)
    sim2 = Simulator("sage", host_capacity=1400 << 20, load_timeout_s=60.0)
    small = SimFunction(PROFILES["resnet50"])  # ~98 MB RO
    big = SimFunction(PROFILES["bert"])        # ~1282 MB RO
    sim2.register(small)
    sim2.register(big)
    sim2.submit(small.name, 0.0)
    sim2.submit(big.name, 40.0)  # small's RO is host-demoted (stage 2) by then
    sim2.run(until=700.0)
    node = sim2.nodes[0]
    assert sim2.completed == 2 and sim2.failed == 0
    assert node.host_evictions == 1
    assert node.ro_state[small.name] == "none"  # host copy was evicted


# ---------------------------------------------------------------------------
# alloc(): shim cudaMalloc rides the same backpressure admission path
# ---------------------------------------------------------------------------


def test_alloc_waits_with_backpressure_instead_of_raising():
    from repro.core.daemon import OutOfDeviceMemory

    d, db = _daemon(cap_mb=10, load_timeout_s=5.0)
    ra = _wreq(fn="a", w_mb=8, db=db)
    ha = d.prepare(ra)[ra.in_data[0].key]
    ha.wait(5)
    # device full: a shim cudaMalloc under transient pressure must WAIT for
    # the release (seed behavior: immediate OutOfDeviceMemory)
    threading.Timer(0.25, lambda: d.release(ra, {ra.in_data[0].key: ha})).start()
    rb = Request(function_name="b")
    hb = d.alloc(rb, "b/scratch", 8 * MB)
    assert hb.is_ready() and d.device_used == 8 * MB
    assert d.stats["oom_retries"] >= 1
    d.release(rb, {"b/scratch": hb})
    assert d.device_used == 0

    # past the deadline it still fails typed (OutOfDeviceMemory), promptly
    d2, _ = _daemon(cap_mb=4, load_timeout_s=0.3)
    t0 = time.monotonic()
    with pytest.raises(OutOfDeviceMemory):
        d2.alloc(Request(function_name="c"), "c/scratch", 8 * MB)
    assert time.monotonic() - t0 < 5.0
    assert d2.device_used == 0


# ---------------------------------------------------------------------------
# stats: loads/bytes_loaded are counted on COMPLETION, not at submit
# ---------------------------------------------------------------------------


def test_bytes_loaded_counted_on_completion_only():
    # failed load: nothing counted
    d, _ = _daemon(db=FaultyDB())
    req = _wreq()
    with pytest.raises(DataLoadError):
        d.prepare(req)[req.in_data[0].key].wait(5)
    assert d.stats["loads"] == 0 and d.stats["bytes_loaded"] == 0

    # cancelled load: nothing counted
    db = SlowCountingDB(delay=0.2)
    d2, _ = _daemon(db=db)
    req2 = _wreq(db=db)
    handles = d2.prepare(req2)
    d2.release(req2, handles)  # cancel while the loader is mid-fetch
    with pytest.raises(DataLoadError):
        handles[req2.in_data[0].key].wait(5)
    deadline = time.monotonic() + 5
    while d2.stats["load_cancellations"] == 0 and time.monotonic() < deadline:
        time.sleep(0.01)
    assert d2.stats["loads"] == 0 and d2.stats["bytes_loaded"] == 0

    # successful load: counted exactly once, even across host re-promotion
    db3 = Database()
    d3, _ = _daemon(db=db3)
    r3 = Request(function_name="f")
    db3.put("f/w", np.zeros(1, np.uint8), size=8 * MB)
    r3.in_data = [Data(key="f/w", size=8 * MB, dtype=DataType.READ_ONLY)]
    h3 = d3.prepare(r3)["f/w"]
    h3.wait(5)
    assert d3.stats["loads"] == 1 and d3.stats["bytes_loaded"] == 8 * MB
    d3.release(r3, {"f/w": h3})
    d3.demote_to_host("f")
    r4 = Request(function_name="f")
    r4.in_data = list(r3.in_data)
    h4 = d3.prepare(r4)["f/w"]
    h4.wait(5)  # host -> device promotion: no second count
    assert d3.stats["loads"] == 1 and d3.stats["bytes_loaded"] == 8 * MB
    assert d3.stats["host_promotions"] == 1


# ---------------------------------------------------------------------------
# SLO-aware scheduling: EDF orders the loader queue and the OOM-admission
# wait by (priority, deadline slack, arrival) — on BOTH drivers
# ---------------------------------------------------------------------------


def _slo_req(fn, w_mb, db, deadline_s=None, priority=0):
    req = _wreq(fn=fn, w_mb=w_mb, db=db)
    req.deadline_s = deadline_s
    req.priority = priority
    return req


def test_edf_admission_prefers_tightest_slack_waiter():
    for sched, expect in (("fifo", ["loose", "tight"]),
                          ("edf", ["tight", "loose"])):
        d, db = _daemon(cap_mb=10, load_timeout_s=10.0, scheduler=sched)
        hold = _wreq(fn="hold", w_mb=8, db=db)
        hh = d.prepare(hold)[hold.in_data[0].key]
        hh.wait(5)
        order = []

        def waiter(name, deadline_at, delay):
            def run():
                d.reserve_slot(8 * MB, deadline_at=deadline_at)
                order.append(name)
                d.release_slot(8 * MB)
            t = threading.Thread(target=run)
            threading.Timer(delay, t.start).start()
            return t

        now = time.monotonic()
        # loose-deadline waiter arrives FIRST, tight-deadline second
        threads = [waiter("loose", now + 60.0, 0.0),
                   waiter("tight", now + 1.0, 0.15)]
        time.sleep(0.4)  # both parked on the admission wait
        d.release(hold, {hold.in_data[0].key: hh})
        for t in threads:
            t.join(timeout=10)
            assert not t.is_alive()
        assert order == expect, f"{sched}: admitted in {order}"
        assert d.device_used == 0


def test_small_waiter_backfills_behind_blocked_big_head():
    # a huge parked head must not make a small request time out while the
    # memory it cannot use sits free: the small waiter backfills (without
    # eviction) under BOTH schedulers
    for sched in ("fifo", "edf"):
        d, db = _daemon(cap_mb=20, load_timeout_s=1.0, scheduler=sched)
        hold = _wreq(fn="hold", w_mb=10, db=db)
        hh = d.prepare(hold)[hold.in_data[0].key]
        hh.wait(5)  # 10 MB free remain
        # big head: needs 16 MB, can only ever fit after hold releases
        big_done = threading.Event()

        def big():
            try:
                d.reserve_slot(16 * MB, timeout=5.0)
                d.release_slot(16 * MB)
            finally:
                big_done.set()

        threading.Thread(target=big).start()
        time.sleep(0.15)  # big is parked at the head of the waiter heap
        t0 = time.monotonic()
        d.reserve_slot(8 * MB)  # fits in the free 10 MB: backfills now
        assert time.monotonic() - t0 < 0.5, f"{sched}: backfill was blocked"
        d.release_slot(8 * MB)
        d.release(hold, {hold.in_data[0].key: hh})
        assert big_done.wait(10)
        assert d.device_used == 0


def test_edf_loader_queue_orders_by_deadline():
    class OrderDB(Database):
        def __init__(self):
            super().__init__()
            self.order = []

        def fetch(self, key, broker=None, *, scale: float = 1.0):
            self.order.append(key.split("/")[0])
            time.sleep(0.15)
            return super().fetch(key, broker, scale=scale)

    for sched, expect in (("fifo", ["loose", "tight"]),
                          ("edf", ["tight", "loose"])):
        db = OrderDB()
        d, _ = _daemon(db=db, loader_threads=1, scheduler=sched)
        first = _slo_req("first", 1, db)  # occupies the single worker
        d.prepare(first)
        time.sleep(0.05)
        loose = _slo_req("loose", 1, db, deadline_s=60.0)
        tight = _slo_req("tight", 1, db, deadline_s=1.0)
        hl = d.prepare(loose)[loose.in_data[0].key]  # queued first
        ht = d.prepare(tight)[tight.in_data[0].key]  # queued second
        hl.wait(10)
        ht.wait(10)
        assert db.order[0] == "first"
        assert db.order[1:] == expect, f"{sched}: ran in {db.order}"
        d.shutdown()


def _mk_gpu_fn(name):
    from repro.core.engine import GPUFunction

    def handler(shim, request):
        for dd in request.in_data:
            shim.sage_load_to_gpu(dd.key).wait(30)

    return GPUFunction(name=name, handler=handler,
                       context_builder=lambda: object(),
                       context_bytes=1 * MB, container_s=0.0, cpu_ctx_s=0.0)


def _runtime_slo_replay(scheduler):
    """Contended mixed-deadline trace on the REAL runtime: one loader
    thread, four loose-deadline 500 MB loads queued ahead of one
    tight-deadline 16 MB load."""
    from repro.core.runtime import SageRuntime

    rt = SageRuntime("sage", loader_threads=1, scheduler=scheduler,
                     serialize_compute=False)
    rt.sage_init()
    for i in range(4):
        rt.register_function(_mk_gpu_fn(f"batch{i}"))
    rt.register_function(_mk_gpu_fn("crit"))
    futs = [rt.submit(_slo_req(f"batch{i}", 500, rt.db, deadline_s=30.0))
            for i in range(4)]
    time.sleep(0.1)  # batches are queued on the single loader worker
    futs.append(rt.submit(_slo_req("crit", 16, rt.db,
                                   deadline_s=1.2, priority=1)))
    for f in futs:
        f.result(timeout=60)
    rate = rt.telemetry.slo_miss_rate()
    assert rt.daemon.max_inflight_loads <= 1  # pool bound holds under EDF too
    # zero leakage after drain: writable bytes all returned; only the live
    # instances' contexts remain on device
    deadline = time.monotonic() + 5
    while (rt.daemon.device_used != rt.daemon.context_bytes_used
           or rt.daemon.host_used != 0) and time.monotonic() < deadline:
        time.sleep(0.02)
    assert rt.daemon.device_used == rt.daemon.context_bytes_used
    assert rt.daemon.host_used == 0
    rt.shutdown()
    return rate


def test_runtime_edf_strictly_beats_fifo_on_mixed_deadlines():
    fifo = _runtime_slo_replay("fifo")
    edf = _runtime_slo_replay("edf")
    assert fifo > 0.0   # FIFO makes the tight request wait out its deadline
    assert edf < fifo   # EDF admits it first: strictly fewer misses


def _sim_slo_replay(scheduler):
    """The same contended mixed-deadline shape on the virtual-time twin."""
    from repro.core.profiles import FunctionProfile

    sim = Simulator("sage", loader_threads=1, scheduler=scheduler)
    names = []
    for i in range(4):
        p = FunctionProfile(f"batch{i}", "custom", 1.0, 0.0, 500.0, 5.0)
        sim.register(SimFunction(p))
        names.append(p.name)
    sim.register(SimFunction(FunctionProfile("crit", "custom", 1.0, 0.0, 16.0, 5.0)))
    for i, n in enumerate(names):
        sim.submit(n, 0.001 * i, deadline_s=30.0, priority=0)
    sim.submit("crit", 0.05, deadline_s=1.2, priority=1)
    sim.run(until=600.0)
    node = sim.nodes[0]
    assert sim.completed == 5 and sim.failed == 0
    assert node.max_inflight_loads <= 1
    assert node.host_used == 0  # private bytes left the host tier at finish
    node._advance_ladders()  # walk idle instances off the exit ladder
    return sim.telemetry.slo_miss_rate()


def test_simulator_edf_strictly_beats_fifo_on_mixed_deadlines():
    fifo = _sim_slo_replay("fifo")
    edf = _sim_slo_replay("edf")
    assert fifo > 0.0
    assert edf < fifo


# ----------------------------------------------------------------------
# retry budget under node eviction: a request whose node dies mid-load
# either lands on a healthy node within its remaining budget or fails
# with the typed error — with exact device/host accounting either way,
# on BOTH drivers (docs/resilience.md)
# ----------------------------------------------------------------------
def _sim_crash_mid_load(max_retries):
    """Single cold request, 2 nodes; the node it lands on (determined by
    a fault-free probe run with the same seed) crashes 0.1s in — squarely
    inside the ~0.6s db leg of the 1 GB read-only load."""
    from repro.core.faults import FaultPlan, NodeCrash
    from repro.core.profiles import FunctionProfile

    def build(faults=None):
        sim = Simulator("sage", n_nodes=2, seed=11, faults=faults,
                        eviction=faults is not None)
        sim.register(SimFunction(
            FunctionProfile("f", "custom", 16.0, 1024.0, 8.0, 50.0)))
        sim.submit("f", 0.0, request_id="r0", max_retries=max_retries)
        return sim

    probe = build()
    probe.run(120.0)
    victim = next(r.node_id for r in probe.telemetry.snapshot()
                  if r.request_id == "r0")
    sim = build(FaultPlan([NodeCrash(victim, at_s=0.1)], seed=11))
    sim.run(120.0)
    rec = next(r for r in sim.telemetry.snapshot()
               if r.request_id == "r0" and not r.dropped)
    dead = next(n for n in sim.nodes if n.name == victim)
    healthy = next(n for n in sim.nodes if n.name != victim)
    assert not dead.healthy
    assert dead.used == 0 and dead.host_used == 0  # exact: nothing leaks
    assert dead.inflight_loads == 0
    return rec, victim, healthy


def test_sim_retry_budget_lands_on_healthy_node():
    rec, victim, healthy = _sim_crash_mid_load(max_retries=1)
    assert rec.error is None
    assert rec.redispatches == 1
    assert rec.node_id != victim
    # exact accounting on the rescuer: ctx + ro on device, the ro host
    # copy retained, the 8 MB writable payload fully drained
    assert healthy.used == (16 + 1024) * MB
    assert healthy.host_used == 1024 * MB


def test_sim_retry_budget_exhausted_fails_typed():
    rec, _, healthy = _sim_crash_mid_load(max_retries=0)
    assert rec.error_class == "node_lost"
    assert "NodeLostError" in rec.error
    assert rec.redispatches == 0
    # fail-fast: the request never reached the healthy node
    assert healthy.used == 0 and healthy.host_used == 0


def _runtime_crash_mid_load(max_retries):
    """The same shape on the threaded runtime: crash the node the gateway
    picked while its 512 MB read-only load is on the db leg (~0.3s)."""
    from repro.api.gateway import Gateway
    from repro.api.spec import FunctionSpec
    from repro.core.daemon import NodeLostError

    gw = Gateway(backend="runtime", n_nodes=2, seed=0, eviction=True)
    try:
        gw.register(FunctionSpec(
            name="f", read_only_bytes=512 * MB, writable_bytes=8 * MB,
            context_bytes=16 * MB, compute_ms=20.0))
        h = gw.invoke_async("f", max_retries=max_retries)
        victim = gw._nodes[h._node_idx]
        time.sleep(0.1)  # let the load reach the db leg
        assert not h._done.is_set()  # still in flight when the node dies
        victim.crash()
        if max_retries == 0:
            with pytest.raises(NodeLostError):
                h.wait(timeout=60)
            rec = h.wait(timeout=60, strict=False)
            assert rec.error_class == "node_lost"
            assert "NodeLostError" in rec.error
            assert rec.redispatches == 0
            assert gw.resilience_stats()["redispatches"] == 0
        else:
            rec = h.wait(timeout=60)
            assert rec.error is None
            assert rec.redispatches == 1
            assert rec.node_id != victim.node_id
        # exact accounting: the dead node holds nothing; on success the
        # rescuer holds ctx + ro on device and the ro host copy, with the
        # writable payload fully drained — on fail-fast it holds nothing
        mu = victim.memory_usage()
        assert mu["device_used"] == 0 and mu["host_used"] == 0
        other = next(n for n in gw._nodes if n is not victim)
        want_dev = 0 if max_retries == 0 else (
            other.daemon.context_bytes_used + 512 * MB)
        want_host = 0 if max_retries == 0 else 512 * MB
        deadline = time.monotonic() + 5
        while (other.daemon.device_used != want_dev
               or other.daemon.host_used != want_host) \
                and time.monotonic() < deadline:
            want_dev = 0 if max_retries == 0 else (
                other.daemon.context_bytes_used + 512 * MB)
            time.sleep(0.02)
        assert other.daemon.device_used == want_dev
        assert other.daemon.host_used == want_host
    finally:
        gw.shutdown()


def test_runtime_retry_budget_lands_on_healthy_node():
    _runtime_crash_mid_load(max_retries=1)


def test_runtime_retry_budget_exhausted_fails_typed():
    _runtime_crash_mid_load(max_retries=0)


# ----------------------------------------------------------------------
# hedge-loser cancellation (docs/resilience.md, "Gray failures"): the
# cancelled twin unwinds byte-exactly through the same release chain a
# failed load uses — nothing held, nothing double-counted on the link
# ----------------------------------------------------------------------
def _hedge_cancel_gateway():
    from repro.api.gateway import Gateway
    from repro.api.spec import FunctionSpec

    gw = Gateway(backend="runtime", n_nodes=1, seed=0)
    # context ~0.3s and writable ~0.45s on the default link: the pre-kernel
    # cancel checkpoint fires while the writable leg is still streaming
    gw.register(FunctionSpec(
        name="f", read_only_bytes=64 * MB, writable_bytes=768 * MB,
        context_bytes=512 * MB, compute_ms=20.0))
    return gw


def test_runtime_hedge_cancel_mid_load_byte_exact():
    """Cancelled mid-load, the loser leaves EXACTLY the residency a
    successful invocation leaves (zero delta on device/host), holds no
    loader slot, and the link counted only the loads that completed."""
    from repro.core.slowness import HedgedError

    ctl = _hedge_cancel_gateway()  # control: same spec run to completion
    try:
        ctl.invoke("f", seed=0)
        want = ctl._nodes[0].memory_usage()
        ctl_bytes = ctl._nodes[0].daemon.stats["bytes_loaded"]
    finally:
        ctl.shutdown()
    assert want["device_used"] > 0

    gw = _hedge_cancel_gateway()
    try:
        node = gw._nodes[0]
        from repro.api.gateway import DEFAULT_INPUT_BYTES
        req = gw._build_request("f", 0, seed=0,
                                input_bytes=DEFAULT_INPUT_BYTES,
                                deadline_s=None, priority=0)
        req.hedge_cancel = threading.Event()
        fut = node.submit(req)
        time.sleep(0.1)  # context load in flight (~0.3s)
        req.hedge_cancel.set()
        with pytest.raises(HedgedError):
            fut.result(timeout=60)
        rec = node.telemetry.find(req.uuid)
        assert rec is not None and rec.error.startswith("HedgedError")
        assert rec.end_t > 0.0  # finalized, never left half-open
        # zero delta vs the success path: ctx + ro resident, writable and
        # input fully drained, loader slots free
        deadline = time.monotonic() + 5
        while (node.memory_usage() != want
               or node.daemon._pool.in_flight != 0) \
                and time.monotonic() < deadline:
            time.sleep(0.02)
        assert node.memory_usage() == want
        assert node.daemon._pool.in_flight == 0
        # exact link accounting: the db legs that completed (read-only
        # share + input payload) are counted once, the cancelled context
        # leg never lands in the books (completion-only contract), and
        # the totals match the success path byte for byte
        assert node.daemon.stats["bytes_loaded"] == ctl_bytes
        assert ctl_bytes == 64 * MB + DEFAULT_INPUT_BYTES
    finally:
        gw.shutdown()


def test_runtime_hedge_cancel_before_load_loads_nothing():
    """A cancel token already set before the engine starts aborts ahead
    of the instance claim: no slot, no load, no context — every book on
    the node reads exactly zero and the link moved no bytes."""
    from repro.core.slowness import HedgedError

    gw = _hedge_cancel_gateway()
    try:
        node = gw._nodes[0]
        req = gw._build_request("f", 0, seed=0, input_bytes=MB,
                                deadline_s=None, priority=0)
        req.hedge_cancel = threading.Event()
        req.hedge_cancel.set()  # loser before it even started
        fut = node.submit(req)
        with pytest.raises(HedgedError):
            fut.result(timeout=60)
        deadline = time.monotonic() + 5
        while node.daemon._pool.in_flight != 0 \
                and time.monotonic() < deadline:
            time.sleep(0.02)
        mu = node.memory_usage()
        assert mu["device_used"] == 0 and mu["host_used"] == 0
        assert mu["context_bytes"] == 0  # the ensure never ran
        assert node.daemon._pool.in_flight == 0
        assert node.daemon.stats["bytes_loaded"] == 0
    finally:
        gw.shutdown()


# ----------------------------------------------------------------------
# release during batching (docs/compute.md): a member cancelled while
# parked in the batch collector unwinds through the SAME release chain a
# hedge loser uses — the surviving member launches, nothing leaks
# ----------------------------------------------------------------------
def test_runtime_hedge_cancel_while_parked_in_batch_no_leak():
    from repro.api.gateway import DEFAULT_INPUT_BYTES, Gateway
    from repro.api.spec import FunctionSpec
    from repro.core.slowness import HedgedError

    def make_gw():
        gw = Gateway(backend="runtime", n_nodes=1, seed=0,
                     compute={"max_batch": 4, "batch_window_s": 1.0})
        gw.register(FunctionSpec(
            name="f", read_only_bytes=8 * MB, writable_bytes=8 * MB,
            context_bytes=8 * MB, compute_ms=20.0))
        return gw

    def pair(gw, cancel_second):
        """Two concurrent members; optionally cancel the second while it
        is parked in the open batch. Returns (results, memory, stats)."""
        node = gw._nodes[0]
        reqs, futs = [], []
        for _ in range(2):
            req = gw._build_request("f", 0, seed=0,
                                    input_bytes=DEFAULT_INPUT_BYTES,
                                    deadline_s=None, priority=0)
            req.hedge_cancel = threading.Event()
            reqs.append(req)
            futs.append(node.submit(req))
        if cancel_second:
            # wait until both are parked in the collector (batch open
            # with 2 members), then cancel one mid-park
            plane = node._plane
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                with plane._cond:
                    b = plane._open.get("f")
                    if b is not None and len(b.requests) == 2:
                        break
                time.sleep(0.005)
            reqs[1].hedge_cancel.set()
        outcomes = []
        for fut in futs:
            try:
                fut.result(timeout=60)
                outcomes.append("ok")
            except HedgedError:
                outcomes.append("hedged")
        return outcomes, node.memory_usage(), node

    ctl = make_gw()  # control: the same pair, both run to completion
    try:
        outcomes, want, _ = pair(ctl, cancel_second=False)
        assert outcomes == ["ok", "ok"]
    finally:
        ctl.shutdown()
    assert want["device_used"] > 0

    gw = make_gw()
    try:
        outcomes, mem, node = pair(gw, cancel_second=True)
        assert outcomes == ["ok", "hedged"]
        # the survivor launched solo: its record carries no batch peers
        recs = [r for r in node.telemetry.snapshot() if r.error is None]
        assert len(recs) == 1 and recs[0].batch_size == 1
        # zero delta vs the success path: the cancelled member's claim
        # unwound byte-exactly (no leaked device_used), and the plane
        # holds no slices and no open batch
        deadline = time.monotonic() + 5
        while (node.memory_usage() != want
               or node.daemon._pool.in_flight != 0) \
                and time.monotonic() < deadline:
            time.sleep(0.02)
        assert node.memory_usage() == want
        assert node.daemon._pool.in_flight == 0
        plane = node._plane
        with plane._cond:
            assert plane._free == plane.cfg.slices
            assert not plane._open
    finally:
        gw.shutdown()
