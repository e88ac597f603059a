"""Sharing-aware cluster dispatch (docs/cluster.md): policy scoring, the
residency/pressure snapshot contract, random-dispatch seed regression,
runtime/sim parity of locality assignments, per-request retry budgets, and
the locality-strictly-beats-random acceptance bar on BOTH backends."""
import random
import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))  # benchmarks/

from repro.api import Arrival, FunctionSpec, Gateway, TraceWorkload
from repro.core.daemon import DataLoadError, MemoryDaemon, OutOfDeviceMemory
from repro.core.datapath import DataPaths
from repro.core.dispatch import (
    DISPATCH_POLICIES, NodeSnapshot, choose_node, locality_score,
)
from repro.core.profiles import PROFILES
from repro.core.request import Data, DataType, Request
from repro.core.runtime import ClusterRuntime
from repro.core.simulator import SimFunction, Simulator
from repro.core.telemetry import STAGES, InvocationRecord, Telemetry
from repro.data.database import Database

MB = 1 << 20
GB = 1 << 30


def _snap(node_id="gpu0", tier="none", free=40 * GB, cap=40 * GB,
          pending=0, queue=0, workers=4):
    return NodeSnapshot(node_id=node_id, ro_tier=tier, ro_bytes=0,
                        device_free=free, device_capacity=cap,
                        pending_admissions=pending, loader_queue=queue,
                        loader_threads=workers)


def _wreq(fn="f", w_mb=8, db=None, **kw):
    req = Request(function_name=fn, **kw)
    key = f"{fn}/in/{req.uuid}"
    if db is not None:
        db.put(key, np.zeros(1, np.uint8), size=w_mb * MB)
    req.in_data = [Data(key=key, size=w_mb * MB, dtype=DataType.WRITABLE)]
    return req


def _daemon(cap_mb=1024, db=None, **kw):
    db = db or Database()
    paths = DataPaths.make(db_bw=1e12, pcie_bw=1e12)
    return MemoryDaemon(paths, db, device_capacity=cap_mb * MB, **kw), db


# ---------------------------------------------------------------------------
# policy scoring (pure units)
# ---------------------------------------------------------------------------

def test_locality_prefers_residency_tier_order():
    snaps = [_snap("gpu0", "none"), _snap("gpu1", "host"),
             _snap("gpu2", "device"), _snap("gpu3", "loading")]
    assert choose_node("locality", snaps) == 2  # device wins, index breaks
    # loading counts as much as device (attach to the in-flight stream)
    assert locality_score(snaps[2]) == locality_score(snaps[3])
    assert choose_node("locality", snaps[:2]) == 1  # host beats cold


def test_locality_spills_off_a_saturated_hot_node():
    hot = _snap("gpu0", "device", free=2 * GB, pending=6, queue=12, workers=2)
    cold = _snap("gpu1", "none")
    assert choose_node("locality", [hot, cold]) == 1  # spill-and-warm
    warm_ok = _snap("gpu0", "device", free=30 * GB, queue=1, workers=2)
    assert choose_node("locality", [warm_ok, cold]) == 0  # mild load sticks


def test_locality_cold_functions_spread_by_memory_pressure():
    # no residency anywhere: the emptier node wins, so cold functions
    # spread instead of piling onto node 0
    a = _snap("gpu0", "none", free=20 * GB)
    b = _snap("gpu1", "none", free=39 * GB)
    assert choose_node("locality", [a, b]) == 1


def test_least_loaded_and_tie_breaks_deterministic():
    assert choose_node("least_loaded",
                       [_snap(queue=4), _snap(queue=1), _snap(queue=2)]) == 1
    # full tie: lowest index (stable across both drivers)
    assert choose_node("locality", [_snap(), _snap(), _snap()]) == 0
    assert choose_node("least_loaded", [_snap(), _snap()]) == 0
    # EDF-compatible tie-break: equal score, fewer parked waiters wins
    assert choose_node("locality",
                       [_snap("a", pending=3), _snap("b", pending=0)]) == 1
    with pytest.raises(ValueError):
        choose_node("round_robin", [_snap()])


# ---------------------------------------------------------------------------
# residency/pressure snapshot contract (daemon + sim twin)
# ---------------------------------------------------------------------------

class SlowDB(Database):
    def __init__(self, delay=0.4):
        super().__init__()
        self.delay = delay

    def fetch(self, key, broker=None, *, scale: float = 1.0):
        time.sleep(self.delay)
        return super().fetch(key, broker, scale=scale)


def test_daemon_residency_walks_tiers_and_never_blocks_on_inflight_loads():
    db = SlowDB(delay=0.4)
    d, _ = _daemon(db=db)
    req = Request(function_name="f")
    db.put("f/w", np.zeros(1, np.uint8), size=8 * MB)
    req.in_data = [Data(key="f/w", size=8 * MB, dtype=DataType.READ_ONLY)]
    assert d.residency("f") == ("none", 0)
    h = d.prepare(req)["f/w"]
    # the loader is parked inside the slow fetch: the snapshot must return
    # immediately (lock is only held at loader checkpoints)
    t0 = time.monotonic()
    tier, nbytes = d.residency("f")
    p = d.pressure()
    assert time.monotonic() - t0 < 0.2
    assert tier == "loading" and nbytes == 8 * MB
    assert p["loader_queue"] >= 1
    assert p["device_capacity"] == 1024 * MB
    h.wait(5)
    assert d.residency("f")[0] == "device"
    assert d.pressure()["device_free"] == (1024 - 8) * MB
    d.release(req, {"f/w": h})
    d.demote_to_host("f")
    assert d.residency("f") == ("host", 8 * MB)
    d.drop_host("f")
    assert d.residency("f") == ("none", 0)
    d.shutdown()


def test_daemon_function_entries_rides_per_function_index():
    db = Database()
    d, _ = _daemon(db=db)
    reqs = {}
    for fn in ("a", "b"):
        db.put(f"{fn}/w", np.zeros(1, np.uint8), size=4 * MB)
        req = Request(function_name=fn)
        req.in_data = [Data(key=f"{fn}/w", size=4 * MB,
                            dtype=DataType.READ_ONLY)]
        d.prepare(req)[f"{fn}/w"].wait(5)
        reqs[fn] = req
    assert {e.key for e in d.function_entries("a")} == {"a/w"}
    assert {e.key for e in d.function_entries("b")} == {"b/w"}
    assert d.function_entries("nope") == []
    # exit-ladder actions ride the index (same semantics as the old scan)
    d.release(reqs["a"], {})
    d.demote_to_host("a")
    assert len(d.evictable_entries("a")) == 0  # host tier, not device
    d.drop_host("a")
    # re-preparing a dropped key REPLACES the entry in both maps
    req2 = Request(function_name="a")
    req2.in_data = [Data(key="a/w", size=4 * MB, dtype=DataType.READ_ONLY)]
    d.prepare(req2)["a/w"].wait(5)
    assert len(d.function_entries("a")) == 1
    assert d.function_entries("a")[0].tier.value == "device"
    d.shutdown()


def test_sim_node_snapshot_mirrors_daemon_contract():
    sim = Simulator("sage")
    f = SimFunction(PROFILES["resnet50"])
    sim.register(f)
    node = sim.nodes[0]
    assert node.residency("resnet50") == ("none", 0)
    sim.submit("resnet50", 0.0)
    sim.run(until=0.05)  # mid-load: db/pcie legs still in flight
    assert node.residency("resnet50")[0] == "loading"
    sim.run(until=600.0)
    tier, nbytes = node.residency("resnet50")
    assert tier == "device" and nbytes == f.ro_bytes
    snap = node.dispatch_snapshot("resnet50")
    assert snap.node_id == "gpu0" and snap.ro_tier == "device"
    assert snap.device_free == node.capacity - node.used


# ---------------------------------------------------------------------------
# random dispatch: seeded paper §7.8 behavior is bit-identical
# ---------------------------------------------------------------------------

def test_sim_random_dispatch_reproduces_seeded_stream():
    sim = Simulator("sage", n_nodes=4, seed=3)  # dispatch defaults to random
    assert sim.dispatch == "random"
    sim.register(SimFunction(PROFILES["resnet50"]))
    for i in range(12):
        sim.submit("resnet50", 0.5 * i)
    sim.run(until=600.0)
    got = [r.node_id for r in
           sorted(sim.telemetry.records, key=lambda r: r.arrival_t)]
    rng = random.Random(3)  # the seed's rng.choice(nodes) stream
    assert got == [f"gpu{rng.randrange(4)}" for _ in range(12)]


def test_cluster_random_dispatch_reproduces_seeded_stream():
    from repro.core.engine import GPUFunction

    def mk(name):
        return GPUFunction(name=name, handler=lambda s, r: None,
                           context_builder=lambda: object(),
                           context_bytes=1 * MB, container_s=0.0,
                           cpu_ctx_s=0.0)

    cluster = ClusterRuntime(n_nodes=4, seed=7, database=Database(),
                             serialize_compute=False)
    assert cluster.dispatch == "random"
    cluster.sage_init()
    cluster.register_function(lambda i: mk("f"))
    reqs = [Request(function_name="f") for _ in range(12)]
    futs = [cluster.submit(r) for r in reqs]
    for f in futs:
        f.result(timeout=60)
    tel = cluster.telemetry
    rng = random.Random(7)
    expect = [f"gpu{rng.randrange(4)}" for _ in range(12)]
    got = [tel.find(r.uuid).node_id for r in reqs]
    assert got == expect
    cluster.shutdown()


# ---------------------------------------------------------------------------
# runtime/sim parity: locality yields the same per-node assignments
# ---------------------------------------------------------------------------

def _assignment_counts(tel):
    out = {}
    for r in tel.snapshot():
        out.setdefault(r.function, {}).setdefault(r.node_id, 0)
        out[r.function][r.node_id] += 1
    return out


def test_locality_parity_runtime_vs_sim():
    """One trace + dispatch="locality" on both backends: same per-node
    assignment counts (within tolerance) and identical record schema."""
    specs = [FunctionSpec(name="a", arch="qwen2.5-3b", profile="seq2seq"),
             FunctionSpec(name="b", arch="qwen2.5-3b", profile="seq2seq")]
    trace = TraceWorkload([(0.0, "a"), (0.8, "b"), (1.6, "a"),
                           (2.4, "b"), (3.2, "a"), (4.0, "b")])

    gw_sim = Gateway(backend="sim", policy="sage", n_nodes=2,
                     dispatch="locality")
    for s in specs:
        gw_sim.register(s)
    tel_sim = gw_sim.replay(trace, until_pad=60.0)
    with Gateway(backend="runtime", policy="sage", n_nodes=2,
                 dispatch="locality", time_scale=0.05) as gw_rt:
        for s in specs:
            gw_rt.register(s)
        tel_rt = gw_rt.replay(trace)

    for tel in (tel_sim, tel_rt):
        recs = tel.snapshot()
        assert len(recs) == 6 and all(r.error is None for r in recs)
        # record schema: canonical stages + per-node attribution on every
        # record of BOTH backends
        assert all(set(r.stages) == set(STAGES) for r in recs)
        assert all(r.node_id in ("gpu0", "gpu1") for r in recs)
        assert all(r.dispatch_tier in ("none", "host", "loading", "device")
                   for r in recs)
    counts_sim = _assignment_counts(tel_sim)
    counts_rt = _assignment_counts(tel_rt)
    # same assignments within tolerance: the drivers differ in timing, so
    # allow one invocation per (function, node) cell to disagree
    for fn in ("a", "b"):
        for node in ("gpu0", "gpu1"):
            assert abs(counts_sim[fn].get(node, 0)
                       - counts_rt[fn].get(node, 0)) <= 1, (counts_sim,
                                                            counts_rt)
    # and each function concentrates on ONE node (the locality win)
    for counts in (counts_sim, counts_rt):
        for fn in ("a", "b"):
            assert max(counts[fn].values()) >= 2
    assert tel_sim.dispatch_hit_rate() > 0.5
    assert tel_rt.dispatch_hit_rate() > 0.5


# ---------------------------------------------------------------------------
# per-request retry budget (Request.max_retries)
# ---------------------------------------------------------------------------

def test_daemon_retry_budget_zero_fails_fast():
    d, db = _daemon(cap_mb=10, load_timeout_s=10.0)
    hold = _wreq(fn="hold", w_mb=8, db=db)
    hh = d.prepare(hold)[hold.in_data[0].key]
    hh.wait(5)
    req = _wreq(fn="ff", w_mb=8, db=db, max_retries=0)
    t0 = time.monotonic()
    with pytest.raises(DataLoadError):
        d.prepare(req)[req.in_data[0].key].wait(10)
    # failed typed on the FIRST OOM, long before the 10 s flat deadline
    assert time.monotonic() - t0 < 2.0
    assert d.stats["load_failures"] == 1
    # the holder is untouched and accounting is exact
    d.release(hold, {hold.in_data[0].key: hh})
    assert d.device_used == 0 and d.host_used == 0
    d.shutdown()


def test_daemon_retry_budget_generous_still_admits_after_release():
    d, db = _daemon(cap_mb=10, load_timeout_s=10.0)
    hold = _wreq(fn="hold", w_mb=8, db=db)
    hh = d.prepare(hold)[hold.in_data[0].key]
    hh.wait(5)
    threading.Timer(
        0.25, lambda: d.release(hold, {hold.in_data[0].key: hh})).start()
    req = _wreq(fn="ok", w_mb=8, db=db, max_retries=1000)
    assert d.prepare(req)[req.in_data[0].key].wait(10) is not None
    assert d.stats["oom_retries"] >= 1
    d.release(req, {req.in_data[0].key: hh})
    d.shutdown()


def test_reserve_slot_honors_retry_budget():
    d, db = _daemon(cap_mb=10, load_timeout_s=10.0)
    hold = _wreq(fn="hold", w_mb=8, db=db)
    hh = d.prepare(hold)[hold.in_data[0].key]
    hh.wait(5)
    t0 = time.monotonic()
    with pytest.raises(OutOfDeviceMemory):
        d.reserve_slot(8 * MB, max_retries=0)
    assert time.monotonic() - t0 < 2.0
    d.release(hold, {hold.in_data[0].key: hh})
    assert d.device_used == 0
    d.shutdown()


def test_sim_retry_budget_mirrors_daemon():
    # capacity fits one working set; the default (None) waits out the
    # backpressure and completes — budget 0 fails typed instead
    def run(max_retries):
        sim = Simulator("sage-nr", capacity=2 << 30, exit_ttl=0.5,
                        load_timeout_s=300.0)
        sim.register(SimFunction(PROFILES["bert"]))
        sim.submit("bert", 0.0)
        sim.submit("bert", 0.01, max_retries=max_retries)
        sim.run(until=900.0)
        return sim

    flat = run(None)  # default: unchanged flat-deadline behavior
    assert flat.completed == 2 and flat.failed == 0
    fast = run(0)
    assert fast.completed == 1 and fast.failed == 1
    err = fast.telemetry.errors()[0]
    assert "DataLoadError" in err.error and err.max_retries == 0
    generous = run(500)
    assert generous.completed == 2 and generous.failed == 0


def test_runtime_request_retry_budget_end_to_end():
    """Engine layer: Request.max_retries rides prepare() into the daemon
    and the typed failure lands in telemetry."""
    from repro.core.runtime import SageRuntime

    rt = SageRuntime("sage", device_capacity=10 * MB, load_timeout_s=10.0,
                     serialize_compute=False)
    rt.sage_init()
    from repro.core.engine import GPUFunction

    def handler(shim, request):
        for dd in request.in_data:
            shim.sage_load_to_gpu(dd.key).wait(30)

    fn = GPUFunction(name="f", handler=handler,
                     context_builder=lambda: object(),
                     context_bytes=1 * MB, container_s=0.0, cpu_ctx_s=0.0)
    rt.register_function(fn)
    block = threading.Event()

    def slow_handler(shim, request):
        for dd in request.in_data:
            shim.sage_load_to_gpu(dd.key).wait(30)
        block.wait(20)

    hold_fn = GPUFunction(name="hold", handler=slow_handler,
                          context_builder=lambda: object(),
                          context_bytes=1 * MB, container_s=0.0,
                          cpu_ctx_s=0.0)
    rt.register_function(hold_fn)
    hold = _wreq(fn="hold", w_mb=7, db=rt.db)
    fut_hold = rt.submit(hold)
    deadline = time.monotonic() + 5
    while rt.daemon.device_used < 7 * MB and time.monotonic() < deadline:
        time.sleep(0.01)  # holder's bytes are on device, handler parked
    req = _wreq(fn="f", w_mb=7, db=rt.db, max_retries=0)
    t0 = time.monotonic()
    fut = rt.submit(req)
    with pytest.raises(DataLoadError):
        fut.result(timeout=30)
    assert time.monotonic() - t0 < 5.0
    rec = rt.telemetry.find(req.uuid)
    assert rec.max_retries == 0 and "DataLoadError" in rec.error
    block.set()
    fut_hold.result(timeout=30)
    rt.shutdown()


def test_retry_budget_zero_fails_fast_even_behind_other_waiters():
    """Budget 0 charges the FIRST failed opportunity even when the request
    is queued behind an earlier waiter (non-head) — parity with the sim,
    which fails a budget-0 reservation at its inline reserve() attempt."""
    d, db = _daemon(cap_mb=10, load_timeout_s=10.0)
    hold = _wreq(fn="hold", w_mb=8, db=db)
    hh = d.prepare(hold)[hold.in_data[0].key]
    hh.wait(5)
    head_done = threading.Event()

    def head():  # parks at the head of the waiter heap, budget-less
        try:
            d.reserve_slot(8 * MB, timeout=10.0)
            d.release_slot(8 * MB)
        finally:
            head_done.set()

    threading.Thread(target=head).start()
    time.sleep(0.15)
    t0 = time.monotonic()
    with pytest.raises(OutOfDeviceMemory):
        d.reserve_slot(8 * MB, max_retries=0)  # non-head: still fail-fast
    assert time.monotonic() - t0 < 2.0
    d.release(hold, {hold.in_data[0].key: hh})
    assert head_done.wait(10)
    assert d.device_used == 0
    d.shutdown()


def test_daemon_retry_budget_counts_memory_events_not_poll_slices():
    """A small budget must survive a holder that releases later: only
    admission attempts that follow a memory event consume the budget, not
    the daemon's 50 ms poll wakes (parity with the sim's per-kick count)."""
    d, db = _daemon(cap_mb=10, load_timeout_s=10.0)
    hold = _wreq(fn="hold", w_mb=8, db=db)
    hh = d.prepare(hold)[hold.in_data[0].key]
    hh.wait(5)
    # ~0.6 s of waiting = ~12 poll slices; budget 2 must NOT be consumed
    threading.Timer(
        0.6, lambda: d.release(hold, {hold.in_data[0].key: hh})).start()
    req = _wreq(fn="ok", w_mb=8, db=db, max_retries=2)
    assert d.prepare(req)[req.in_data[0].key].wait(10) is not None
    d.release(req, {req.in_data[0].key: hh})
    d.shutdown()


def test_shared_entry_budget_widened_by_late_attacher():
    """A sharer attaching mid-wait widens the entry's budget and the
    in-flight admission wait must honor it (re-read, not a stale copy)."""
    db = Database()
    d, _ = _daemon(cap_mb=10, db=db, load_timeout_s=10.0)
    hold = _wreq(fn="hold", w_mb=8, db=db)
    hh = d.prepare(hold)[hold.in_data[0].key]
    hh.wait(5)
    db.put("f/w", np.zeros(1, np.uint8), size=8 * MB)

    def ro_req(budget):
        r = Request(function_name="f", max_retries=budget)
        r.in_data = [Data(key="f/w", size=8 * MB, dtype=DataType.READ_ONLY)]
        return r

    tight = ro_req(1)  # one post-memory-event re-admission allowed
    ht = d.prepare(tight)["f/w"]
    time.sleep(0.2)  # loader is parked on the admission wait
    generous = ro_req(None)  # attaches: entry budget widens to None
    hg = d.prepare(generous)["f/w"]
    assert ht.entry is hg.entry and ht.entry.max_retries is None
    time.sleep(0.3)
    d.release(hold, {hold.in_data[0].key: hh})
    # with the stale budget=1 snapshot this failed typed; widened it admits
    assert hg.wait(10) is not None
    d.shutdown()


def test_sim_kick_charges_blocked_head_once_per_memory_event():
    """Backfilling several small waiters in ONE kick must charge the
    blocked head's retry budget once, not once per loop iteration."""
    from repro.core.baselines import get_system
    from repro.core.clock import VirtualClock
    from repro.core.simulator import GPUNode

    node = GPUNode(get_system("sage"), VirtualClock(), capacity=100 * MB)
    node.used = 100 * MB  # full: everything below queues
    state = {"head": None, "smalls": 0}
    node.reserve(50 * MB, lambda: state.__setitem__("head", "ok"),
                 on_fail=lambda: state.__setitem__("head", "failed"),
                 max_retries=2)
    for _ in range(3):
        node.reserve(2 * MB,
                     lambda: state.__setitem__("smalls", state["smalls"] + 1),
                     on_fail=lambda: None)
    head = node.pending_mem[0][1]
    assert head.nbytes == 50 * MB and head.attempts == 1
    node.release(10 * MB)  # one memory event: kick backfills all 3 smalls
    assert state["smalls"] == 3
    assert state["head"] is None and head.attempts == 2  # charged ONCE
    node.release(60 * MB)  # now the head fits and is granted
    assert state["head"] == "ok"


# ---------------------------------------------------------------------------
# gateway knob plumbing + spec adoption/conflict (same rules as scheduler)
# ---------------------------------------------------------------------------

def test_gateway_dispatch_knob_plumbs_to_both_backends():
    gw = Gateway(backend="sim", policy="sage", n_nodes=2, dispatch="locality")
    assert gw.dispatch == "locality" and gw.sim.dispatch == "locality"
    with pytest.raises(ValueError):
        Gateway(backend="sim", dispatch="round_robin")
    with Gateway(backend="runtime", policy="sage", n_nodes=2,
                 dispatch="least_loaded", time_scale=0.02) as gw_rt:
        assert gw_rt.runtime.dispatch == "least_loaded"


def test_spec_dispatch_adoption_and_conflict():
    with pytest.raises(ValueError):
        FunctionSpec(name="x", dispatch="everywhere")
    # an undecided gateway adopts the first spec's declared dispatch
    gw = Gateway(backend="sim", policy="sage", n_nodes=2)
    gw.register(FunctionSpec.from_profile("resnet50", dispatch="locality"))
    assert gw.dispatch == "locality" and gw.sim.dispatch == "locality"
    with pytest.raises(ValueError, match="dispatch"):
        gw.register(FunctionSpec.from_profile("bert", dispatch="random"))
    # an explicit constructor choice is not overridable by a spec
    gw2 = Gateway(backend="sim", policy="sage", n_nodes=2, dispatch="random")
    with pytest.raises(ValueError, match="dispatch"):
        gw2.register(FunctionSpec.from_profile("resnet50", dispatch="locality"))
    # agreement is fine and pins the knob
    gw2.register(FunctionSpec.from_profile("resnet50", dispatch="random"))


# ---------------------------------------------------------------------------
# dynamic node pool (docs/planner.md): deterministic dispatch across
# join/drain churn, identical on both drivers; draining nodes leave the
# candidate set of every policy
# ---------------------------------------------------------------------------

def _churn_sequence(gw):
    """One blocking invoke sequence across a node join and a drain; every
    dispatch decision happens on an idle pool, so the chosen node ids are
    a pure function of the shared scoring + residency state."""
    seq = [gw.invoke("a").node_id, gw.invoke("b").node_id,
           gw.invoke("a").node_id]
    gw.add_node()  # cold joiner enters the candidate set immediately
    seq.append(gw.invoke("c").node_id)
    gw.drain_node(seq[0])  # a's warm home leaves the pool mid-trace
    seq.append(gw.invoke("a").node_id)
    seq.append(gw.invoke("b").node_id)
    return seq


def test_dynamic_pool_dispatch_identical_runtime_vs_sim():
    specs = [
        FunctionSpec(name="a", read_only_bytes=64 * MB,
                     writable_bytes=8 * MB, context_bytes=16 * MB),
        FunctionSpec(name="b", read_only_bytes=64 * MB,
                     writable_bytes=8 * MB, context_bytes=16 * MB),
        FunctionSpec(name="c", read_only_bytes=8 * MB,
                     writable_bytes=8 * MB, context_bytes=16 * MB),
    ]
    gw_sim = Gateway(backend="sim", policy="sage", n_nodes=2,
                     dispatch="locality")
    for s in specs:
        gw_sim.register(s)
    seq_sim = _churn_sequence(gw_sim)
    with Gateway(backend="runtime", policy="sage", n_nodes=2,
                 dispatch="locality", time_scale=0.02) as gw_rt:
        for s in specs:
            gw_rt.register(s)
        seq_rt = _churn_sequence(gw_rt)
    # record-for-record identical dispatch across join + drain churn
    assert seq_sim == seq_rt, (seq_sim, seq_rt)
    drained = seq_sim[0]
    # the drained node never serves again; its warm function re-homed
    assert drained not in seq_sim[4:]
    assert seq_sim[3] == "gpu2"  # the cold joiner won the cold function


def test_sim_policies_never_select_a_draining_node():
    # least_loaded: gpu0 wins the all-idle tie — unless it is draining
    sim = Simulator("sage", n_nodes=2, seed=0, dispatch="least_loaded")
    sim.register(SimFunction(PROFILES["resnet50"]))
    sim.drain_node("gpu0")
    sim.submit("resnet50", 0.0)
    sim.run(until=300.0)
    assert [r.node_id for r in sim.telemetry.snapshot()] == ["gpu1"]
    # locality: the residency holder drains mid-trace; device-tier
    # residency must not pull traffic back onto it
    sim2 = Simulator("sage", n_nodes=2, seed=0, dispatch="locality")
    sim2.register(SimFunction(PROFILES["resnet50"]))
    sim2.submit("resnet50", 0.0)
    sim2.run(until=300.0)
    warm = sim2.telemetry.snapshot()[0].node_id
    assert sim2.nodes[0].residency("resnet50")[0] == "device"
    sim2.drain_node(warm)
    sim2.submit("resnet50", sim2.clock.now() + 1.0)
    sim2.run(until=sim2.clock.now() + 300.0)
    recs = sorted(sim2.telemetry.snapshot(), key=lambda r: r.arrival_t)
    assert recs[-1].node_id != warm and recs[-1].error is None


def test_runtime_policies_never_select_a_draining_node():
    from repro.core.engine import GPUFunction

    def mk(name):
        return GPUFunction(name=name, handler=lambda s, r: None,
                           context_builder=lambda: object(),
                           context_bytes=1 * MB, container_s=0.0,
                           cpu_ctx_s=0.0)

    for policy in ("least_loaded", "locality"):
        cluster = ClusterRuntime(n_nodes=2, seed=0, database=Database(),
                                 dispatch=policy, serialize_compute=False)
        cluster.sage_init()
        cluster.register_function(lambda i: mk("f"))
        cluster.drain_node("gpu0")  # idle: retires immediately
        assert cluster.nodes[0].retired
        for _ in range(3):
            idx, _tier = cluster.select_node("f")
            assert idx == 1
        cluster.shutdown()


# ---------------------------------------------------------------------------
# telemetry attribution
# ---------------------------------------------------------------------------

def test_telemetry_per_node_attribution_and_public_snapshot():
    tel = Telemetry()
    for i, (node, tier) in enumerate([("gpu0", "device"), ("gpu0", "none"),
                                      ("gpu1", "host"), ("gpu1", None)]):
        tel.add(InvocationRecord(request_id=f"r{i}", function="f",
                                 system="sage", node_id=node,
                                 dispatch_tier=tier))
    assert isinstance(tel.snapshot(), list) and len(tel.snapshot()) == 4
    assert tel.node_counts() == {"gpu0": 2, "gpu1": 2}
    assert set(tel.by_node()) == {"gpu0", "gpu1"}
    # hit rate over cluster-dispatched records only (tier None excluded)
    assert tel.dispatch_hit_rate() == pytest.approx(2 / 3)
    by_node = tel.dispatch_by_node()
    assert by_node["gpu0"] == {"requests": 2, "hits": 1, "hit_rate": 0.5}
    assert by_node["gpu1"] == {"requests": 1, "hits": 1, "hit_rate": 1.0}
    assert Telemetry().dispatch_hit_rate() == 0.0


# ---------------------------------------------------------------------------
# acceptance: locality strictly beats random on p50 AND bytes_loaded, on
# BOTH backends (the benchmark helpers are the single source of truth)
# ---------------------------------------------------------------------------

def test_locality_strictly_beats_random_sim():
    from benchmarks.scaleout import dispatch_comparison_sim

    rnd = dispatch_comparison_sim("random")
    loc = dispatch_comparison_sim("locality")
    assert loc["p50_duration"] < rnd["p50_duration"]
    assert loc["bytes_loaded"] < rnd["bytes_loaded"]
    assert loc["hit_rate"] > rnd["hit_rate"]


def test_locality_strictly_beats_random_runtime():
    from benchmarks.scaleout import dispatch_comparison_runtime

    rnd = dispatch_comparison_runtime("random")
    loc = dispatch_comparison_runtime("locality")
    assert loc["p50_duration"] < rnd["p50_duration"]
    assert loc["bytes_loaded"] < rnd["bytes_loaded"]
    assert loc["hit_rate"] > rnd["hit_rate"]
