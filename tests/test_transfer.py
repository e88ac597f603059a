"""Preemptible chunked transfer engine (docs/dataplane.md, "Transfer
scheduling"): stream/arbiter units, preemptive strictly beating
run_to_completion for a tight-deadline load on BOTH drivers,
runtime<->simulator preemption parity, byte-exact accounting when a paused
stream is cancelled by release(), and a golden-trace guard that the default
``run_to_completion`` mode reproduces the pre-stream simulator bit-for-bit."""
import threading
import time

import numpy as np
import pytest

from repro.api import FunctionSpec, Gateway
from repro.core.daemon import DataLoadError, MemoryDaemon
from repro.core.datapath import BandwidthBroker, DataPaths
from repro.core.profiles import PROFILES, FunctionProfile
from repro.core.request import Data, DataType, Request
from repro.core.simulator import SimFunction, Simulator
from repro.core.telemetry import InvocationRecord, Telemetry
from repro.core.transfer import (
    TRANSFER_MODES, LinkArbiter, TransferStream, key_prefix,
)
from repro.data.database import Database

MB = 1 << 20


# ---------------------------------------------------------------------------
# stream / arbiter units
# ---------------------------------------------------------------------------


def test_stream_chunked_progress_and_cancel_freeze_bytes():
    broker = BandwidthBroker(1e12, name="test")
    st = broker.open_stream(10 * MB)
    st.advance(4 * MB)
    assert st.moved == 4 * MB and st.remaining == 6 * MB and not st.done
    st.cancel()
    assert st.advance(4 * MB) == 0.0  # cancelled: advances are no-ops
    assert st.moved == 4 * MB and not st.done
    # the link was charged ONLY for the bytes actually moved
    assert broker.total_bytes == 4 * MB

    st2 = broker.open_stream(3 * MB)
    st2.advance()  # full-size advance == one blocking transfer
    assert st2.done and st2.remaining == 0.0
    assert broker.total_bytes == 7 * MB


def test_stream_pause_resume_accounting():
    broker = BandwidthBroker(1e12, name="test")
    st = broker.open_stream(8 * MB)
    st.advance(2 * MB)
    st.pause(10.0)
    st.pause(11.0)  # idempotent: one pause, one preemption
    assert st.preemptions == 1
    st.resume(12.5)
    assert st.stalled_s == pytest.approx(2.5)
    st.advance()
    assert st.done and st.moved == 8 * MB


def test_arbiter_yields_only_to_strictly_tighter_prefix():
    demand = {"head": None}
    arb = LinkArbiter("preemptive", demand=lambda: demand["head"])
    mine = (0, 50.0)  # prio 0, deadline 50
    assert not arb.should_yield(mine)          # no demand
    demand["head"] = (0, 50.0, 99)             # same class, later arrival
    assert not arb.should_yield(mine)          # seq must NOT preempt
    demand["head"] = (0, 10.0, 99)             # tighter deadline
    assert arb.should_yield(mine)
    demand["head"] = (-1, float("inf"), 99)    # higher priority
    assert arb.should_yield(mine)
    demand["head"] = (0, 0.0, 1)               # fifo keys: degenerate prefix
    assert not arb.should_yield((0, 0.0))
    arb.set_mode("run_to_completion")
    demand["head"] = (-5, 0.0, 0)
    assert not arb.should_yield(mine)          # mode gates everything
    with pytest.raises(ValueError):
        LinkArbiter("bogus")
    assert key_prefix(None) is None
    assert key_prefix((1, 2.0, 3)) == (1, 2.0)


# ---------------------------------------------------------------------------
# golden guard: default run_to_completion is bit-identical to the
# pre-stream simulator (captured from the seed implementation)
# ---------------------------------------------------------------------------

_GOLDEN = {
    ("sage", "fifo"): [0.3105, 1.919762113, 1.171215559, 1.199215586,
                       1.863762113, 1.344072957, 1.372072984, 1.891762113,
                       1.516930356],
    ("sage", "edf"): [0.3105, 1.919762113, 1.171215559, 1.199215586,
                      1.863762113, 1.24962996, 1.466515981, 1.891762113,
                      1.516930356],
    ("fixedgsl", "fifo"): [0.403762692, 4.567450713, 2.641303739,
                           1.055328784, 5.318721576, 3.56865155, 1.79106986,
                           5.408869439, 3.923614329],
    ("dgsf", "fifo"): [0.117662692, 4.281350713, 2.355203739, 0.769228784,
                       5.032621576, 3.28255155, 1.50496986, 5.122769439,
                       3.637514329],
}


@pytest.mark.parametrize("policy,scheduler", list(_GOLDEN))
def test_run_to_completion_bit_identical_to_seed(policy, scheduler):
    sim = Simulator(policy, loader_threads=2, scheduler=scheduler)
    assert sim.transfer == "run_to_completion"  # the default knob
    fns = []
    for p in ("resnet50", "bert", "vgg11"):
        f = SimFunction(PROFILES[p])
        sim.register(f)
        fns.append(f.name)
    for i in range(9):
        sim.submit(fns[i % 3], 0.15 * i, deadline_s=5.0 + i, priority=i % 2)
    sim.run(until=900.0)
    got = [round(r.end_t, 9) for r in
           sorted(sim.telemetry.records, key=lambda r: (r.arrival_t,
                                                        r.request_id))]
    assert got == _GOLDEN[(policy, scheduler)]
    # and nothing was preempted or stalled under the default mode
    assert sim.preemption_count() == 0
    assert sim.telemetry.transfer_wait() == 0.0


# ---------------------------------------------------------------------------
# simulator: preemptive strictly beats run_to_completion for the tight class
# ---------------------------------------------------------------------------


def _sim_two_class(transfer):
    sim = Simulator("sage", loader_threads=1, scheduler="edf",
                    transfer=transfer)
    sim.register(SimFunction(
        FunctionProfile("loose", "custom", 1.0, 0.0, 800.0, 5.0)))
    sim.register(SimFunction(
        FunctionProfile("tight", "custom", 1.0, 0.0, 24.0, 5.0)))
    sim.submit("loose", 0.0, deadline_s=60.0, priority=0)
    sim.submit("tight", 0.05, deadline_s=1.0, priority=1)  # mid-loose-stream
    sim.run(until=600.0)
    assert sim.completed == 2 and sim.failed == 0
    return sim, {r.function: r for r in sim.telemetry.records}


def test_sim_preemptive_tight_load_completes_sooner():
    _, rtc = _sim_two_class("run_to_completion")
    sim, pre = _sim_two_class("preemptive")
    # the tight load no longer waits out the loose 800 MB stream
    assert pre["tight"].e2e < rtc["tight"].e2e
    # under run_to_completion the tight load finishes AFTER the loose one;
    # preemption flips the completion order
    assert rtc["tight"].end_t > rtc["loose"].end_t
    assert pre["tight"].end_t < pre["loose"].end_t
    # exactly the loose in-flight stream was paused, then resumed to run
    # to completion without losing bytes
    assert pre["loose"].preemptions >= 1
    assert pre["tight"].preemptions == 0
    assert pre["loose"].stalled_s > 0.0
    assert sim.preemption_count() == pre["loose"].preemptions
    assert sim.nodes[0].bytes_loaded == (800 + 24) * MB
    assert sim.telemetry.transfer_wait() == pytest.approx(
        pre["loose"].stalled_s)


def test_sim_gpu_data_records_actual_contended_span():
    # two identical private loads in lockstep share the PCIe link: the
    # recorded gpu_data must be the ACTUAL ~2x-solo contended span, not the
    # solo estimate nbytes/pcie.bw the seed charged
    sim = Simulator("sage-nr", loader_threads=4)
    f = SimFunction(FunctionProfile("f", "custom", 1.0, 0.0, 512.0, 5.0))
    sim.register(f)
    sim.submit("f", 0.0)
    sim.submit("f", 0.0)
    sim.run(until=600.0)
    assert sim.completed == 2
    solo = f.w_bytes / sim.nodes[0].pcie.bw
    for r in sim.telemetry.records:
        assert r.stages["gpu_data"] > 1.5 * solo
        assert r.stages["gpu_data"] == pytest.approx(2 * solo, rel=0.1)

    # an uncontended load still records ~the solo time
    sim2 = Simulator("sage-nr", loader_threads=4)
    sim2.register(f)
    sim2.submit("f", 0.0)
    sim2.run(until=600.0)
    r = sim2.telemetry.records[0]
    assert r.stages["gpu_data"] == pytest.approx(solo, rel=0.05)


# ---------------------------------------------------------------------------
# threaded daemon: preemption + parity with the sim + byte-exact cancel
# ---------------------------------------------------------------------------


def _wreq(fn, mb, db, deadline_s=None, priority=0):
    req = Request(function_name=fn)
    key = f"{fn}/in/{req.uuid}"
    db.put(key, np.zeros(1, np.uint8), size=mb * MB)
    req.in_data = [Data(key=key, size=mb * MB, dtype=DataType.WRITABLE)]
    req.deadline_s, req.priority = deadline_s, priority
    return req


def _preempt_daemon(transfer, db=None, **kw):
    db = db or Database()
    paths = DataPaths.make(db_bw=2e9, pcie_bw=4e9)  # legs take real but
    # test-sized wall time (160 MB ~ 0.08 s db + 0.04 s pcie)
    kw.setdefault("chunk_bytes", 8 * MB)
    d = MemoryDaemon(paths, db, loader_threads=1, scheduler="edf",
                     transfer=transfer, **kw)
    return d, db


def _run_two_class_daemon(transfer):
    d, db = _preempt_daemon(transfer)
    ends = {}

    def waiter(name, h):
        h.wait(30)
        ends[name] = time.monotonic()

    loose = _wreq("loose", 160, db, deadline_s=60.0, priority=0)
    hl = d.prepare(loose)[loose.in_data[0].key]
    tl = threading.Thread(target=waiter, args=("loose", hl))
    tl.start()
    time.sleep(0.03)  # the loose stream is mid-db-leg
    tight = _wreq("tight", 8, db, deadline_s=0.5, priority=1)
    t0 = time.monotonic()
    ht = d.prepare(tight)[tight.in_data[0].key]
    tt = threading.Thread(target=waiter, args=("tight", ht))
    tt.start()
    for t in (tl, tt):
        t.join(timeout=30)
        assert not t.is_alive()
    tight_s = ends["tight"] - t0
    stats = dict(d.stats)
    out = {
        "tight_s": tight_s,
        "tight_first": ends["tight"] < ends["loose"],
        "loose_preempt": hl.entry.transfer_preemptions(),
        "tight_preempt": ht.entry.transfer_preemptions(),
        "loose_stall": hl.entry.transfer_stalled_s(),
        "preemptions": stats["preemptions"],
        "db_bytes": d.paths.db.total_bytes,
    }
    d.release(loose, {loose.in_data[0].key: hl})
    d.release(tight, {tight.in_data[0].key: ht})
    assert d.device_used == 0 and d.host_used == 0
    d.shutdown()
    return out


def test_runtime_preemptive_tight_load_completes_sooner():
    rtc = _run_two_class_daemon("run_to_completion")
    pre = _run_two_class_daemon("preemptive")
    assert rtc["preemptions"] == 0 and rtc["loose_preempt"] == 0
    assert pre["preemptions"] >= 1
    assert pre["tight_s"] < rtc["tight_s"]
    # full byte accounting: both streams moved everything they declared
    assert rtc["db_bytes"] == (160 + 8) * MB
    assert pre["db_bytes"] == (160 + 8) * MB


def test_runtime_sim_preemption_parity():
    """Same arrival pattern (tight small load arriving mid-way through a
    loose large stream, one loader worker, EDF keys) => the same stream is
    paused then resumed on BOTH drivers, and only under "preemptive"."""
    sim_pre = _sim_two_class("preemptive")[1]
    sim_rtc = _sim_two_class("run_to_completion")[1]
    rt_pre = _run_two_class_daemon("preemptive")
    rt_rtc = _run_two_class_daemon("run_to_completion")
    # loose paused >=1 then resumed to completion; tight never paused
    assert sim_pre["loose"].preemptions >= 1 and rt_pre["loose_preempt"] >= 1
    assert sim_pre["tight"].preemptions == 0 and rt_pre["tight_preempt"] == 0
    assert sim_pre["loose"].stalled_s > 0.0 and rt_pre["loose_stall"] > 0.0
    # the tight load overtakes the loose one only under "preemptive"
    assert sim_pre["tight"].end_t < sim_pre["loose"].end_t
    assert rt_pre["tight_first"]
    assert sim_rtc["tight"].end_t > sim_rtc["loose"].end_t
    assert not rt_rtc["tight_first"]
    assert sim_rtc["loose"].preemptions == 0 and rt_rtc["loose_preempt"] == 0


def test_release_of_paused_stream_cancels_byte_exact():
    """release() of a writable entry whose stream is PAUSED (preempted)
    cancels it at the next loader checkpoint; accounting is byte-exact:
    no device/host leak, and the links are charged only for chunks that
    actually moved."""
    d, db = _preempt_daemon("preemptive", chunk_bytes=4 * MB)
    loose = _wreq("loose", 80, db, deadline_s=60.0, priority=0)
    handles = d.prepare(loose)
    hl = handles[loose.in_data[0].key]
    time.sleep(0.01)  # loose mid-db-leg
    tight = _wreq("tight", 64, db, deadline_s=0.5, priority=1)
    ht = d.prepare(tight)[tight.in_data[0].key]
    # wait for the preemption, then cancel the paused loose stream while
    # the tight load still owns the single worker
    deadline = time.monotonic() + 5
    while d.stats["preemptions"] == 0 and time.monotonic() < deadline:
        time.sleep(0.001)
    assert d.stats["preemptions"] >= 1
    d.release(loose, handles)
    with pytest.raises(DataLoadError):
        hl.wait(10)
    ht.wait(10)
    deadline = time.monotonic() + 5
    while d.stats["load_cancellations"] == 0 and time.monotonic() < deadline:
        time.sleep(0.01)
    assert d.stats["load_cancellations"] == 1
    assert d.device_used == 64 * MB  # only the tight entry remains
    assert d.host_used == 64 * MB
    # link accounting is exact: tight's full size + exactly the loose
    # chunks that moved before the cancel — never the full loose stream
    loose_db = hl.entry.db_stream.moved
    loose_pcie = hl.entry.pcie_stream.moved if hl.entry.pcie_stream else 0.0
    assert d.paths.db.total_bytes == 64 * MB + loose_db
    assert d.paths.pcie.total_bytes == 64 * MB + loose_pcie
    assert loose_db + loose_pcie < 2 * 80 * MB  # the tail was never moved
    d.release(tight, {tight.in_data[0].key: ht})
    assert d.device_used == 0 and d.host_used == 0
    d.shutdown()


def test_transfer_attribution_claimed_once_across_sharers():
    """A pause on an entry is attributed to exactly ONE record: the claim
    API returns the not-yet-attributed delta and zero afterwards, so
    concurrent sharers cannot each report the same stall (runtime totals
    stay comparable to daemon.stats and the sim twin)."""
    d, db = _preempt_daemon("preemptive")
    loose = _wreq("loose", 160, db, deadline_s=60.0, priority=0)
    hl = d.prepare(loose)[loose.in_data[0].key]
    time.sleep(0.03)
    tight = _wreq("tight", 8, db, deadline_s=0.5, priority=1)
    ht = d.prepare(tight)[tight.in_data[0].key]
    hl.wait(30)
    ht.wait(30)
    assert d.stats["preemptions"] >= 1
    handles = {loose.in_data[0].key: hl}
    p1, s1 = d.claim_transfer_attribution(handles)
    assert p1 >= 1 and s1 > 0.0
    p2, s2 = d.claim_transfer_attribution(handles)
    assert p2 == 0 and s2 == 0.0
    d.release(loose, handles)
    d.release(tight, {tight.in_data[0].key: ht})
    d.shutdown()


# ---------------------------------------------------------------------------
# knob plumbing: validation, gateway adoption/conflict, runtime switch
# ---------------------------------------------------------------------------


def test_transfer_knob_validation():
    with pytest.raises(ValueError):
        FunctionSpec(name="f", transfer="bogus")
    with pytest.raises(ValueError):
        Simulator("sage", transfer="bogus")
    with pytest.raises(ValueError):
        MemoryDaemon(DataPaths.make(), Database(), transfer="bogus")
    with pytest.raises(ValueError):
        Gateway(backend="sim", transfer="bogus")
    assert set(TRANSFER_MODES) == {"run_to_completion", "preemptive"}


def test_gateway_adopts_spec_transfer_and_refuses_conflicts():
    gw = Gateway(backend="sim", policy="sage")
    assert gw.transfer == "run_to_completion"
    gw.register(FunctionSpec.from_profile("resnet50", name="a",
                                          transfer="preemptive"))
    assert gw.transfer == "preemptive"
    assert gw.sim.transfer == "preemptive"
    # a later spec declaring a DIFFERENT mode is refused
    with pytest.raises(ValueError, match="transfer"):
        gw.register(FunctionSpec.from_profile("resnet50", name="b",
                                              transfer="run_to_completion"))
    # a pinned gateway refuses a conflicting spec up front
    gw2 = Gateway(backend="sim", policy="sage", transfer="run_to_completion")
    with pytest.raises(ValueError, match="transfer"):
        gw2.register(FunctionSpec.from_profile("resnet50", name="a",
                                               transfer="preemptive"))


def test_set_transfer_switches_both_drivers():
    sim = Simulator("sage", n_nodes=2)
    sim.set_transfer("preemptive")
    assert all(n.arbiter.mode == "preemptive" for n in sim.nodes)
    with pytest.raises(ValueError):
        sim.set_transfer("bogus")

    from repro.core.runtime import ClusterRuntime
    cluster = ClusterRuntime(n_nodes=2, database=Database(),
                             serialize_compute=False)
    assert cluster.transfer == "run_to_completion"
    cluster.set_transfer("preemptive")
    assert all(n.daemon.transfer == "preemptive" for n in cluster.nodes)
    cluster.shutdown()


# ---------------------------------------------------------------------------
# telemetry: tail percentiles + transfer_wait
# ---------------------------------------------------------------------------


def test_telemetry_tail_percentiles_and_transfer_wait():
    tel = Telemetry()
    for i in range(100):
        r = InvocationRecord(request_id=f"r{i}", function="f", system="sage",
                             start_t=0.0, end_t=float(i + 1))
        r.stalled_s = 0.25
        r.preemptions = 2
        tel.add(r)
    assert tel.p50_duration() == 51.0
    assert tel.p95_duration() == 96.0
    assert tel.p99_duration() == 100.0
    assert tel.p99_duration("other") == 0.0
    assert tel.transfer_wait() == pytest.approx(25.0)
    assert tel.preemption_count() == 200
    assert tel.transfer_wait("other") == 0.0


# ---------------------------------------------------------------------------
# hedged redispatch: loser cancellation stays byte-exact on the sim driver
# ---------------------------------------------------------------------------


def test_sim_hedge_loser_cancel_byte_exact():
    """Gray-failure hedging on the virtual-time driver: a SlowNode drags
    one node, the straggling invocations launch speculative twins, and
    every cancelled loser unwinds byte-exactly — no node leaks device or
    host bytes, no loader slot stays claimed, and each request produces
    exactly one outcome (the loser's record is ``dropped``/``hedged``,
    never a second completion)."""
    from repro.core.faults import FaultPlan, SlowNode
    from repro.core.profiles import FunctionProfile

    duration = 30.0
    sim = Simulator(
        "sage", n_nodes=3, seed=7,
        faults=FaultPlan([SlowNode("gpu1", at_s=3.0, factor=12.0)], seed=7),
        eviction=True, dispatch="random",
        hedging=dict(min_samples=6, hedge_quantile=0.9), quarantine=False,
    )
    sim.register(SimFunction(FunctionProfile(
        "f", "tail", context_mb=64.0, read_only_mb=24.0, writable_mb=4.0,
        compute_ms=15.0)))
    rng_t = 0.0
    for i in range(240):
        rng_t += duration / 240.0
        sim.submit("f", rng_t, deadline_s=0.5, request_id=f"h{i}")
    sim.run(duration + 120.0)

    recs = sim.telemetry.snapshot()
    losers = [r for r in recs if r.dropped and r.error_class == "hedged"]
    stats = sim.resilience_stats()
    assert stats["hedges_launched"] > 0, "the fault never provoked a hedge"
    # a launched hedge resolves exactly one way: the loser is dropped
    # (win) or the hedge itself was wasted — and every loser is a drop
    assert len(losers) == stats["hedges_won"] + stats["hedges_wasted"] \
        == stats["hedges_launched"]
    for r in losers:
        assert r.error and "Hedged" in r.error
        assert r.end_t > 0.0  # the loser finalized, not abandoned
    # exactly one outcome per submitted request id
    kept = [r for r in recs if not r.dropped]
    assert len({r.request_id for r in kept}) == len(kept) == 240
    # byte-exact books after every loser unwound
    for n in sim.nodes:
        assert 0 <= n.used <= n.capacity, f"{n.name}: used={n.used}"
        assert n.host_used >= 0, f"{n.name}: host_used={n.host_used}"
        assert n.inflight_loads == 0, f"{n.name} leaked loader slots"
