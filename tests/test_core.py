"""SAGE core unit tests: daemon sharing/refcounts, exit ladder, shim
classification, executor readiness, baselines policy table."""
import threading
import time

import numpy as np
import pytest

from repro.core.baselines import SYSTEMS, get_system
from repro.core.clock import RealClock, VirtualClock
from repro.core.daemon import GPU_CONTEXT_BYTES, MemoryDaemon, OutOfDeviceMemory, Tier
from repro.core.datapath import BandwidthBroker, DataPaths
from repro.core.exit_policy import ExitLadder, stage_skips
from repro.core.request import Data, DataType, Request
from repro.data.database import Database

MB = 1 << 20


def _daemon(cap_mb=1024, db=None):
    db = db or Database()
    paths = DataPaths.make(db_bw=1e12, pcie_bw=1e12)  # near-instant for tests
    return MemoryDaemon(paths, db, device_capacity=cap_mb * MB), db


def _req(fn="f", ro_mb=10, w_mb=2, db=None, uid=None):
    req = Request(function_name=fn)
    if db is not None:
        db.put(f"{fn}/w", np.zeros(1, np.uint8), size=ro_mb * MB)
        db.put(f"{fn}/in/{req.uuid}", np.zeros(1, np.uint8), size=w_mb * MB)
    req.in_data = [
        Data(key=f"{fn}/w", size=ro_mb * MB, dtype=DataType.READ_ONLY),
        Data(key=f"{fn}/in/{req.uuid}", size=w_mb * MB, dtype=DataType.WRITABLE),
    ]
    return req


class TestDaemon:
    def test_read_only_shared_loaded_once(self):
        d, db = _daemon()
        r1, r2 = _req(db=db), _req(db=db)
        h1 = d.prepare(r1)
        h2 = d.prepare(r2)
        for h in (*h1.values(), *h2.values()):
            h.wait(5)
        # 1 shared weights entry + 2 private inputs = 3 loads; 1 shared hit
        assert d.stats["loads"] == 3
        assert d.stats["shared_hits"] == 1
        assert h1["f/w"].entry is h2["f/w"].entry

    def test_no_sharing_when_disabled(self):
        d, db = _daemon()
        r1, r2 = _req(db=db), _req(db=db)
        h1 = d.prepare(r1, system_shares_ro=False)
        h2 = d.prepare(r2, system_shares_ro=False)
        for h in (*h1.values(), *h2.values()):
            h.wait(5)
        assert d.stats["shared_hits"] == 0
        assert d.stats["loads"] == 4

    def test_release_refcounts_and_writable_freed(self):
        d, db = _daemon()
        r1 = _req(db=db)
        h1 = d.prepare(r1)
        for h in h1.values():
            h.wait(5)
        used_before = d.device_used
        d.release(r1, h1)
        # writable freed; read-only cached (refcount 0, still on device)
        assert d.device_used == used_before - 2 * MB
        e = h1["f/w"].entry
        assert e.refcount == 0 and e.tier is Tier.DEVICE

    def test_demote_and_host_promotion(self):
        d, db = _daemon()
        r1 = _req(db=db)
        h1 = d.prepare(r1)
        for h in h1.values():
            h.wait(5)
        d.release(r1, h1)
        moved = d.demote_to_host("f")
        assert moved == 10 * MB
        assert h1["f/w"].entry.tier is Tier.HOST
        # next invocation promotes host -> device (PCIe only, no db load)
        r2 = _req(db=db)
        h2 = d.prepare(r2)
        for h in h2.values():
            h.wait(5)
        assert d.stats["host_promotions"] == 1
        assert h2["f/w"].entry.tier is Tier.DEVICE

    def test_oom_and_eviction(self):
        d, db = _daemon(cap_mb=32)
        r1 = _req(fn="a", ro_mb=20, w_mb=1, db=db)
        h1 = d.prepare(r1)
        for h in h1.values():
            h.wait(5)
        d.release(r1, h1)  # 20MB cached RO
        d.set_evictable_provider(lambda: d.evictable_entries("a"))
        # new function needs 20MB -> must evict a's cached weights
        db.put("b/w", np.zeros(1, np.uint8), size=20 * MB)
        r2 = Request(function_name="b",
                     in_data=[Data(key="b/w", size=20 * MB)])
        h2 = d.prepare(r2)
        for h in h2.values():
            h.wait(5)
        assert d.stats["evictions"] == 1
        assert h1["a/w"].entry.tier is Tier.DROPPED

    def test_hard_oom_raises(self):
        d, db = _daemon(cap_mb=8)
        with pytest.raises(OutOfDeviceMemory):
            d._reserve_device(16 * MB)


class TestExitLadder:
    def test_stage_progression(self):
        lad = ExitLadder(ttls=(1.0, 1.0, 1.0, 1.0))
        lad.on_complete(100.0)
        assert lad.stage_at(100.5) == 1
        assert lad.stage_at(101.5) == 2
        assert lad.stage_at(102.5) == 3
        assert lad.stage_at(103.5) == 4
        assert lad.stage_at(104.5) == 5

    def test_actions_fire_once_in_order(self):
        fired = []
        lad = ExitLadder(ttls=(1.0,) * 4)
        lad.on_enter = {k: (lambda k=k: fired.append(k)) for k in (2, 3, 4)}
        lad.on_complete(0.0)
        lad.advance(1.5)
        assert fired == [2]
        lad.advance(3.5)  # skipped ahead two stages -> both fire, in order
        assert fired == [2, 3, 4]
        lad.advance(3.6)
        assert fired == [2, 3, 4]  # idempotent

    def test_reuse_stops_exit_and_reports_stage(self):
        lad = ExitLadder(ttls=(1.0,) * 4)
        lad.on_complete(0.0)
        s = lad.on_reuse(1.5)
        assert s == 2
        assert lad.stage_at(99.0) == 0  # running again

    def test_warmer_stage_skips_more(self):
        assert len(stage_skips[1]) > len(stage_skips[2]) > len(stage_skips[3]) \
            > len(stage_skips[4])
        assert "gpu_data" in stage_skips[1] and "gpu_data" not in stage_skips[2]
        assert "gpu_ctx" in stage_skips[2] and "gpu_ctx" not in stage_skips[3]


class TestPolicies:
    def test_policy_table(self):
        sage = get_system("sage")
        assert sage.parallel_setup and sage.share_read_only and sage.multi_stage_exit
        fixed = get_system("fixedgsl")
        assert not fixed.parallel_setup and fixed.slot_granularity == 1 << 30
        flex = get_system("fixedgsl-f")
        assert flex.slot_granularity == 0
        dgsf = get_system("dgsf")
        assert dgsf.pre_created_contexts == 4 and not dgsf.share_read_only
        nr = get_system("sage-nr")
        assert nr.parallel_setup and not nr.share_read_only

    def test_unknown_system_raises(self):
        with pytest.raises(KeyError):
            get_system("nope")


class TestBroker:
    def test_solo_transfer_time(self):
        b = BandwidthBroker(100 * MB)  # 100 MB/s
        t = b.transfer(10 * MB)
        assert 0.08 < t < 0.5

    def test_fair_share_contention(self):
        b = BandwidthBroker(100 * MB)
        results = []

        def go():
            results.append(b.transfer(5 * MB))

        ts = [threading.Thread(target=go) for _ in range(4)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        solo = 5 * MB / b.bw
        assert min(results) > 1.2 * solo  # contended: visibly slower than solo
        assert b.max_concurrency >= 3

    def test_virtual_transfer(self):
        clock = VirtualClock()
        b = BandwidthBroker(100 * MB, clock)
        done = []
        b.sim_transfer(10 * MB, lambda: done.append(clock.now()))
        b.sim_transfer(10 * MB, lambda: done.append(clock.now()))
        clock.run_until(10.0)
        assert len(done) == 2
        # two equal transfers sharing the link both finish at ~2x solo
        assert abs(done[0] - 0.2) < 0.02
