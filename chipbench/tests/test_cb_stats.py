"""Percentiles are taken over every invocation, from its due time."""
import math
import statistics

import numpy as np
import pytest

import _paths  # noqa: F401
import harness
import readers
from stats import percentile, spread


@pytest.mark.parametrize("q", [0, 5, 50, 95, 99, 100])
def test_percentile_matches_numpy_linear(q):
    xs = list(np.random.default_rng(1).exponential(size=163))
    assert percentile(xs, q) == pytest.approx(np.percentile(xs, q))


def test_spread_uses_statistics_quartiles():
    xs = [10.0, 11.0, 12.0, 13.0, 14.0, 20.0]
    q1, med, q3 = statistics.quantiles(xs, n=4)
    assert spread(xs) == pytest.approx((q3 - q1) / med)


def _run(invs, seconds=10.0):
    cell = harness.Cell(name="x", chips=1, config={}, traffic={}, bench={})
    return harness.RunData(cell=cell, seconds=seconds, setup_s=1.0, t_open=100.0,
                           invs=invs, counters={}, counts={}, peaks=None)


class _Rec:
    error = None
    stages = {"compute": 0.002, "gpu_data": 0.5}
    dispatch_tier = None


def test_latency_counts_from_due_time_and_missing_ones():
    invs = []
    for k in range(19):  # due at 100+k/2, each sent 5 ms late, done 20 ms later
        inv = harness.Inv(function=0, seed=k, due=100.0 + k / 2)
        inv.sent, inv.done, inv.record = inv.due + 0.005, inv.due + 0.025, _Rec()
        invs.append(inv)
    lost = harness.Inv(function=0, seed=99, due=105.0)
    lost.error = "TimeoutError: never came"
    invs.append(lost)
    run = _run(invs)
    assert readers.latency_ms(run, 50) == pytest.approx(25.0)
    # the lost one counts as the longest wait: window plus a minute
    cap = (10.0 + harness.WAIT_PAST_CLOSE_S) * 1e3
    assert readers.latency_ms(run, 100) == pytest.approx(cap)
    assert readers.latency_ms(run, 95) == pytest.approx(25.0 + 0.05 * (cap - 25.0))


def test_cold_stage_means_and_shares():
    invs = []
    for k, tier in enumerate(["device", "device", "none", "loading"]):
        inv = harness.Inv(function=0, seed=k, due=100.0)
        inv.done, inv.record, inv.tier = 100.1, _Rec(), tier
        invs.append(inv)
    run = _run(invs)
    assert readers.mean_stage_ms(run, "gpu_data", cold=True) == pytest.approx(500.0)
    assert readers.mean_stage_ms(run, "compute") == pytest.approx(2.0)
    mod = harness.load_module(harness.HERE / "metrics" / "dispatch_resident_share.py")
    assert mod.read(run) == pytest.approx(50.0)
    run.counters = {"loads": 6, "host_promotions": 1}
    mod = harness.load_module(harness.HERE / "metrics" / "cold_share.py")
    assert mod.read(run) == pytest.approx(100.0 * (6 + 1 - 4) / 4)
    assert not math.isnan(readers.latency_ms(run, 50))
