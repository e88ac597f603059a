"""The readers of the expert layer's metrics, on synthetic runs as the
harness hands them over, and on the records of a whole run of the
granite-4.0-h-small cell at the program's reduced width on the CPU. A
program whose records lack the counters (the parent of the change that
adds them) gives no value."""
import time
from types import SimpleNamespace

import pytest

import _paths
import harness

CELL = "granite-4.0-h-small.closed16"
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
# a forward of 1e12 FLOPs and 8.19e9 bytes (10 ms at the v5e's bandwidth),
# 4e3 held-expert rows expected over 10 layers x 4 held experts
COUNTS = {"flops": 1e12, "bytes": 8.19e9, "expert_rows": 4e3,
          "expert_row_flops": 1e7, "expert_groups": 40.0,
          "expert_weight_bytes": 2.0e9, "expert_row_bytes": 8e3}
MODULE = "jit_logits_and_counters"


def _read(name, run):
    return harness.load_module(harness.HERE / "metrics" / f"{name}.py").read(run)


def _run(records, ops=None, executions=100, device_s=2.0):
    trace = {"window_s": 2.5, "devices": 1, "ops": ops or {},
             "modules": {MODULE: (executions, device_s)}}
    return SimpleNamespace(served=[SimpleNamespace(record=r) for r in records],
                           counts=COUNTS, peaks=V5E, trace=trace)


def _rec(rows, busiest):
    return SimpleNamespace(expert_rows=rows, expert_rows_max=busiest)


def test_forward_roofline_counts_the_measured_rows():
    # measured 5e3 rows: 1e12 + 1e3 x 1e7 = 1.01e12 FLOPs, 5.13 ms; bytes 10 ms
    run = _run([_rec(4e3, 0), _rec(6e3, 0)])
    assert _read("forward_roofline.moe", run) == pytest.approx(100 * 0.010 / 0.020)
    heavy = _run([_rec(1e5, 0)])  # 1.96e12 FLOPs: 9.95 ms, bytes still bind
    assert _read("forward_roofline.moe", heavy) == pytest.approx(50.0)
    heavier = _run([_rec(2e5, 0)])  # 2.96e12 FLOPs bind: 15.03 ms
    assert _read("forward_roofline.moe", heavier) == pytest.approx(
        100 * 2.96e12 / 197e12 / 0.020)


def test_mfu_counts_the_measured_rows():
    run = _run([_rec(5e3, 0)])
    assert _read("mfu.moe", run) == pytest.approx(
        100 * 100 * 1.01e12 / (2.5 * 197e12))


def test_imbalance_is_the_busiest_expert_over_the_mean():
    # 4e3 rows over 40 held experts: 100 a layer each on average
    run = _run([_rec(4e3, 150), _rec(4e3, 250)])
    assert _read("expert_imbalance.moe", run) == pytest.approx(2.0)


def test_gmm_roofline_reads_the_kernel_ops_alone():
    ops = {"gmm": 0.1, "gmm.7": 0.3, "fusion.3": 1.0, "gmm_like.2": 9.0}
    run = _run([_rec(5e3, 0)], ops=ops)
    # 5e3 x 1e7 FLOPs: 0.254 ms; 2e9 + 5e3 x 8e3 bytes: 2.49 ms, over 4 ms
    want = 100 * (2.04e9 / 819e9) / (0.4 / 100)
    assert _read("expert_gmm_roofline.moe", run) == pytest.approx(want)
    assert _read("expert_gmm_roofline.moe", _run([_rec(5e3, 0)])) is None


@pytest.mark.parametrize("name", ["forward_roofline.moe", "mfu.moe",
                                  "expert_imbalance.moe",
                                  "expert_gmm_roofline.moe"])
def test_no_value_from_records_without_counters(name):
    parent = SimpleNamespace(stages={"compute": 0.1}, substages={})
    assert _read(name, _run([parent, parent], ops={"gmm": 0.1})) is None
    assert _read(name, _run([_rec(None, None)], ops={"gmm": 0.1})) is None


def test_whole_cell_at_reduced_width_is_correct_and_carries_counters():
    cell = harness.load_cell(_paths.ROOT, CELL,
                             config_file=_paths.DATA / "granite-tiny.json",
                             traffic_file=_paths.DATA / "closed-tiny.json")
    run = harness.Run(cell, seed=2**31 + 5150, seconds=1.0, trace=False,
                      t_start=time.perf_counter(), require_tpu=False,
                      log=lambda s: None)
    out = run.result()
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"setup_s", "inv_per_chip_s"}
    cfg = cell.config
    counts = cell.family.counts(cfg, 1, 256)
    data = SimpleNamespace(served=[i for i in run.invs if i.ok], counts=counts,
                           peaks=V5E, trace={"window_s": 1.0, "devices": 1,
                                             "ops": {"gmm.1": 0.01},
                                             "modules": {MODULE: (10, 0.5)}})
    layers, held = cfg["num_hidden_layers"], cfg["num_local_experts"]
    for i in data.served:
        assert 0 < i.record.expert_rows_max <= 256
        assert i.record.expert_rows <= layers * held * 256
    for name in ("forward_roofline.moe", "mfu.moe", "expert_imbalance.moe",
                 "expert_gmm_roofline.moe"):
        assert _read(name, data) > 0, name
    assert counts["expert_groups"] == layers * held
