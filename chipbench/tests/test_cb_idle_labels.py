"""Idle gaps labelled by the program's ``sage.`` spans, on synthetic traces:
work outranks waiting, self time beats an enclosing span, and a trace with
no program span keeps ``trace_reduce``'s labels. And the readers of the
program's ``substages``, on records with and without them."""
import os
import subprocess
import sys
from types import SimpleNamespace

import pytest

import _paths
import harness
import idle_labels as il
import trace_reduce as tr
from trace_reduce import Line, Plane

MS = 1e6  # ns


def _device(*busy_ms):
    return Plane("/device:TPU:0", [Line("XLA Ops", [
        (f"%fusion.{i} = f32[2] fusion(x)", a * MS, (b - a) * MS)
        for i, (a, b) in enumerate(busy_ms)])])


def _host(*lines):
    return Plane("/host:CPU", [Line(name, [(n, a * MS, (b - a) * MS)
                                           for n, a, b in evs])
                               for name, evs in lines])


def _caller(*evs):
    return ("python3", [("cb.traced", 0, 100)] + list(evs))


def test_no_program_span_keeps_trace_reduce_labels():
    planes = [_host(_caller(("cb.submit", 10, 12), ("cb.wait", 12, 42),
                            ("cb.register", 60, 90)),
                    ("python3", [("PjitFunction(f)", 10, 11)])),
              _device((15, 30), (20, 30), (50, 55), (95, 105))]
    r = tr.reduce(planes, chips=1)
    a = il.attribute(planes, chips=1)
    assert a["idle_gaps"] == r["idle_gaps"]
    assert il.attribute(planes, chips=1, top=2)["idle_gaps"] == r["idle_gaps"][:2]
    assert a["idle_by_label"] == pytest.approx(
        {"cb.register": 0.040, "cb.wait": 0.020, "cb.wait+cb.submit": 0.015})


def test_work_outranks_wait_and_program_spans_outrank_cb():
    planes = [_host(_caller(("cb.wait", 5, 95)),
                    ("sage-worker", [("sage.wait.data", 10, 40)]),
                    ("sage-loader-0", [("sage.load.h2d", 35, 50)]),
                    ("sage-worker2", [("sage.wait.compute_lock", 60, 90)])),
              _device((0, 10), (50, 60), (90, 100))]
    a = il.attribute(planes, chips=1)
    # [10, 50]: 30 ms of waiting, but 15 ms of work; [60, 90]: a wait only
    assert a["idle_gaps"] == [["sage.load.h2d", pytest.approx(0.040)],
                              ["sage.wait.compute_lock", pytest.approx(0.030)]]


def test_self_time_beats_an_enclosing_span():
    planes = [_host(_caller(("cb.wait", 0, 100)),
                    ("worker", [("sage.forward", 10, 40),
                                ("sage.launch", 12, 38),
                                ("sage.forward", 60, 90),
                                ("sage.wait.data", 60, 90)])),
              _device((0, 10), (40, 60), (90, 100))]
    a = il.attribute(planes, chips=1)
    # [10, 40]: the launch owns 26 ms of it, the forward 4; [60, 90]: the
    # forward's self time is nil, the nested wait owns the gap
    assert a["idle_gaps"] == [["sage.launch+sage.forward", pytest.approx(0.030)],
                              ["sage.wait.data", pytest.approx(0.030)]]


def test_idle_by_label_sums_to_the_idle_time_over_two_chips():
    dev1 = Plane("/device:TPU:1", [Line("XLA Ops", [("%x = y", 0, 100 * MS)])])
    planes = [_host(_caller(("cb.wait", 0, 100)),
                    ("worker", [("sage.forward", 20, 70)])),
              _device((0, 20), (70, 80)), dev1]
    r = tr.reduce(planes, chips=2)
    a = il.attribute(planes, chips=2)
    assert sum(a["idle_by_label"].values()) == pytest.approx(
        r["window_s"] - r["busy_s"])
    assert a["idle_by_label"] == pytest.approx(
        {"sage.forward": 0.025, "cb.wait": 0.010})
    with pytest.raises(ValueError):
        il.attribute(planes[1:], chips=1)


def test_self_pieces_of_nested_spans():
    assert il.self_pieces([("a", 0, 10), ("b", 2, 5), ("c", 5, 7),
                           ("d", 12, 13)]) == [
        ("a", 0, 2), ("b", 2, 5), ("c", 5, 7), ("a", 7, 10), ("d", 12, 13)]


def _run(*substages):
    invs = [SimpleNamespace(record=SimpleNamespace(substages=s, stages={}))
            for s in substages]
    return SimpleNamespace(served=invs)


@pytest.mark.parametrize("metric,key", [
    ("forward_ms.tput", "forward"),
    ("compute_queue_ms.lat", "compute_queue"),
    ("weights_queue_ms", "weights_queue"),
    ("weights_admit_ms", "weights_admit"),
    ("weights_h2d_ms", "weights_h2d"),
])
def test_substage_readers(metric, key):
    mod = harness.load_module(harness.HERE / "metrics" / f"{metric}.py")
    # the mean over the records that carry the key, in ms
    assert mod.read(_run({key: 0.002}, {}, {key: 0.004})) == pytest.approx(3.0)
    assert mod.read(_run({}, {})) is None
    # a program whose records have no substages: no value, no error
    parent = SimpleNamespace(served=[SimpleNamespace(
        record=SimpleNamespace(stages={"compute": 0.1}))])
    assert mod.read(parent) is None


def test_cli_exits_nonzero_without_a_tpu():
    p = subprocess.run(
        [sys.executable, "chipbench/idle_labels.py", "--workload",
         "mamba2-780m.zipf16", "--seed", str(2**31 + 5), "--seconds", "1"],
        cwd=_paths.ROOT, env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=300)
    assert p.returncode != 0 and "no TPU" in p.stderr
    assert "idle_labels" not in p.stdout
