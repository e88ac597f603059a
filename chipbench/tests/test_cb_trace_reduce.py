"""From a trace to busy time, idle share, per-operation time and labelled
idle gaps: on a synthetic trace, and the loader on one recorded here."""
from pathlib import Path

import pytest

import _paths  # noqa: F401
import trace_reduce as tr
from trace_reduce import Line, Plane

MS = 1e6  # ns


def _trace():
    host = Plane("/host:CPU", [Line("python3", [
        ("cb.traced", 0, 100 * MS),
        ("cb.submit", 10 * MS, 2 * MS),
        ("cb.wait", 12 * MS, 30 * MS),
        ("cb.register", 60 * MS, 30 * MS),
        ("PjitFunction(f)", 10 * MS, 1 * MS),  # not the benchmark's span
    ])])
    dev = Plane("/device:TPU:0", [
        Line("XLA Ops", [
            ("%fusion.1 = f32[2] fusion(x)", 15 * MS, 10 * MS),
            ("%fusion.2 = f32[2] fusion(y)", 20 * MS, 10 * MS),  # overlaps
            ("%copy.3 = f32[2] copy(z)", 50 * MS, 5 * MS),
            ("%fusion.1 = f32[2] fusion(x)", 95 * MS, 10 * MS),  # runs past end
        ]),
        Line("XLA Modules", [("jit_f(1)", 15 * MS, 15 * MS),
                             ("jit_f(1)", 50 * MS, 5 * MS),
                             ("jit_g(2)", 95 * MS, 10 * MS)]),
    ])
    other = Plane("/device:TPU:1", [Line("XLA Ops", [("%x = y", 0, 100 * MS)])])
    return [host, dev, other]


def test_busy_union_idle_ops_and_modules():
    r = tr.reduce(_trace(), chips=1)
    assert r["window_s"] == pytest.approx(0.1)
    # [15, 30] + [50, 55] + [95, 100] ms, clipped to the span
    assert r["busy_s"] == pytest.approx(0.025)
    assert r["ops"] == pytest.approx({"fusion.1": 0.015, "fusion.2": 0.010,
                                      "copy.3": 0.005})
    assert r["modules"] == {"jit_f(1)": (2, pytest.approx(0.020))}


def test_idle_gaps_longest_first_labelled_by_host_spans():
    r = tr.reduce(_trace(), chips=1, top=3)
    # gaps [55, 95], [30, 50] and [0, 15] ms; the last overlaps the wait
    # (12-15) more than the submit (10-12)
    assert r["idle_gaps"] == [["cb.register", pytest.approx(0.040)],
                              ["cb.wait", pytest.approx(0.020)],
                              ["cb.wait+cb.submit", pytest.approx(0.015)]]
    b = tr.breakdown(r, top=2)
    assert b["device_ops"][0] == ["fusion.1", pytest.approx(0.015)]
    assert len(b["idle_gaps"]) == 2


def test_two_chips_average_over_devices():
    r = tr.reduce(_trace(), chips=2)
    assert r["devices"] == 2
    assert r["busy_s"] == pytest.approx((0.025 + 0.1) / 2)


def test_missing_span_or_device_raises():
    host, dev, _ = _trace()
    with pytest.raises(ValueError):
        tr.reduce([dev], chips=1)
    with pytest.raises(ValueError):
        tr.reduce([host], chips=1)


def test_union():
    assert tr.union([(5, 6), (1, 3), (2, 4), (4, 4.5)]) == [(1, 4.5), (5, 6)]


def test_loader_reads_a_recorded_trace(tmp_path):
    import jax
    import jax.numpy as jnp

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
        with jax.profiler.TraceAnnotation("cb.submit"):
            jax.jit(lambda a: a @ a)(jnp.ones((64, 64))).block_until_ready()
    jax.profiler.stop_trace()
    (pb,) = list(Path(tmp_path).rglob("*.xplane.pb"))
    planes = tr.load(str(pb))
    spans = {e[0] for p in planes for ln in p.lines for e in ln.events}
    assert {tr.WINDOW_SPAN, "cb.submit"} <= spans
    # the CPU has no device plane: the reduction refuses rather than guess
    with pytest.raises(ValueError):
        tr.reduce(planes, chips=1)
