"""The whole run on the CPU at the program's reduced width: set-up, the
window, the check. Sound runs come out correct; the control (the reference
in float8) and each fault planted in the timed path come out not correct.

The faults a serving cell can have: an answer altered where it is
produced, a function served with another function's weights, and an
invocation that fails. (The training faults, and a batch half left out or
an exchange between chips left out, do not exist in these cells: requests
are batch 1 and the nodes exchange nothing.)
"""
import dataclasses
import itertools
import threading
import time

import pytest

import _paths
import calibrate
import harness

SEED = 2**31 + 4242
CELLS = {
    "closed": ("qwen2.5-3b.closed16", "qwen2-tiny.json", "closed-tiny.json"),
    "open": ("mamba2-780m.zipf16", "mamba2-tiny.json", "open-tiny.json"),
}


def _cell(kind):
    name, cfg, traffic = CELLS[kind]
    return harness.load_cell(_paths.ROOT, name, config_file=_paths.DATA / cfg,
                             traffic_file=_paths.DATA / traffic)


def _run(kind, seed=SEED, **kw):
    return harness.Run(_cell(kind), seed=seed, seconds=1.0, trace=False,
                       t_start=time.perf_counter(), require_tpu=False,
                       log=lambda s: None, **kw).result()


@pytest.mark.parametrize("kind", sorted(CELLS))
def test_sound_run_is_correct(kind):
    out = _run(kind)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert list(out)[-1] == "checks"
    want = {m["name"] for m in _cell(kind).metrics(trace=False)}
    assert set(out["metrics"]) == want
    assert out["metrics"]["setup_s"]["value"] > 0


def test_control_reads_above_the_limit_and_three_times_the_program():
    cell = _cell("open")
    ((_, prog, ctrl),) = calibrate.readings(cell, [SEED], 1.0,
                                            require_tpu=False, log=lambda s: None)
    limit = cell.config["logit_err_limit"]
    assert prog <= limit < ctrl
    assert ctrl >= 3 * prog


def _answer_altered(monkeypatch):
    import repro.core.functions as F

    served = F.served_logits

    def altered(cfg):
        f = served(cfg)
        return lambda p, t: f(p, t).at[:, -1, 0].add(0.1)

    monkeypatch.setattr(F, "served_logits", altered)


def _wrong_weights(monkeypatch):
    import repro.core.functions as F

    make, first = F.make_model_function, {}

    def same(db, name, *a, params=None, **kw):
        first.setdefault("p", params)
        return make(db, name, *a, params=first["p"], **kw)

    monkeypatch.setattr(F, "make_model_function", same)


def _invocation_fails(monkeypatch):
    import repro.core.functions as F

    make, armed, n = F.make_model_function, threading.Event(), itertools.count()

    def failing(*a, **kw):
        fn = make(*a, **kw)

        def handler(shim, req):
            if armed.is_set() and next(n) % 2:
                raise RuntimeError("injected failure")
            return fn.handler(shim, req)

        return dataclasses.replace(fn, handler=handler)

    window = harness.Run._window

    def arm_then_window(self):
        armed.set()
        return window(self)

    monkeypatch.setattr(F, "make_model_function", failing)
    monkeypatch.setattr(harness.Run, "_window", arm_then_window)


@pytest.mark.parametrize("fault,kind,caught_by", [
    (_answer_altered, "closed", "logit_err"), (_answer_altered, "open", "logit_err"),
    (_wrong_weights, "open", "logit_err"), (_invocation_fails, "open", "failed")])
def test_fault_in_the_timed_path_is_not_correct(monkeypatch, fault, kind, caught_by):
    fault(monkeypatch)
    out = _run(kind)
    assert not out["correct"], out["checks"]
    c = out["checks"][caught_by]
    assert c["value"] > c["limit"], out["checks"]
