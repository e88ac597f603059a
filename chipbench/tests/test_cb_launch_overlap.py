"""The reader of ``launch_overlap_share.tput``: the share of served
invocations whose program was enqueued behind an unfinished one, and no
value from a program whose records lack the field."""
from types import SimpleNamespace

import pytest

import _paths  # noqa: F401
import harness


def _run(*records):
    return SimpleNamespace(served=[SimpleNamespace(record=r) for r in records])


@pytest.fixture
def metric():
    return harness.load_module(
        harness.HERE / "metrics" / "launch_overlap_share.tput.py")


def test_share_of_served_invocations_enqueued_behind_another(metric):
    recs = [SimpleNamespace(launch_overlapped=v)
            for v in (True, True, False, None)]
    assert metric.read(_run(*recs)) == pytest.approx(50.0)


def test_no_value_on_records_without_the_field(metric):
    parent = SimpleNamespace(stages={"compute": 0.1}, substages={})
    assert metric.read(_run(parent, parent)) is None
    assert metric.read(_run()) is None
