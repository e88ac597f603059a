"""Arithmetic the readers of the program's own durations share: the mean
of an ``InvocationRecord.substages`` key over the window's served
invocations that carry it. A program whose records have no ``substages``
gives no value, and the metric is left out of the result line."""
from __future__ import annotations

from typing import Optional


def mean_substage_ms(run, key: str) -> Optional[float]:
    xs = [i.record.substages[key] for i in run.served
          if key in getattr(i.record, "substages", ())]
    return 1e3 * sum(xs) / len(xs) if xs else None
