"""Arithmetic the readers of the expert counters share: the counters
``InvocationRecord.expert_rows`` and ``expert_rows_max`` that a model with
dropless experts carries, and the FLOPs of a forward counted from them. A
program whose records lack the counters gives None."""
from __future__ import annotations

from typing import Optional


def mean_counter(run, name: str) -> Optional[float]:
    """Mean of one counter over the window's served invocations."""
    xs = [getattr(i.record, name) for i in run.served
          if getattr(i.record, name, None) is not None]
    return sum(xs) / len(xs) if xs else None


def measured_flops(run) -> Optional[float]:
    """FLOPs of one forward with the held experts' work counted from the
    measured mean ``expert_rows`` (summed over layers) in place of the
    family's uniform expectation."""
    rows = mean_counter(run, "expert_rows")
    if rows is None:
        return None
    c = run.counts
    return c["flops"] + (rows - c["expert_rows"]) * c["expert_row_flops"]
