"""Arithmetic the metric readers in ``metrics/`` share."""
from __future__ import annotations

import math
from typing import Optional

from harness import WAIT_PAST_CLOSE_S, RunData
from stats import percentile


def latency_ms(run: RunData, q: float) -> float:
    """The ``q``-th percentile of client latency, from each invocation's
    due time to the return of its ``wait()``, over every invocation due in
    the window. One that failed or never came counts as the longest wait
    the benchmark makes (the window's end plus a minute)."""
    cap = (run.seconds + WAIT_PAST_CLOSE_S) * 1e3
    return percentile([min(i.latency_ms, cap) for i in run.invs], q)


def mean_stage_ms(run: RunData, stage: str, *, cold: bool = False) -> Optional[float]:
    """Mean of an ``InvocationRecord`` stage over the window's served
    invocations, or over the cold ones among them."""
    xs = [i.record.stages[stage] for i in run.served
          if stage in i.record.stages and (i.cold or not cold)]
    return 1e3 * sum(xs) / len(xs) if xs else None


def forward_module(run: RunData):
    """(executions, device seconds) of the compiled program that took most
    of the traced span: the served forward, in a cell that runs one."""
    if run.trace is None or not run.trace["modules"]:
        return None
    return max(run.trace["modules"].values(), key=lambda cs: cs[1])


def roofline_s(run: RunData) -> float:
    """The least time one forward could take on this chip."""
    c, p = run.counts, run.peaks
    return max(c["flops"] / p["bf16_flops_per_s"], c["bytes"] / p["hbm_bytes_per_s"])


def share(num: float, den: float) -> Optional[float]:
    return 100.0 * num / den if den > 0 and not math.isnan(num) else None
