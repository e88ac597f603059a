"""The held experts' grouped products alone: the least time they could
take over their device time a forward, in percent. FLOPs are 6 d f a row
(gate, up and down projections) over the measured mean ``expert_rows``;
bytes are the held experts' weights, read once, and each row in and out.
Their device time is that of the operations the Pallas megablox kernel runs
as, ``gmm`` and ``gmm.<n>`` in the trace, over the forward program's
executions in the traced span. A program whose records lack the counters,
or a trace with no such operation, gives no value."""
import re

from expert_counts import mean_counter
from readers import forward_module, share

KERNEL = re.compile(r"gmm(\.\d+)?")


def read(run):
    fwd, rows = forward_module(run), mean_counter(run, "expert_rows")
    if fwd is None or fwd[0] == 0 or rows is None:
        return None
    kernel_s = sum(s for name, s in run.trace["ops"].items()
                   if KERNEL.fullmatch(name))
    if kernel_s == 0:
        return None
    c, p = run.counts, run.peaks
    best = max(rows * c["expert_row_flops"] / p["bf16_flops_per_s"],
               (c["expert_weight_bytes"] + rows * c["expert_row_bytes"])
               / p["hbm_bytes_per_s"])
    return share(best, kernel_s / fwd[0])
