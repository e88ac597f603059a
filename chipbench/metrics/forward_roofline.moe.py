"""The least time one forward could take (FLOPs over peak FLOP/s, or bytes
over peak bandwidth, whichever is larger) over the forward program's mean
device time in the traced span, in percent; the held experts' FLOPs are
counted from the measured mean ``expert_rows``, not the expectation. A
program whose records lack the counters gives no value."""
from expert_counts import measured_flops
from readers import forward_module, share


def read(run):
    fwd, flops = forward_module(run), measured_flops(run)
    if fwd is None or fwd[0] == 0 or flops is None:
        return None
    c, p = run.counts, run.peaks
    best = max(flops / p["bf16_flops_per_s"], c["bytes"] / p["hbm_bytes_per_s"])
    return share(best, fwd[1] / fwd[0])
