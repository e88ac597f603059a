"""The served forward's FLOPs completed in the traced span over the chips'
peak in that span, in percent; the held experts' FLOPs are counted from
the measured mean ``expert_rows``. A program whose records lack the
counters gives no value."""
from expert_counts import measured_flops
from readers import forward_module, share


def read(run):
    fwd, flops = forward_module(run), measured_flops(run)
    if fwd is None or flops is None:
        return None
    t = run.trace
    peak = t["window_s"] * t["devices"] * run.peaks["bf16_flops_per_s"]
    return share(fwd[0] * flops, peak)
