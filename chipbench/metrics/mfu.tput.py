"""The served forward's FLOPs completed in the traced span over the chips'
peak in that span, in percent."""
from readers import forward_module, share


def read(run):
    fwd = forward_module(run)
    if fwd is None:
        return None
    t = run.trace
    peak = t["window_s"] * t["devices"] * run.peaks["bf16_flops_per_s"]
    return share(fwd[0] * run.counts["flops"], peak)
