"""The least time one forward could take (FLOPs over peak FLOP/s, or bytes
over peak bandwidth, whichever is larger) over the forward program's mean
device time in the traced span, in percent."""
from readers import forward_module, roofline_s, share


def read(run):
    fwd = forward_module(run)
    if fwd is None or fwd[0] == 0:
        return None
    return share(roofline_s(run), fwd[1] / fwd[0])
