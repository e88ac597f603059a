"""Share of the window's invocations dispatched to a node that held the
function's weights in HBM (``dispatch_tier == "device"``), in percent."""
from readers import share


def read(run):
    return share(sum(i.tier == "device" for i in run.invs), len(run.invs))
