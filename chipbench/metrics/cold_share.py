"""Cold starts over invocations in the window, in percent. A cold start is
a load of a function's read-only weights into HBM: the daemon's ``loads``
(each invocation also loads its own input once) plus its promotions of a
host copy back to HBM."""
from readers import share


def read(run):
    c = run.counters
    weight_loads = c["loads"] + c["host_promotions"] - len(run.served)
    return share(weight_loads, len(run.invs))
