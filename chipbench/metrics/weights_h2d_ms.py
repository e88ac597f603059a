"""Mean ``weights_h2d`` substage: a read-only weight load's copy from
host to HBM, over the invocations that claimed a load."""
from substages import mean_substage_ms


def read(run):
    return mean_substage_ms(run, "weights_h2d")
