"""Mean ``weights_admit`` substage: a read-only weight load's wait for
device admission, evictions included, over the invocations that claimed a
load."""
from substages import mean_substage_ms


def read(run):
    return mean_substage_ms(run, "weights_admit")
