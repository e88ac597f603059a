"""Mean ``weights_queue`` substage: a read-only weight load's wait for a
loader worker, over the invocations that claimed a load."""
from substages import mean_substage_ms


def read(run):
    return mean_substage_ms(run, "weights_queue")
