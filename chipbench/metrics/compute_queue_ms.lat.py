"""Mean ``compute_queue`` substage: an invocation's wait for the node's
compute lock, behind the invocations holding or queued for it."""
from substages import mean_substage_ms


def read(run):
    return mean_substage_ms(run, "compute_queue")
