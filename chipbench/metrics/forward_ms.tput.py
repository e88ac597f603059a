"""Mean ``forward`` substage: the time an invocation holds the node's
compute lock (the forward and its host copy), less its own data wait."""
from substages import mean_substage_ms


def read(run):
    return mean_substage_ms(run, "forward")
