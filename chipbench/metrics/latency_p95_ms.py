"""95th-percentile client latency over every invocation due in the window."""
from readers import latency_ms


def read(run):
    return latency_ms(run, 95)
