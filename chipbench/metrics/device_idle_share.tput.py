"""Share of the traced span in which no operation ran on the device."""
from readers import share


def read(run):
    t = run.trace
    return None if t is None else share(t["window_s"] - t["busy_s"], t["window_s"])
