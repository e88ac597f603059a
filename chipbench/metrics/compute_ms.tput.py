"""Mean ``compute`` stage (the forward and its host copy) in the window."""
from readers import mean_stage_ms


def read(run):
    return mean_stage_ms(run, "compute")
