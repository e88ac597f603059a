"""Mean ``gpu_data`` stage (wait for weights and input in HBM) of cold
invocations."""
from readers import mean_stage_ms


def read(run):
    return mean_stage_ms(run, "gpu_data", cold=True)
