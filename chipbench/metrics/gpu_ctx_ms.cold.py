"""Mean ``gpu_ctx`` stage (context built or reused) of cold invocations."""
from readers import mean_stage_ms


def read(run):
    return mean_stage_ms(run, "gpu_ctx", cold=True)
