"""Set-up: from process start to the first instant of the window."""


def read(run):
    return run.setup_s
