"""Invocations completed inside the window per second and chip."""


def read(run):
    done = sum(i.ok and i.done <= run.t_close for i in run.invs)
    return done / (run.seconds * run.cell.chips)
