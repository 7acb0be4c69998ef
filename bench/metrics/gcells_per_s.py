"""End to end: cell-updates completed (cells x steps x whole solves)
over the time from window start to the end of the last solve, in 1e9/s."""


def read(run):
    w = run.window
    return w.cell_updates / w.seconds / 1e9 if w.completed else None
