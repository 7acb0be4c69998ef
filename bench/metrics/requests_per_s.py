"""End to end: requests completed over the window (window start to the
return of the last flush)."""


def read(run):
    w = run.window
    return w.completed / w.seconds if w.seconds > 0 else None
