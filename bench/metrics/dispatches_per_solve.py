"""Per layer (planner): engine dispatches (ops.dispatch_count) over the
solves of the window: ceil(n_steps / bt) of the plan the program chose."""


def read(run):
    c = run.window.counters
    if not c.get("solves"):
        return None
    return c["dispatches"] / c["solves"]
