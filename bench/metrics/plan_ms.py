"""Per layer (planner): host time in the program's ``ops.plan`` span (the
blocking resolution and the out-of-core routing decision) per
``ops.stencil_run`` call, in ms."""
from bench.program_spans import ms_per


def read(run):
    return ms_per(run, "ops.plan", per="ops.stencil_run")
