"""Per layer (entry points): host time in the program's ``ops.sweep``
spans (the engine calls that enqueue a solve's blocked sweeps) per
``ops.stencil_run`` call, in ms."""
from bench.program_spans import ms_per


def read(run):
    return ms_per(run, "ops.sweep", per="ops.stencil_run")
