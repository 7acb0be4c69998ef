"""Per layer (service): what the service waits on the chip per flush, in
ms: the program's ``service.device_wait`` spans over its
``service.flush`` spans."""
from bench.program_spans import ms_per


def read(run):
    return ms_per(run, "service.device_wait", per="service.flush")
