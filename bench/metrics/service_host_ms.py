"""Per layer (service): the service's own host work per flush, in ms: the
program's ``service.flush`` spans less their ``service.device_wait``
spans (grouping, stacking, upload and dispatch, copy to the host and
unstacking), over the flushes."""
from bench.program_spans import ms_per


def read(run):
    return ms_per(run, "service.flush", per="service.flush",
                  less="service.device_wait")
