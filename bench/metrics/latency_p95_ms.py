"""The 95th percentile of the latency of every request of the window,
from its scheduled arrival to the return of the flush that served it; a
request that failed counts with its wait to the window's end. Host
clock. Read per layer as ``latency_p95_ms.serve``: a host stall moves it
further than any bound the benchmark may set."""

import numpy as np


def read(run):
    lat = run.window.latencies_s
    return 1e3 * float(np.percentile(lat, 95)) if lat else None
