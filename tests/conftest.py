import os

# Tests must see exactly the host's real device (the dry-run, and only
# the dry-run, forces 512 fake devices — see launch/dryrun.py).
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture
def traced(tmp_path):
    """Run ``fn()`` under a ``jax.profiler`` session and return its
    result, ``repro.spans.snapshot()`` after the session, and the host
    plane's program spans as ``(name, start_ns, end_ns, stats)``, sorted
    by start (spans named ``ops.*``, ``service.*`` or ``test.*``)."""
    import glob
    import warnings

    import jax
    from jax.profiler import ProfileData

    from repro import spans

    def run(fn):
        jax.profiler.start_trace(str(tmp_path / "trace"))
        try:
            out = fn()
        finally:
            jax.profiler.stop_trace()
        snap = spans.snapshot()
        (path,) = glob.glob(str(tmp_path / "trace" / "plugins" / "profile"
                                / "*" / "*.xplane.pb"))
        with warnings.catch_warnings():   # jaxlib's stats type
            warnings.simplefilter("ignore", DeprecationWarning)
            events = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                       dict(e.stats))
                      for plane in ProfileData.from_file(path).planes
                      if plane.name.startswith("/host:")
                      for line in plane.lines for e in line.events
                      if e.name.startswith(("ops.", "service.", "test."))]
        return out, snap, sorted(events, key=lambda e: e[1])

    return run
