import os

# The benchmark's tests run on the CPU, at tiny sizes, through the
# Pallas interpreter.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _own_tuner_cache(tmp_path, monkeypatch):
    """Plans the tests make stay out of the checkout."""
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE",
                       str(tmp_path / "autotune.json"))
