"""The traffic generator: the same seed gives the same work."""
import json

import numpy as np
import pytest

from bench import generator
from bench.tests.tiny import BENCH

ENSEMBLE = json.loads((BENCH / "traffic" / "ensemble.json").read_text())
HOTSPOT = json.loads((BENCH / "configs" / "hotspot2d.json").read_text())
BIG_SEED = 2 ** 31 + 12345


@pytest.mark.parametrize("seed", [0, 7, BIG_SEED])
def test_schedule_is_deterministic(seed):
    a = generator.schedule(ENSEMBLE, seed, 10.0)
    b = generator.schedule(ENSEMBLE, seed, 10.0)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)


def test_seeds_reorder_the_same_work():
    """Every seed gets the same gaps and the same count of each size."""
    arr1, size1, _ = generator.schedule(ENSEMBLE, 1, 10.0)
    arr2, size2, _ = generator.schedule(ENSEMBLE, BIG_SEED, 10.0)
    n = round(ENSEMBLE["rate_per_s"] * 10.0)
    assert len(arr1) == len(arr2) == n
    np.testing.assert_allclose(np.sort(np.diff(arr1, prepend=0.0)),
                               np.sort(np.diff(arr2, prepend=0.0)),
                               atol=1e-12)
    assert arr1[-1] == pytest.approx(arr2[-1], rel=1e-12)
    assert np.array_equal(np.bincount(size1), np.bincount(size2))
    assert abs(np.bincount(size1)[0] - np.bincount(size1)[1]) <= 1
    assert not np.array_equal(size1, size2)
    assert np.all(np.diff(arr1) >= 0) and arr1[0] > 0.0


def test_mean_gap_is_the_offered_rate():
    arr, _, _ = generator.schedule(dict(ENSEMBLE, rate_per_s=250), 3, 8.0)
    assert len(arr) == 2000
    assert arr[-1] == pytest.approx(8.0, rel=0.05)


def test_problems_are_deterministic_and_in_range():
    key = generator.prng_key(BIG_SEED)
    a = generator.make_problems(HOTSPOT, (8, 128), 2, key)
    b = generator.make_problems(HOTSPOT, (8, 128), 2, key)
    for pa, pb in zip(a, b):
        for name in ("x", "power"):
            np.testing.assert_array_equal(np.asarray(pa[name]),
                                          np.asarray(pb[name]))
    x = np.asarray(a[0]["x"])
    assert x.dtype == np.float32 and 70.0 <= x.min() and x.max() <= 80.0
    assert not np.array_equal(x, np.asarray(a[1]["x"]))
    other = generator.make_problems(HOTSPOT, (8, 128), 2,
                                    generator.prng_key(BIG_SEED + 2 ** 32))
    assert not np.array_equal(x, np.asarray(other[0]["x"]))


def test_negative_seed_is_refused():
    with pytest.raises(ValueError):
        generator.prng_key(-1)

