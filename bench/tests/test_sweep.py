"""The rate sweep's knee: sustained by completions and by the tail."""
import pytest

from bench import sweep


def _pt(rate, p95, first=None, last=None, share=1.0):
    return {"offered_per_s": rate, "completed_share": share,
            "completed_per_s": share * rate, "p95_ms": p95,
            "first_quarter_p95_ms": p95 if first is None else first,
            "last_quarter_p95_ms": p95 if last is None else last}


def test_knee_is_the_last_rate_whose_tail_stays_near_its_base():
    pts = [_pt(40, 10), _pt(40, 12), _pt(60, 20), _pt(60, 30),
           _pt(80, 30), _pt(80, 40), _pt(100, 12)]
    k = sweep.knee(pts)
    assert k["base_p95_ms"] == 11.0
    assert k["sustained"] == {"40": True, "60": True, "80": False,
                              "100": True}
    # A rate sustained past one that was not does not count.
    assert k["knee_per_s"] == 60


@pytest.mark.parametrize("point, ok", [
    (_pt(100, 20), True),
    (_pt(100, 20, share=0.95), False),           # a backlog left over
    (_pt(100, 20, first=10, last=25), False),    # the tail grows
    (_pt(100, 20, first=5, last=20), True),      # within twice the base
    (_pt(100, 34), False),                       # over 3x the base
])
def test_sustained(point, ok):
    assert sweep.sustained(point, base_p95_ms=11.0) is ok
