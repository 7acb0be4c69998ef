"""The program's own spans (``repro.spans``) as the per-layer readers see
them: the aggregates of the latest profiler session, which in a traced
run is the window (the program clears them when a session starts after
untraced set-up). Nothing to read in an untraced run, or from a program
that records no spans."""
from __future__ import annotations


def snapshot(run):
    """``repro.spans.snapshot()`` of a traced run, else None."""
    if run.trace is None:
        return None
    try:
        from repro import spans
    except ImportError:          # a program without spans
        return None
    return spans.snapshot()


def ms_per(run, name: str, per: str, less: str | None = None):
    """Milliseconds of span ``name`` (less those of span ``less``, where
    it was recorded) per recorded span ``per``; None where either of
    ``name`` and ``per`` was not recorded."""
    snap = snapshot(run)
    if not snap or name not in snap or not snap.get(per, {}).get("count"):
        return None
    ns = snap[name]["total_ns"] - snap.get(less, {}).get("total_ns", 0)
    return ns / snap[per]["count"] / 1e6
