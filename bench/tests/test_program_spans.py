"""The per-layer metrics that read the program's own spans: on snapshots
made by hand, and through the whole harness at tiny sizes, where a
traced window reports exactly the span metrics its cell lists."""
import json
import time
import types

import pytest

from bench import harness, program_spans
from bench.tests.tiny import ROOT, tiny_root
from repro import spans

MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
SPAN_METRICS = {m["name"]: m for m in MANIFEST["per_layer"]
                if m["source"] == "program_span"}
TRACED = types.SimpleNamespace(trace=object())


def _agg(count, total_ns):
    return {"count": count, "total_ns": total_ns, "self_ns": total_ns,
            "max_ns": total_ns}


SOLVES = {"ops.stencil_run": _agg(4, 40_000_000),
          "ops.plan": _agg(4, 6_000_000),
          "ops.sweep": _agg(32, 10_000_000)}
FLUSHES = {"service.flush": _agg(10, 30_000_000),
           "service.device_wait": _agg(12, 4_000_000),
           "service.stack": _agg(12, 2_000_000)}


def _read(name, snap, monkeypatch, run=TRACED):
    monkeypatch.setattr(spans, "snapshot", lambda: snap)
    return harness.reader(ROOT, name)(run)


def test_the_manifest_lists_the_four_span_metrics():
    assert set(SPAN_METRICS) == {"plan_ms.solve", "enqueue_ms.solve",
                                 "service_host_ms.serve",
                                 "device_wait_ms.serve"}
    for m in SPAN_METRICS.values():
        assert m["unit"] == "ms" and m["better"] == "lower"
        assert m["workloads"]


@pytest.mark.parametrize("name,want", [
    ("plan_ms.solve", 1.5),                 # 6 ms over 4 solves
    ("enqueue_ms.solve", 2.5),              # 10 ms over 4 solves
    ("service_host_ms.serve", 2.6),         # (30 - 4) ms over 10 flushes
    ("device_wait_ms.serve", 0.4),          # 4 ms over 10 flushes
])
def test_readers_on_hand_made_snapshots(name, want, monkeypatch):
    snap = {**SOLVES, **FLUSHES}
    assert _read(name, snap, monkeypatch) == pytest.approx(want)
    assert _read(name, {}, monkeypatch) is None
    untraced = types.SimpleNamespace(trace=None)
    assert _read(name, snap, monkeypatch, untraced) is None


def test_readers_need_the_span_they_divide_by(monkeypatch):
    assert _read("plan_ms.solve", {"ops.plan": _agg(1, 5)},
                 monkeypatch) is None
    assert _read("enqueue_ms.solve", FLUSHES, monkeypatch) is None
    assert _read("device_wait_ms.serve", SOLVES, monkeypatch) is None
    # A window whose buckets all failed waits on nothing: its host
    # work is the whole flush.
    only = {"service.flush": _agg(2, 3_000_000)}
    assert _read("service_host_ms.serve", only, monkeypatch) == 1.5
    assert _read("device_wait_ms.serve", only, monkeypatch) is None


def test_a_program_without_spans_reads_nothing(monkeypatch):
    """The benchmark's files laid over a program that has no
    ``repro.spans``: the readers find nothing and raise nothing."""
    import sys

    import repro
    monkeypatch.delattr(repro, "spans")
    monkeypatch.setitem(sys.modules, "repro.spans", None)
    assert program_spans.snapshot(TRACED) is None
    assert program_spans.ms_per(TRACED, "ops.plan", "ops.stencil_run") \
        is None


@pytest.mark.parametrize("cell,window_span,counter", [
    ("hotspot2d.solve", "ops.stencil_run", "solves"),
    ("hotspot2d.ensemble", "service.dispatch", "dispatches"),
])
def test_traced_window_reports_the_span_metrics_its_cell_lists(
        tmp_path, cell, window_span, counter):
    root = tiny_root(tmp_path)
    spans.reset()
    t0 = time.perf_counter()
    r, _ = harness.run_cell(root, cell, 2 ** 33 + 7, 0.3, False, t0)
    assert r["correct"]
    assert spans.snapshot() == {}           # untraced: nothing recorded
    r, plan = harness.run_cell(root, cell, 2 ** 33 + 7, 0.3, True, t0)
    assert r["correct"]
    listed = {n for n, m in SPAN_METRICS.items() if cell in m["workloads"]}
    got = {n for n in r["metrics"] if n in SPAN_METRICS}
    assert got == listed
    assert all(r["metrics"][n]["value"] > 0 for n in got)
    # The window's spans alone: set-up ran the same calls untraced.
    snap = spans.snapshot()
    assert snap[window_span]["count"] == plan["counters"][counter] > 0
    if cell == "hotspot2d.ensemble":
        f = snap["service.flush"]
        host = r["metrics"]["service_host_ms.serve"]["value"]
        wait = r["metrics"]["device_wait_ms.serve"]["value"]
        assert host + wait == pytest.approx(
            f["total_ns"] / f["count"] / 1e6)
    else:
        run = snap["ops.stencil_run"]
        assert (r["metrics"]["plan_ms.solve"]["value"]
                + r["metrics"]["enqueue_ms.solve"]["value"]) <= \
            run["total_ns"] / run["count"] / 1e6
