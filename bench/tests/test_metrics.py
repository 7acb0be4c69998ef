"""Every metric reader against inputs computed by hand."""
import json
import types

import pytest

from bench import generator, harness, tracing
from bench.tests.tiny import BENCH, ROOT

MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())
HOTSPOT = json.loads((BENCH / "configs" / "hotspot2d.json").read_text())
KIND = "TPU v5 lite"


def _run(window, trace=None, setup_s=12.5, chips=1):
    cell = types.SimpleNamespace(config=HOTSPOT, chips=chips)
    return harness.Run(cell=cell, window=window, setup_s=setup_s,
                       device_kind=KIND, trace=trace)


def _solve_window(n=10, seconds=2.0, dispatches=80):
    grid = (8192, 8192)
    return generator.Window(
        seconds=seconds, attempted=n, completed=n, failed=0,
        work=[{"grid": grid, "n_steps": 64, "count": n}],
        counters={"dispatches": dispatches, "solves": n})


def _serve_window():
    return generator.Window(
        seconds=4.0, attempted=20, completed=19, failed=1,
        work=[{"grid": (512, 512), "n_steps": 16, "count": 19}],
        latencies_s=[0.001 * (i + 1) for i in range(20)],
        counters={"dispatches": 6, "problems": 19, "pad_rows": 5,
                  "bucket_failures": 0, "failed": 0})


def _read(name, run):
    return harness.reader(ROOT, name)(run)


def test_every_manifest_metric_has_a_reader():
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        assert callable(harness.reader(ROOT, m["name"]))


def test_end_to_end_readers():
    run = _run(_solve_window())
    assert _read("gcells_per_s", run) == pytest.approx(
        10 * 8192 * 8192 * 64 / 2.0 / 1e9)
    assert _read("setup_s", run) == 12.5
    serve = _run(_serve_window())
    # numpy's linear percentile of 1..20 ms at 95%: 19.05 ms
    assert _read("latency_p95_ms", serve) == pytest.approx(19.05)
    assert _read("requests_per_s", serve) == pytest.approx(19 / 4.0)


def test_counter_readers():
    assert _read("dispatches_per_solve", _run(_solve_window())) == 8.0
    assert _read("pad_share.serve", _run(_serve_window())) == \
        pytest.approx(100 * 5 / 24)
    assert _read("dispatches_per_solve", _run(_serve_window())) is None


def test_trace_readers():
    win = _solve_window()
    trace = tracing.Summary(kernel_s=1.6, busy_s=1.9, window_s=2.0,
                            device_ops=[], idle_gaps=[])
    run = _run(win, trace)
    ops = 10 * 10 * 8192 * 8192 * 64      # 10 ops per update
    from bench import roofline
    vpu = roofline.peaks(KIND)["vpu_f32_ops_per_s"]
    least = ops / vpu                     # VPU-bound
    assert _read("engine_roofline.solve", run) == pytest.approx(
        100 * least / 1.6)
    assert _read("step_mfu.solve", run) == pytest.approx(100 * least / 2.0)
    assert _read("device_idle_share.solve", run) == pytest.approx(5.0)


@pytest.mark.parametrize("name", ["engine_roofline.solve",
                                  "engine_roofline.serve",
                                  "step_mfu.solve", "step_mfu.serve",
                                  "device_idle_share.solve",
                                  "device_idle_share.serve"])
def test_trace_readers_find_nothing_without_a_device_trace(name):
    assert _read(name, _run(_solve_window())) is None
    empty = tracing.Summary(0.0, 0.0, 2.0, [], [])
    assert _read(name, _run(_solve_window(), empty)) is None


def test_step_mfu_counts_the_cells_chips():
    """The least time of the work on four chips is one chip's over
    four; on one chip the reading is the one-chip formula itself."""
    trace = tracing.Summary(kernel_s=1.6, busy_s=1.9, window_s=2.0,
                            device_ops=[], idle_gaps=[])
    one = _read("step_mfu.solve", _run(_solve_window(), trace))
    four = _read("step_mfu.solve", _run(_solve_window(), trace, chips=4))
    assert four == pytest.approx(one / 4)
    run = _run(_solve_window(), trace)
    assert one == 100.0 * run.count["roofline_s"] / trace.window_s
    # The kernels' share is summed over devices already: no chips in it.
    assert _read("engine_roofline.solve", _run(_solve_window(), trace,
                                               chips=4)) == \
        _read("engine_roofline.solve", run)


def test_halo_exposed_share():
    """Collective time summed over the devices, over devices x window."""
    trace = tracing.Summary(kernel_s=1.6, busy_s=1.9, window_s=2.0,
                            device_ops=[], idle_gaps=[], collective_s=0.4,
                            devices=4)
    run = _run(_solve_window(), trace, chips=4)
    assert _read("halo_exposed_share.solve", run) == pytest.approx(5.0)
    for t in (None, tracing.Summary(1.6, 1.9, 2.0, [], []),
              tracing.Summary(1.6, 1.9, 2.0, [], [], collective_s=0.0,
                              devices=4)):
        assert _read("halo_exposed_share.solve",
                     _run(_solve_window(), t, chips=4)) is None
