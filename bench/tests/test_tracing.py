"""The trace reduction: kernel time, union-busy idle share, and the
attribution of idle gaps to the host span open at the time."""
import pytest

from bench import tracing

MS = 1_000_000  # ns


def _trace():
    """A window of 100 ms on one chip: two kernels (custom calls) and a
    copy, with the copy overlapping the second kernel; the host is in
    ``block_until_ready`` and ``stencil_run`` during the gaps."""
    k2 = '%k.2 = f32[8]{0} custom-call(), custom_call_target="tpu_custom_call"'
    k4 = '%k.4 = f32[8]{0} custom-call(), custom_call_target="tpu_custom_call"'
    return {
        "devices": {"/device:TPU:0": [
            ["fusion.1", 5 * MS, 5 * MS],           # 5..10
            [k2, 10 * MS, 30 * MS],                 # 10..40
            ["copy.3", 35 * MS, 10 * MS],           # 35..45 overlaps
            [k4, 60 * MS, 50 * MS],                 # 60..110, clipped
        ]},
        "spans": [
            ["window", 0, 100 * MS],
            ["stencil_run", 0, 4 * MS],                 # gap 0..5
            ["block_until_ready", 44 * MS, 20 * MS],    # gap 45..60
            ["stencil_run", 200 * MS, 1 * MS],          # outside
        ],
    }


def test_reduce_hand_computed():
    s = tracing.reduce(_trace())
    assert s.window_s == pytest.approx(0.100)
    # union: 5..45 and 60..100 -> 80 ms busy
    assert s.busy_s == pytest.approx(0.080)
    # kernels inside the window: 30 + 40 (clipped at 100) ms
    assert s.kernel_s == pytest.approx(0.070)
    ops = dict(s.device_ops)
    # k.2 and k.4 are one kernel, called twice: 30 + 40 ms
    assert ops["k f32[8] custom-call"] == pytest.approx(0.070)
    assert ops["copy.3"] == pytest.approx(0.010)
    assert [n for n, _ in s.device_ops] == ["k f32[8] custom-call",
                                            "copy.3", "fusion.1"]
    gaps = dict(s.idle_gaps)
    assert gaps == pytest.approx({"stencil_run": 0.005,
                                  "block_until_ready": 0.015})


def test_innermost_span_takes_the_gap():
    t = _trace()
    t["spans"].append(["flush", 50 * MS, 5 * MS])   # inside the bur span
    gaps = dict(tracing.reduce(t).idle_gaps)
    assert gaps["flush"] == pytest.approx(0.015)
    assert "block_until_ready" not in gaps


def test_gap_with_no_span_is_none_and_devices_average():
    t = _trace()
    t["spans"] = [["window", 0, 100 * MS]]
    t["devices"]["/device:TPU:1"] = [
        ['%k = f32[8]{0} custom-call(), custom_call_target="tpu_custom_call"',
         0, 100 * MS]]
    s = tracing.reduce(t)
    assert s.busy_s == pytest.approx((0.080 + 0.100) / 2)
    assert dict(s.idle_gaps) == pytest.approx({"none": 0.010})
    assert s.kernel_s == pytest.approx(0.170)


def test_collective_time_on_two_devices():
    """Collective permutes (``-start``, ``-done``, synchronous) on two
    device planes: the union of their intervals inside the window on
    each device, summed over the devices; other ops are not counted,
    nor a fusion that takes a permute's result as its operand (the edge
    fusion of the sharded runner)."""
    cps = ('%collective-permute-start.3 = (f32[4,8]{1,0}, f32[4,8]{1,0}) '
           'collective-permute-start(f32[4,8]{1,0} %p), '
           'source_target_pairs={{0,1}}')
    cpd = ('%collective-permute-done.3 = f32[4,8]{1,0} '
           'collective-permute-done((f32[4,8]{1,0}, f32[4,8]{1,0}) %s)')
    cp = '%collective-permute.1 = f32[4,8]{1,0} collective-permute(%p)'
    k = '%k = f32[8]{0} custom-call(), custom_call_target="tpu_custom_call"'
    edge = ('%fusion.12 = (f32[12,8]{1,0}, f32[12,8]{1,0}) fusion('
            'f32[4,8]{1,0} %collective-permute-done.3, f32[8,8]{1,0} %q), '
            'kind=kLoop, calls=%fused_computation.12')
    t = {"devices": {
        "/device:TPU:0": [[cps, 0, 1 * MS], [k, 1 * MS, 40 * MS],
                          [cpd, 41 * MS, 4 * MS],
                          [cpd, 98 * MS, 5 * MS]],      # clipped to 2 ms
        "/device:TPU:1": [[cps, 0, 2 * MS], [k, 2 * MS, 50 * MS],
                          [cp, 60 * MS, 3 * MS],
                          ["fusion.9", 70 * MS, 10 * MS],
                          [edge, 80 * MS, 6 * MS]],
        "/device:TPU:2": []},
        "spans": [["window", 0, 100 * MS]]}
    s = tracing.reduce(t)
    assert s.collective_s == pytest.approx(0.001 + 0.004 + 0.002
                                           + 0.002 + 0.003)
    assert s.devices == 2
    assert tracing.is_collective(cps) and tracing.is_collective(cpd)
    assert tracing.is_collective(cp) and not tracing.is_collective(k)
    assert not tracing.is_collective(edge)
    assert tracing.is_collective("collective-permute-done.3")
    assert not tracing.is_collective("fusion.9")
    from bench.tests.test_metrics import _read, _run, _solve_window
    share = _read("halo_exposed_share.solve",
                  _run(_solve_window(), s, chips=2))
    assert share == pytest.approx(100 * 0.012 / (2 * 0.100))


def test_single_chip_trace_has_no_collective_time():
    s = tracing.reduce(_trace())
    assert s.collective_s == 0.0 and s.devices == 1


def test_empty_trace_reads_nothing():
    s = tracing.reduce({"devices": {}, "spans": []})
    assert (s.kernel_s, s.busy_s, s.window_s) == (0.0, 0.0, 0.0)


# ---------------------------------------------------------------------------
# Traces recorded on a TPU v5e (bench/tests/data/tpu_traces.json.gz).
# ---------------------------------------------------------------------------

def _recorded():
    import gzip
    import json
    import pathlib
    path = pathlib.Path(__file__).parent / "data" / "tpu_traces.json.gz"
    with gzip.open(path, "rt") as f:
        return json.load(f)


def _merged_busy(events):
    """Busy time by a second, plain method: mark every nanosecond edge
    and sum the covered stretches."""
    edges = sorted({s for _, s, _ in events}
                   | {s + d for _, s, d in events})
    busy = 0.0
    for a, b in zip(edges, edges[1:]):
        if any(s <= a and b <= s + d for _, s, d in events):
            busy += b - a
    return busy


@pytest.mark.parametrize("tag", ["hotspot", "serve"])
def test_recorded_trace(tag):
    t = _recorded()[tag]
    (plane, events), = t["devices"].items()
    assert plane == "/device:TPU:0"
    s = tracing.reduce(t)
    kernels = [e for e in events if "custom-call" in e[0]]
    assert kernels and all("tpu_custom_call" in e[0] for e in kernels)
    assert s.kernel_s == pytest.approx(sum(e[2] for e in kernels) * 1e-9)
    w0 = min(e[1] for e in events)
    w1 = max(e[1] + e[2] for e in events)
    assert s.window_s == pytest.approx((w1 - w0) * 1e-9)
    assert s.busy_s == pytest.approx(_merged_busy(events) * 1e-9)
    idle = dict(s.idle_gaps)
    assert sum(idle.values()) == pytest.approx(s.window_s - s.busy_s)
    assert s.kernel_s <= s.busy_s <= s.window_s


def test_recorded_hotspot_trace_is_the_2d_kernel():
    s = tracing.reduce(_recorded()["hotspot"])
    # 3 solves x 8 dispatches of the one 8192^2 kernel, ~16 ms each
    assert s.device_ops == [["stencil_call_program f32[8192,8192] "
                             "custom-call", pytest.approx(s.kernel_s)]]
    assert s.kernel_s == pytest.approx(0.386, rel=0.01)
    assert 1 - s.busy_s / s.window_s < 0.02
    assert [n for n, _ in s.idle_gaps] == ["block_until_ready"]


def test_recorded_serve_trace_charges_flush():
    s = tracing.reduce(_recorded()["serve"])
    assert 1 - s.busy_s / s.window_s > 0.9
    names = [n for n, _ in s.idle_gaps]
    assert names[0] == "flush"
    assert set(names) <= {"flush", "submit", "none"}
    assert any("f32[8,1024,1024]" in n or "f32[4,1024,1024]" in n
               for n, _ in s.device_ops)
