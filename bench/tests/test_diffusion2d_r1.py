"""The cell ``diffusion2d_r1.solve`` (thesis Table 5-6, first-order 2D
diffusion): its configuration is the program's stencil, its count is
the benchmark's, and through the whole harness at a tiny size on the
CPU a sound run is correct, every fault is not, and a traced window
reports the span metrics the manifest lists for it."""
import json
import time

import pytest

from bench import generator, harness, roofline
from bench.tests import faults
from bench.tests.tiny import ROOT, tiny_root
from repro import spans

CELL = "diffusion2d_r1.solve"
TINY = {"grid": [16, 256], "n_steps": 4}
SEED = 2 ** 31 + 1601
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The benchmark as committed, this configuration cut to ``TINY``."""
    root = tiny_root(tmp_path_factory.mktemp("bench"))
    path = root / "bench" / "configs" / "diffusion2d_r1.json"
    path.write_text(json.dumps(dict(json.loads(path.read_text()), **TINY)))
    return root


def _config():
    return json.loads((ROOT / "bench" / "configs"
                       / "diffusion2d_r1.json").read_text())


def _run(root, monkeypatch=None, fault=None, trace=False):
    if fault is not None:
        from repro.kernels import ops
        config = harness.find_cell(root, CELL).config
        monkeypatch.setattr(ops, "stencil_run",
                            fault(config, ops.stencil_run))
    return harness.run_cell(root, CELL, SEED, 0.3, trace,
                            time.perf_counter())


def test_config_is_the_thesis_stencil():
    from repro.core.stencil import diffusion
    cfg = _config()
    assert generator.program_spec(cfg) == diffusion(2, 1)
    assert cfg["stencil"]["boundary"] == "dirichlet0"
    assert "source" not in cfg["stencil"]
    assert (cfg["grid"], cfg["n_steps"], cfg["chips"]) == \
        ([8192, 8192], 64, 1)


def test_count_is_nine_ops_and_two_streams():
    """5 multiplies + 4 adds a cell-update; the grid read once and
    written once."""
    cfg = _config()
    w = roofline.work(cfg, cfg["grid"], cfg["n_steps"], "TPU v5 lite")
    cells = 8192 * 8192
    assert roofline.ops_per_update(cfg["stencil"]) == 9
    assert w["ops"] == 9 * cells * 64
    assert w["bytes"] == 2 * 4 * cells
    assert w["bound"] == "vpu"


def test_sound_run_is_correct(root):
    r, plan = _run(root)
    assert r["correct"], r["checks"]
    assert r["checks"]["rel_err"]["value"] <= \
        r["checks"]["rel_err"]["limit"]
    assert plan["cell"] == CELL and plan["compiles_in_window"] == 0


@pytest.mark.parametrize("fault", [faults.control, faults.unchanged,
                                   faults.altered],
                         ids=["control", "unchanged", "altered"])
def test_control_and_faults_are_not_correct(root, fault, monkeypatch):
    r, _ = _run(root, monkeypatch, fault)
    assert not r["correct"], r["checks"]
    assert r["checks"]["rel_err"]["value"] > \
        r["checks"]["rel_err"]["limit"]


def test_traced_window_reports_the_span_metrics_the_cell_lists(root):
    span_metrics = {m["name"]: m for m in MANIFEST["per_layer"]
                    if m["source"] == "program_span"}
    listed = {n for n, m in span_metrics.items() if CELL in m["workloads"]}
    assert listed == {"plan_ms.solve", "enqueue_ms.solve"}
    spans.reset()
    r, plan = _run(root, trace=True)
    assert r["correct"], r["checks"]
    got = {n for n in r["metrics"] if n in span_metrics}
    assert got == listed
    assert all(r["metrics"][n]["value"] > 0 for n in got)
    snap = spans.snapshot()
    assert snap["ops.stencil_run"]["count"] == \
        plan["counters"]["solves"] > 0
