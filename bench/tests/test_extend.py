"""A later change adds a configuration, a mix, a limit file, a metric and
a cell as new files and new manifest entries alone, and the harness
finds them by name."""
import json
import time

from bench import harness
from bench.tests.tiny import tiny_root

DUMMY_CONFIG = {
    "name": "diffusion2d_r1", "dtype": "float32",
    "stencil": {"dims": 2, "radius": 1, "boundary": "dirichlet0",
                "center": 0.4,
                "axis_weights": [[0.15, 0.0, 0.15], [0.15, 0.0, 0.15]]},
    "grid": [16, 128], "n_steps": 3, "inputs": {"x": [0.0, 1.0]},
    "chips": 1, "reduced": [], "source": "test", "assumed": {}}
DUMMY_METRIC = '''
def read(run):
    return 1000.0 * run.window.counters["solves"] / run.window.attempted
'''


def test_new_cell_and_metric_from_new_files(tmp_path):
    root = tiny_root(tmp_path)
    b = root / "bench"
    (b / "configs" / "diffusion2d_r1.json").write_text(
        json.dumps(DUMMY_CONFIG))
    (b / "traffic" / "solve_once.json").write_text(
        json.dumps({"loop": "closed", "inputs_in_rotation": 1}))
    (b / "limits" / "diffusion2d_r1.solve_once.json").write_text(
        json.dumps({"rel_err": {"limit": 1e-4}}))
    (b / "metrics" / "dummy_per_mille.py").write_text(DUMMY_METRIC)
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    manifest["configs"].append({
        "name": "diffusion2d_r1", "source": "test",
        "file": "bench/configs/diffusion2d_r1.json", "reduced": [],
        "why": "test"})
    manifest["workloads"].append({
        "name": "diffusion2d_r1.solve_once", "config": "diffusion2d_r1",
        "traffic": "solve_once", "chips": 1, "why": "test"})
    manifest["per_layer"].append({
        "name": "dummy_per_mille", "unit": "permille", "better": "higher",
        "source": "program_counter", "layer": "planner",
        "moves": "setup_s", "workloads": ["diffusion2d_r1.solve_once"]})
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))

    cell = harness.find_cell(root, "diffusion2d_r1.solve_once")
    assert [m["name"] for m in cell.end_to_end] == ["setup_s"]
    assert [m["name"] for m in cell.per_layer] == ["dummy_per_mille"]
    t0 = time.perf_counter()
    r, plan = harness.run_cell(root, "diffusion2d_r1.solve_once", 5, 0.2,
                               False, t0)
    assert r["correct"] and set(r["metrics"]) == {"setup_s"}
    assert plan["cell"] == "diffusion2d_r1.solve_once"
    r, _ = harness.run_cell(root, "diffusion2d_r1.solve_once", 5, 0.2,
                            True, t0)
    assert r["metrics"] == {"dummy_per_mille": {"value": 1000.0,
                                                "unit": "permille"}}
    # The committed cells do not pick up the new metric.
    assert "dummy_per_mille" not in [
        m["name"] for m in harness.find_cell(root,
                                             "hotspot2d.solve").per_layer]


def test_plan_line_counts_compiles_in_set_up_and_window(tmp_path):
    """Set-up compiles the cell's programs; the window compiles none."""
    root = tiny_root(tmp_path)
    _, plan = harness.run_cell(root, "hotspot2d.solve", 11, 0.2, False,
                               time.perf_counter())
    assert plan["compiles_in_window"] == 0
    assert plan["setup_compiles"]["compiles"] > 0
    assert 0.0 <= plan["init_s"] <= plan["setup_s"]


def test_warm_runs_set_up_alone(tmp_path):
    """A checkout's first run warms the cell up in a process of its own."""
    root = tiny_root(tmp_path)
    assert harness.warm(root, "hotspot2d.ensemble", 2 ** 33 + 1) is None
