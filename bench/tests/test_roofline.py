"""The benchmark's count of operations and bytes, from the config alone."""
import inspect
import json

import pytest

from bench import roofline
from bench.tests.tiny import BENCH

CONFIGS = {p.stem: json.loads(p.read_text())
           for p in (BENCH / "configs").glob("*.json")}


@pytest.mark.parametrize("name,taps,ops,streams", [
    ("hotspot2d", 5, 10, 3),        # 5 mul + 4 add + the source grid
    ("diffusion3d_r4", 25, 49, 2),  # 25 mul + 24 add
])
def test_count_per_config(name, taps, ops, streams):
    cfg = CONFIGS[name]
    assert roofline.taps(cfg["stencil"]) == taps
    assert roofline.ops_per_update(cfg["stencil"]) == ops
    grid = cfg["grid"]
    cells = 1
    for g in grid:
        cells *= g
    w = roofline.work(cfg, grid, cfg["n_steps"], "TPU v5 lite")
    assert w["ops"] == ops * cells * cfg["n_steps"]
    assert w["bytes"] == streams * 4 * cells
    pk = roofline.peaks("TPU v5 lite")
    assert w["roofline_s"] == max(w["ops"] / pk["vpu_f32_ops_per_s"],
                                  w["bytes"] / pk["hbm_bytes_per_s"])
    assert w["bound"] == "vpu"


def test_count_takes_no_plan():
    """Nothing of a plan (bx, bt, variant, tile) reaches the count, so
    a change of plan cannot change the denominator."""
    params = set(inspect.signature(roofline.work).parameters)
    assert params == {"config", "grid", "n_steps", "device_kind"}
    cfg = CONFIGS["hotspot2d"]
    one = roofline.work(cfg, (1024, 1024), 16, "TPU v5 lite")
    for bx, bt in ((128, 1), (512, 8), (1024, 16)):
        plan_cfg = dict(cfg, plan={"bx": bx, "bt": bt})
        assert roofline.work(plan_cfg, (1024, 1024), 16,
                             "TPU v5 lite") == one


def test_count_is_linear_in_steps_and_cells():
    cfg = CONFIGS["diffusion3d_r4"]
    a = roofline.work(cfg, (64, 64, 64), 8, "TPU v5 lite")
    b = roofline.work(cfg, (128, 64, 64), 16, "TPU v5 lite")
    assert b["ops"] == 4 * a["ops"] and b["bytes"] == 2 * a["bytes"]


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        roofline.peaks("TPU v99")


def test_peaks_name_their_source():
    table = json.loads((BENCH / "peaks.json").read_text())
    for kind, rows in table.items():
        assert set(rows) == {"hbm_bytes_per_s", "vpu_f32_ops_per_s"}
        for row in rows.values():
            assert row["value"] > 0 and row["source"]
