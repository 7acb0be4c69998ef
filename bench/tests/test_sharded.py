"""A cell on four chips, on four virtual CPU devices: the blocked
reference equals the whole-grid one, and a tiny four-chip cell of the
radius-4 3D stencil, added to a tiny benchmark as new files and entries
alone, runs through the harness split over the devices, correct, while
the control, an unchanged state and the exchange between chips left out
are not.

The devices exist only in a process started with
``--xla_force_host_platform_device_count=4``, so one such process runs
everything and prints its readings as JSON; the tests read them."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from bench.tests.tiny import ROOT

CELL = "diffusion3d_r4_x4.solve"
FAULTS = ("control", "unchanged", "no_exchange")
BLOCKED = {"diffusion3d_r4": ([32, 16, 128], 3), "hotspot2d": ([64, 256], 5)}

SCRIPT = """
import json, math, pathlib, sys, time
import numpy as np, jax, jax.numpy as jnp
from bench import check, generator, harness, reference
from bench.tests import faults
from bench.tests.tiny import BENCH, tiny_root
from repro.kernels import ops

devices = jax.devices()
rows = jax.sharding.NamedSharding(
    jax.sharding.Mesh(np.array(devices), ("rows",)),
    jax.sharding.PartitionSpec("rows"))
out = {"devices": len(devices)}
for name, (grid, n) in BLOCKED.items():
    cfg = json.loads((BENCH / "configs" / f"{name}.json").read_text())
    st = cfg["stencil"]
    split = generator.make_problems(cfg, grid, 1, generator.prng_key(5),
                                    rows)[0]
    one = {k: jax.device_put(v, devices[0]) for k, v in split.items()}
    src = reference.source_grid(st, one)
    whole = np.asarray(reference.multistep(one["x"], st, n, src))
    padded = np.asarray(reference._multistep_padded(
        jnp.copy(one["x"]), src, reference._frozen(st), n))
    blocks = check.row_blocks(split["x"], 3 * math.prod(grid[1:]))
    got = np.concatenate([
        np.asarray(reference.multistep_rows(split, st, n, lo, hi, dev))
        for lo, hi, dev, *_ in sorted(blocks, key=lambda b: b[0])])
    out[name] = {
        "blocks": len(blocks), "devices": len({b[2] for b in blocks}),
        "exact": bool(np.array_equal(got, padded)),
        "rel_to_shifted": float(np.abs(got - whole).max()
                                / np.abs(whole).max())}

root = tiny_root(pathlib.Path(sys.argv[1]))
b = root / "bench"
cfg = json.loads((b / "configs" / "diffusion3d_r4.json").read_text())
cfg.update(name="diffusion3d_r4_x4", grid=[32, 16, 128], n_steps=2,
           chips=4)
(b / "configs" / "diffusion3d_r4_x4.json").write_text(json.dumps(cfg))
(b / "limits" / f"{CELL}.json").write_text(
    (b / "limits" / "diffusion3d_r4.solve.json").read_text())
manifest = json.loads((root / "BENCHMARK.json").read_text())
manifest["configs"].append({
    "name": "diffusion3d_r4_x4", "source": "test",
    "file": "bench/configs/diffusion3d_r4_x4.json", "reduced": [],
    "why": "test"})
manifest["workloads"].append({"name": CELL, "config": "diffusion3d_r4_x4",
                              "traffic": "solve", "chips": 4,
                              "why": "test"})
for m in manifest["end_to_end"]:
    if "diffusion3d_r4.solve" in m.get("workloads", []):
        m["workloads"].append(CELL)
(root / "BENCHMARK.json").write_text(json.dumps(manifest))
cell = harness.find_cell(root, CELL)
win = generator.drive(cell.config, cell.traffic, 7, 0.0, False,
                      generator.NoHooks(), chips=cell.chips)
out["blocks_compared"] = len(check.row_blocks(win.outputs[0]["got"]))
out["input_devices"] = len(win.outputs[0]["problem"]["x"].devices())
result, plan = harness.run_cell(root, CELL, 2 ** 31 + 99, 0.2, False,
                                time.perf_counter())
out["sound"] = {"result": result, "plan": plan}
real = ops.stencil_run
for fault in FAULTS:
    ops.stencil_run = getattr(faults, fault)(cell.config, real)
    out[fault], _ = harness.run_cell(
        root, CELL, 2 ** 31 + 99, 0.2, False, time.perf_counter())
ops.stencil_run = real
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def readings(tmp_path_factory):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(ROOT), str(ROOT / "src")]),
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               REPRO_AUTOTUNE_CACHE=str(tmp_path_factory.mktemp("tuner")
                                        / "autotune.json"))
    script = (f"CELL = {CELL!r}\nBLOCKED = {BLOCKED!r}\n"
              f"FAULTS = {FAULTS!r}\n"
              + textwrap.dedent(SCRIPT))
    p = subprocess.run([sys.executable, "-c", script,
                        str(tmp_path_factory.mktemp("bench"))],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["devices"] == 4
    return out


@pytest.mark.parametrize("name", sorted(BLOCKED))
def test_blocked_reference_equals_the_whole_grid(readings, name):
    """More blocks than devices, each computed from its rows and ghost
    rows alone: bit for bit the whole grid's padded reference, and the
    shifted one to float32 rounding."""
    r = readings[name]
    assert r["devices"] == 4 and r["blocks"] > 4
    assert r["exact"]
    assert r["rel_to_shifted"] < 1e-6


def test_four_chip_inputs_and_output_are_split(readings):
    assert readings["input_devices"] == 4
    assert readings["blocks_compared"] >= 4


def test_four_chip_sound_run_is_correct(readings):
    r, plan = readings["sound"]["result"], readings["sound"]["plan"]
    assert r["correct"], r["checks"]
    assert r["checks"]["rel_err"]["value"] <= \
        r["checks"]["rel_err"]["limit"]
    assert r["device"]["count"] == 4
    assert set(r["metrics"]) == {"gcells_per_s", "setup_s"}
    assert plan["plan"]["n_devices"] == 4
    assert plan["compiles_in_window"] == 0


@pytest.mark.parametrize("fault", FAULTS)
def test_four_chip_control_and_fault_are_not_correct(readings, fault):
    r = readings[fault]
    assert not r["correct"], r["checks"]
    assert r["checks"]["rel_err"]["value"] > \
        r["checks"]["rel_err"]["limit"]
