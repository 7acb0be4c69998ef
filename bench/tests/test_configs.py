"""The configuration files state the stencils the program runs."""
import json

import jax
import numpy as np
import pytest

from bench import generator, reference
from bench.tests.tiny import BENCH, ROOT

MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())


def _config(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def test_hotspot_config_is_the_programs_hotspot():
    from repro.apps import hotspot
    cfg = _config("hotspot2d")
    p = hotspot.HotspotParams(**cfg["rodinia"])
    assert generator.program_spec(cfg) == hotspot.spec_of(p)
    power = jax.random.uniform(jax.random.PRNGKey(0), (8, 128)) * 0.1
    np.testing.assert_array_equal(
        np.asarray(reference.source_grid(cfg["stencil"], {"power": power})),
        np.asarray(hotspot.source_of(power, p)))


def test_hotspot_taps_follow_from_the_rodinia_constants():
    cfg = _config("hotspot2d")
    r, st = cfg["rodinia"], cfg["stencil"]
    cx = r["dt"] / (r["cap"] * r["rx"])
    cy = r["dt"] / (r["cap"] * r["ry"])
    cz = r["dt"] / (r["cap"] * r["rz"])
    assert st["axis_weights"] == [[cy, 0.0, cy], [cx, 0.0, cx]]
    assert st["center"] == 1.0 - 2.0 * cx - 2.0 * cy - cz
    assert st["source"]["scale"] == r["dt"] / r["cap"]
    assert st["source"]["const"] == cz * r["t_amb"]


def test_diffusion_config_is_the_thesis_stencil():
    from repro.core.stencil import diffusion
    assert generator.program_spec(_config("diffusion3d_r4")) == \
        diffusion(3, 4)


@pytest.mark.parametrize("entry", MANIFEST["configs"],
                         ids=lambda c: c["name"])
def test_manifest_entry_names_its_file(entry):
    cfg = json.loads((ROOT / entry["file"]).read_text())
    assert cfg["name"] == entry["name"]
    assert cfg["reduced"] == entry["reduced"]
    assert cfg["dtype"] == "float32"
    for w in MANIFEST["workloads"]:
        if w["config"] == entry["name"]:
            assert w["chips"] == cfg["chips"]
            assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()
            assert (BENCH / "limits" / f"{w['name']}.json").is_file()
