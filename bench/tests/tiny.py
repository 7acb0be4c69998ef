"""A copy of the benchmark at sizes the CPU's Pallas interpreter runs in
seconds: the manifest's cells, on configurations and mixes cut down."""
from __future__ import annotations

import json
import pathlib
import shutil

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent

SIZES = {"hotspot2d": {"grid": [16, 256], "n_steps": 4},
         "diffusion3d_r4": {"grid": [12, 16, 128], "n_steps": 2}}
ENSEMBLE = {"sizes": [[16, 128], [16, 256]], "n_steps": 2,
            "max_batch": 2, "rate_per_s": 8, "pool": 2, "sample": 4}


def tiny_root(tmp: pathlib.Path) -> pathlib.Path:
    """A root holding ``BENCHMARK.json`` and ``bench/`` as committed,
    with every configuration and mix cut to a tiny size."""
    root = pathlib.Path(tmp) / "root"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    for name, cut in SIZES.items():
        path = root / "bench" / "configs" / f"{name}.json"
        cfg = json.loads(path.read_text())
        cfg.update(cut)
        path.write_text(json.dumps(cfg))
    path = root / "bench" / "traffic" / "ensemble.json"
    mix = json.loads(path.read_text())
    mix.update(ENSEMBLE)
    path.write_text(json.dumps(mix))
    return root
