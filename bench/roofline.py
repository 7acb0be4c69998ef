"""Operations, bytes and least time of a solve, from a configuration alone.

The count is the same whatever implements the solve: it reads the
configuration file and nothing of the plan (no ``bx``, ``bt``, variant
or recomputed halo), so a change of plan cannot move the yardstick.

* Operations per cell-update: one multiply per tap and one add per tap
  less one, plus one add for the source grid where the stencil states
  one (its scale and constant are folded into that grid once per solve).
* Bytes per solve: the compulsory traffic. The grid and every operand
  are read once and the result is written once.
* Least time: ``max(ops / VPU rate, bytes / HBM bandwidth)``, with the
  rates of ``bench/peaks.json`` for the device kind.
"""
from __future__ import annotations

import functools
import json
import math
import pathlib

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"


@functools.lru_cache(maxsize=None)
def _peaks_table() -> dict:
    return json.loads(PEAKS.read_text())


def peaks(device_kind: str) -> dict:
    """``{"vpu_f32_ops_per_s", "hbm_bytes_per_s"}`` for a device kind;
    a kind missing from ``peaks.json`` is an error."""
    table = _peaks_table()
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}; known: {sorted(table)}")
    return {k: float(v["value"]) for k, v in table[device_kind].items()}


def taps(stencil: dict) -> int:
    """Nonzero taps of a star stencil, the center included."""
    n = int(float(stencil["center"]) != 0.0)
    return n + sum(1 for row in stencil["axis_weights"] for w in row
                   if float(w) != 0.0)


def ops_per_update(stencil: dict) -> int:
    t = taps(stencil)
    return 2 * t - 1 + (1 if stencil.get("source") else 0)


def bytes_per_solve(config: dict, grid) -> int:
    """Grid and operands read once, the result written once."""
    import numpy as np
    itemsize = np.dtype(config["dtype"]).itemsize
    streams = 2 + (1 if config["stencil"].get("source") else 0)
    return streams * math.prod(grid) * itemsize


def work(config: dict, grid, n_steps: int, device_kind: str) -> dict:
    """Operations, bytes and least time of one solve of ``grid``."""
    ops = ops_per_update(config["stencil"]) * math.prod(grid) * n_steps
    nbytes = bytes_per_solve(config, grid)
    pk = peaks(device_kind)
    t_ops = ops / pk["vpu_f32_ops_per_s"]
    t_bytes = nbytes / pk["hbm_bytes_per_s"]
    return {"ops": float(ops), "bytes": float(nbytes),
            "roofline_s": max(t_ops, t_bytes),
            "bound": "vpu" if t_ops >= t_bytes else "hbm"}
