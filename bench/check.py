"""The comparison that decides a run's ``correct``.

It compares what the timed path produced in the window (the output of
the last solve of a closed loop; a sample of served requests, drawn
from the seed, of an open loop) with ``bench/reference.py`` at the same
sizes, on the device, after the window has closed.

The numbers compared:

* ``rel_err``: the largest ``max|got - want| / max|want|`` over the
  compared outputs, ``want`` being the float32 reference.
* ``missing``: compared requests that never got a result, or got an
  error in its place.

Each cell's limits are data, ``bench/limits/<cell>.json``; a run is
correct when every number listed there is at most its limit. The
control (``control``) is the reference itself computed one precision
lower, in the program's place; the limits lie between what sound runs
read and what the control reads (PERF.md gives both).
"""
from __future__ import annotations

import json
import math
import pathlib

from bench import reference

LOWER = {"float32": "bfloat16"}


def rel_err(got, want) -> float:
    """``max|got - want| / max|want|``, computed in float32 on the
    device that holds ``want``."""
    import jax.numpy as jnp
    want = jnp.asarray(want, jnp.float32)
    got = jnp.asarray(got, jnp.float32)
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


def solve_reference(config: dict, out: dict, dtype: str):
    """The reference's answer for one compared output, in ``dtype``."""
    import jax.numpy as jnp
    p = out["problem"]
    dt = jnp.dtype(dtype)
    src = reference.source_grid(config["stencil"], p, dt)
    return reference.multistep(p["x"].astype(dt), config["stencil"],
                               out["n_steps"], src)


def compare(window, config: dict, program_dtype: str | None = None
            ) -> dict:
    """The numbers compared, for the program's outputs in ``window``.

    ``program_dtype`` puts the reference, computed in that dtype, in
    the program's place: the control, which has to fail."""
    worst = 0.0
    missing = 0
    want_of: dict = {}
    for out in window.outputs:
        got = out["got"]
        if got is None:
            missing += 1
            continue
        key = id(out["problem"])
        if key not in want_of:
            want_of[key] = solve_reference(config, out, config["dtype"])
        if program_dtype is not None:
            got = solve_reference(config, out, program_dtype)
        worst = max(worst, rel_err(got, want_of[key]))
    return {"rel_err": worst, "missing": float(missing)}


def control(window, config: dict) -> dict:
    """The reference one precision below the configuration's dtype, in
    the program's place."""
    return compare(window, config, program_dtype=LOWER[config["dtype"]])


def load_limits(path: pathlib.Path) -> dict:
    return {k: float(v["limit"])
            for k, v in json.loads(path.read_text()).items()}


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}) for the limited numbers. A
    number that is not finite fails and is reported as null."""
    checks, ok = {}, True
    for k, lim in limits.items():
        v = float(numbers[k])
        finite = math.isfinite(v)
        ok = ok and finite and v <= lim
        checks[k] = {"value": v if finite else None, "limit": lim}
    return ok, checks
