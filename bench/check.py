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

Where an output is split along its leading axis over more than one
device (a cell on several chips), no device could hold the whole-grid
reference, so the comparison is blocked: each device compares the rows
it holds, in blocks of at most ``BLOCK_CELLS`` cells, with the
reference's rows computed there from those rows and their ghost rows
(``reference.multistep_rows``), and ``rel_err`` is the largest gap over
all blocks over the largest ``|want|`` over all blocks: the same number
as the whole-grid comparison gives. The control goes through the same
blocks. An output on one device takes the whole-grid path.

Each cell's limits are data, ``bench/limits/<cell>.json``; a run is
correct when every number listed there is at most its limit. The
control (``control``) is the reference itself computed one precision
lower, in the program's place; the limits lie between what sound runs
read and what the control reads (PERF.md gives both).
"""
from __future__ import annotations

import itertools
import json
import math
import pathlib

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference

LOWER = {"float32": "bfloat16"}
# The most cells one block of the blocked comparison holds: 256 planes
# of 1024 x 1024 in float32 (1 GiB), computed from up to 768 planes.
BLOCK_CELLS = 2 ** 28


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


def row_blocks(got, block_cells: int = BLOCK_CELLS) -> list:
    """``(lo, hi, device, shard, s0)`` for each block of rows of ``got``
    when it is split along its leading axis over more than one device:
    each shard's rows ``[s0, s1)`` cut into blocks of at most
    ``block_cells`` cells, in rounds that take one block from each
    device in turn. Empty for an output on one device or not split
    along its leading axis."""
    n = got.shape[0]
    shards = {}
    for sh in getattr(got, "addressable_shards", []):
        s0, s1, _ = sh.index[0].indices(n)
        if all(i == slice(None) for i in sh.index[1:]):
            shards.setdefault((s0, s1), sh)
    if len(shards) < 2:
        return []
    per = max(1, block_cells // math.prod(got.shape[1:]))
    cuts = [[(lo, min(lo + per, s1), sh.device, sh.data, s0)
             for lo in range(s0, s1, per)]
            for (s0, s1), sh in sorted(shards.items())]
    return [b for rnd in itertools.zip_longest(*cuts) for b in rnd if b]


@jax.jit
def _gap(got, want):
    """``max|got - want|`` and ``max|want|``, in float32 on their
    device."""
    want = want.astype(jnp.float32)
    return (jnp.max(jnp.abs(got.astype(jnp.float32) - want)),
            jnp.max(jnp.abs(want)))


def blocked_rel_err(config: dict, out: dict, blocks: list,
                    program_dtype: str | None = None) -> float:
    """``rel_err`` of one output split over devices, block by block
    (module docstring); ``program_dtype`` puts the reference computed in
    that dtype in the program's place, as ``compare`` does."""
    st = config["stencil"]
    gaps, tops, pending = [], [], []
    ndev = len({b[2] for b in blocks})
    for i, (lo, hi, dev, shard, s0) in enumerate(blocks):
        want = reference.multistep_rows(out["problem"], st, out["n_steps"],
                                        lo, hi, dev, config["dtype"])
        if program_dtype is None:
            got = shard[lo - s0:hi - s0]
        else:
            got = reference.multistep_rows(out["problem"], st,
                                           out["n_steps"], lo, hi, dev,
                                           program_dtype)
        pending.append(_gap(got, want))
        del got, want
        if len(pending) == ndev or i == len(blocks) - 1:
            # One block per device in flight, the devices in parallel.
            for g, t in pending:
                gaps.append(float(g))
                tops.append(float(t))
            pending = []
    return float(np.float32(max(gaps)) / np.float32(max(tops)))


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
        blocks = row_blocks(got)
        if blocks:
            worst = max(worst, blocked_rel_err(config, out, blocks,
                                               program_dtype))
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
