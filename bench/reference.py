"""The plain reference the benchmark compares the timed path with.

A copy of the stencil semantics the program states (star taps, the
``dirichlet0`` and ``clamp`` boundaries, a source grid added after every
step), written in straightforward ``jax.numpy`` from a configuration
file's numbers. It imports nothing of the program and takes nothing the
program made, so a change under ``src/`` cannot move the yardstick.

``multistep`` runs in the dtype it is given: float32 for the reference
itself, bfloat16 for the control that the comparison has to fail.

``multistep_rows`` gives a block of leading-axis rows of the same result
for a grid too large for one device: it runs the same taps on the block
extended by ``radius * n_steps`` ghost rows on each side that lies
inside the grid (``ghost``). The block's false boundary corrupts
``radius`` rows a step from the outside in, so after ``n_steps`` steps
its error stops short of the kept rows, under either boundary; where the
block meets the grid's edge, its boundary is the grid's own.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def shift(x, axis: int, offset: int, boundary: str):
    """``out[i] = x[i + offset]`` along ``axis``; reads outside the grid
    give 0 (``dirichlet0``) or the edge cell (``clamp``)."""
    if offset == 0:
        return x
    n = x.shape[axis]
    r = abs(offset)
    if boundary == "clamp":
        edge = jax.lax.slice_in_dim(x, n - 1 if offset > 0 else 0,
                                    n if offset > 0 else 1, axis=axis)
        fill = jnp.repeat(edge, r, axis=axis)
    elif boundary == "dirichlet0":
        shape = list(x.shape)
        shape[axis] = r
        fill = jnp.zeros(shape, x.dtype)
    else:
        raise ValueError(f"unknown boundary {boundary!r}")
    if offset > 0:
        kept = jax.lax.slice_in_dim(x, r, n, axis=axis)
        return jnp.concatenate([kept, fill], axis=axis)
    kept = jax.lax.slice_in_dim(x, 0, n - r, axis=axis)
    return jnp.concatenate([fill, kept], axis=axis)


def step(x, stencil: dict, source=None):
    """One time step of the star stencil a configuration states."""
    r = stencil["radius"]
    boundary = stencil["boundary"]
    acc = jnp.asarray(stencil["center"], x.dtype) * x
    for axis, row in enumerate(stencil["axis_weights"]):
        for o in range(-r, r + 1):
            w = float(row[r + o])
            if o == 0 or w == 0.0:
                continue
            acc = acc + jnp.asarray(w, x.dtype) * shift(x, axis, o,
                                                        boundary)
    if source is not None:
        acc = acc + source
    return acc


def _frozen(stencil: dict) -> tuple:
    """The parts of a stencil that ``step`` reads, as a hashable key."""
    return (int(stencil["radius"]), stencil["boundary"],
            float(stencil["center"]),
            tuple(tuple(float(w) for w in row)
                  for row in stencil["axis_weights"]))


@functools.partial(jax.jit, static_argnames=("frozen", "n_steps"))
def _multistep(x, source, frozen: tuple, n_steps: int):
    r, boundary, center, weights = frozen
    stencil = {"radius": r, "boundary": boundary, "center": center,
               "axis_weights": weights}
    return jax.lax.fori_loop(0, n_steps,
                             lambda _, g: step(g, stencil, source), x)


def multistep(x, stencil: dict, n_steps: int, source=None):
    """``n_steps`` steps of ``stencil`` on ``x`` (one grid, any dtype);
    ``source`` is added after every step, in ``x``'s dtype."""
    if source is not None:
        source = source.astype(x.dtype)
    return _multistep(x, source, _frozen(stencil), n_steps)


def step_padded(x, stencil: dict, source=None):
    """``step`` read from one copy of ``x`` padded by the radius (zeros
    for ``dirichlet0``, the edge cell for ``clamp``): the same taps added
    in the same order, in one fused pass that holds one padded grid where
    ``step`` holds a shifted grid per tap. XLA may fuse the two
    differently, so they agree to float rounding, not always bit for
    bit."""
    r = stencil["radius"]
    mode = {"clamp": "edge", "dirichlet0": "constant"}[stencil["boundary"]]
    xp = jnp.pad(x, r, mode=mode)
    acc = jnp.asarray(stencil["center"], x.dtype) * x
    for axis, row in enumerate(stencil["axis_weights"]):
        for o in range(-r, r + 1):
            w = float(row[r + o])
            if o == 0 or w == 0.0:
                continue
            idx = [slice(r, r + n) for n in x.shape]
            idx[axis] = slice(r + o, r + o + x.shape[axis])
            acc = acc + jnp.asarray(w, x.dtype) * xp[tuple(idx)]
    if source is not None:
        acc = acc + source
    return acc


@functools.partial(jax.jit, static_argnames=("frozen", "n_steps"),
                   donate_argnums=0)
def _multistep_padded(x, source, frozen: tuple, n_steps: int):
    r, boundary, center, weights = frozen
    stencil = {"radius": r, "boundary": boundary, "center": center,
               "axis_weights": weights}
    return jax.lax.fori_loop(0, n_steps,
                             lambda _, g: step_padded(g, stencil, source),
                             x)


def ghost(stencil: dict, n_steps: int) -> int:
    """Rows a block needs beyond each inner edge for ``n_steps`` steps."""
    return int(stencil["radius"]) * int(n_steps)


def rows(x, lo: int, hi: int, device):
    """Rows ``[lo, hi)`` of ``x``'s leading axis as a new array on
    ``device`` (never one of ``x``'s own buffers, so that it can be
    donated), gathered from the shards of ``x`` that hold them."""
    pieces, seen = [], set()
    n = x.shape[0]
    for sh in sorted(x.addressable_shards,
                     key=lambda sh: (sh.index[0].indices(n)[0],
                                     sh.device != device)):
        s0, s1, _ = sh.index[0].indices(n)
        a, b = max(lo, s0), min(hi, s1)
        if a >= b or s0 in seen:
            continue
        seen.add(s0)
        pieces.append(jax.device_put(sh.data[a - s0:b - s0], device))
    if len(pieces) == 1:
        return jnp.copy(pieces[0])
    return jnp.concatenate(pieces)


def multistep_rows(operands: dict, stencil: dict, n_steps: int, lo: int,
                   hi: int, device, dtype=jnp.float32):
    """Rows ``[lo, hi)`` of ``n_steps`` steps of ``stencil`` on
    ``operands["x"]`` (with the source grid the stencil states), computed
    in ``dtype`` on ``device`` from the block and its ghost rows alone
    (module docstring). ``operands`` may be split over devices along the
    leading axis."""
    n = operands["x"].shape[0]
    g = ghost(stencil, n_steps)
    a, b = max(0, lo - g), min(n, hi + g)
    block = {k: rows(v, a, b, device) for k, v in operands.items()}
    src = source_grid(stencil, block, dtype)
    out = _multistep_padded(block.pop("x").astype(dtype), src,
                            _frozen(stencil), n_steps)
    return out[lo - a:hi - a]


def source_grid(stencil: dict, operands: dict, dtype=jnp.float32):
    """The additive grid a configuration's source term states:
    ``scale * operand + const``, in ``dtype``; None without a source."""
    src = stencil.get("source")
    if src is None:
        return None
    g = operands[src["operand"]].astype(dtype)
    return (jnp.asarray(src["scale"], dtype) * g
            + jnp.asarray(src["const"], dtype))
