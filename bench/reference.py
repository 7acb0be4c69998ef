"""The plain reference the benchmark compares the timed path with.

A copy of the stencil semantics the program states (star taps, the
``dirichlet0`` and ``clamp`` boundaries, a source grid added after every
step), written in straightforward ``jax.numpy`` from a configuration
file's numbers. It imports nothing of the program and takes nothing the
program made, so a change under ``src/`` cannot move the yardstick.

``multistep`` runs in the dtype it is given: float32 for the reference
itself, bfloat16 for the control that the comparison has to fail.
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


def source_grid(stencil: dict, operands: dict, dtype=jnp.float32):
    """The additive grid a configuration's source term states:
    ``scale * operand + const``, in ``dtype``; None without a source."""
    src = stencil.get("source")
    if src is None:
        return None
    g = operands[src["operand"]].astype(dtype)
    return (jnp.asarray(src["scale"], dtype) * g
            + jnp.asarray(src["const"], dtype))
