"""3D stencil plugin for the unified engine (thesis §5.3, 3D).

This module is a *plugin*, not an accelerator: all blocking, z
streaming, boundary fill and ``pallas_call`` machinery lives in
``repro.kernels.engine``, which injects the dimension-specific
arithmetic through its ``apply_fn`` hook. This module contributes
exactly two things:

  * ``_apply_3d(window, spec, coeff, scalars) -> plane`` — the engine's
    3D plugin contract: one IR time step at the center plane of a
    ``[2r+1, rows, cols]`` plane window (star or box taps; the
    per-plane arithmetic and nothing else). z taps index the window's
    planes directly — the engine owns the z boundary (zero or
    plane-replicate per ``spec.boundary``); in-plane taps use the
    boundary-mode reads of ``core.stencil.shift``, which at window
    edges only shapes the cropped-away rim (the engine pre-fills
    true-grid-edge cells);
  * ``stencil3d(...)`` — a thin public wrapper that calls
    ``engine.stencil_call`` with that plugin bound.

TPU mapping (see docs/architecture.md): x is blocked into ``bx``-wide
tiles, y is fully VMEM-resident per plane, and z is *streamed*
front-to-back — the thesis's "2.5D blocking: block two spatial dims,
stream the last" — with temporal blocking as a pipeline of ``bt``
plane stages (engine._kernel_3d_stream).

Custom ``update`` specs are 2D-only (the plane-window contract here
differs from the full-grid/window contract the 2D path shares with the
oracle); ``core.stencil`` enforces that.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.stencil import StencilSpec, shift, shift_nd
from repro.kernels import engine


def _apply_3d(window: jax.Array, spec: StencilSpec, coeff=None,
              scalars=None) -> jax.Array:
    """One IR step at the window's center plane.

    window: [2r+1, rows, cols] — planes z-r .. z+r of the producer field.
    Returns the updated [rows, cols] plane at z.
    """
    r = spec.radius
    if spec.layout == "box":
        from repro.kernels.ref import _box_offsets
        acc = jnp.zeros_like(window[r])
        for offsets, w in _box_offsets(spec):
            plane = window[r + offsets[0]]
            acc = acc + jnp.asarray(w, plane.dtype) * shift_nd(
                plane, offsets[1:], spec.boundary)
        return acc
    w = spec.weights
    center = window[r]
    acc = jnp.asarray(spec.center, center.dtype) * center
    # z taps: direct plane reads — the engine already applied the z
    # boundary (zeroed or replicated planes outside the grid).
    for o in range(-r, r + 1):
        c = float(w[0, r + o])
        if o == 0 or c == 0.0:
            continue
        acc = acc + jnp.asarray(c, center.dtype) * window[r + o]
    # y / x taps on the center plane
    for a in (1, 2):
        for o in range(-r, r + 1):
            c = float(w[a, r + o])
            if o == 0 or c == 0.0:
                continue
            acc = acc + jnp.asarray(c, center.dtype) * shift(
                center, a - 1, o, spec.boundary)
    return acc


# Pre-IR name, kept for external references.
_apply_star_3d = _apply_3d


def stencil3d(x: jax.Array, spec: StencilSpec, bx: int = 128, bt: int = 1,
              variant: str = "revolving", *, backend: str,
              source: jax.Array | None = None, aux=None,
              scalars: jax.Array | None = None) -> jax.Array:
    """Run ``bt`` fused time steps of ``spec`` over a [D, H, W] grid (or
    a [B, D, H, W] batch of independent problems — see engine)."""
    if x.ndim not in (3, 4) or spec.dims != 3:
        raise ValueError("stencil3d needs a 3D grid (or a [B, D, H, W] "
                         "batch) and a 3D spec")
    return engine.stencil_call(x, spec, bx=bx, bt=bt, variant=variant,
                               backend=backend,
                               source=source, aux=aux, scalars=scalars,
                               apply_fn=_apply_3d)
