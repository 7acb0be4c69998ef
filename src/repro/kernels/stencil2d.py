"""2D stencil plugin for the unified engine (thesis ch.5, 2D).

This module is a *plugin*, not an accelerator: all blocking, variant
dispatch, boundary fill, fused-time-step and ``pallas_call`` machinery
lives in ``repro.kernels.engine``, which injects the dimension-specific
arithmetic through its ``apply_fn`` hook. This module contributes
exactly two things:

  * ``_apply_2d(win, spec, coeff, scalars) -> win`` — the engine's 2D
    plugin contract: one IR time step on a ``[rows, cols]`` window
    (star taps, box taps, or the spec's custom ``update``; the
    per-window arithmetic and nothing else). ``coeff`` maps each
    coeff-role operand name to its same-shape window; ``scalars`` is
    this step's ``(n_scalars,)`` vector. Neighbor reads use the
    boundary-mode taps of ``core.stencil.shift`` — at window edges that
    only shapes the (cropped-away) garbage rim, because the engine
    pre-fills true-grid-edge cells before every step;
  * ``stencil2d(...)`` — a thin public wrapper that calls
    ``engine.stencil_call`` with that plugin bound.

TPU mapping (see docs/architecture.md): spatial blocking is 1D in x
with ``bx``-column tiles and the full y extent VMEM-resident (the
thesis streams y through a shift register one cell per cycle; the TPU
VPU wants whole (8,128) tiles, so the engine holds the column panel
instead, and streams it through the vector registers in row strips);
temporal blocking fuses ``bt`` steps per HBM pass, shrinking
validity by ``r`` per step (overlapped blocking, thesis fig. 5-6 a).

Boundary semantics: per ``spec.boundary`` (see docs/stencil_ir.md).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.stencil import StencilSpec, shift, shift_nd
from repro.kernels import engine


def _apply_2d(win: jax.Array, spec: StencilSpec, coeff=None,
              scalars=None) -> jax.Array:
    """One IR step on a [rows, cols] window (star / box / custom)."""
    if spec.update is not None:
        fields = {"x": win}
        if coeff:
            fields.update(coeff)
        if spec.n_scalars:
            fields["scalars"] = scalars
        return spec.update(fields, spec)
    if spec.layout == "box":
        from repro.kernels.ref import _box_offsets
        acc = jnp.zeros_like(win)
        for offsets, w in _box_offsets(spec):
            acc = acc + jnp.asarray(w, win.dtype) * shift_nd(
                win, offsets, spec.boundary)
        return acc
    r = spec.radius
    w = spec.weights
    acc = jnp.asarray(spec.center, win.dtype) * win
    for a in range(2):
        for o in range(-r, r + 1):
            c = float(w[a, r + o])
            if o == 0 or c == 0.0:
                continue
            acc = acc + jnp.asarray(c, win.dtype) * shift(
                win, a, o, spec.boundary)
    return acc


# Pre-IR name, kept for external references.
_apply_star_2d = _apply_2d


def stencil2d(x: jax.Array, spec: StencilSpec, bx: int = 256, bt: int = 1,
              variant: str = "revolving", *, backend: str,
              source: jax.Array | None = None, aux=None,
              scalars: jax.Array | None = None) -> jax.Array:
    """Run ``bt`` fused time steps of ``spec`` over a [H, W] grid (or a
    [B, H, W] batch of independent problems — see engine docstring)."""
    if x.ndim not in (2, 3) or spec.dims != 2:
        raise ValueError("stencil2d needs a 2D grid (or a [B, H, W] "
                         "batch) and a 2D spec")
    return engine.stencil_call(x, spec, bx=bx, bt=bt, variant=variant,
                               backend=backend,
                               source=source, aux=aux, scalars=scalars,
                               apply_fn=_apply_2d)
