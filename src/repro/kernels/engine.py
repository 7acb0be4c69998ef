"""Unified spatial+temporal-blocked stencil engine (thesis ch.5).

One engine owns everything the 2D and 3D accelerators share — the
dimension-*specific* arithmetic is injected as a plugin:

  * boundary fill (re-imposing the true-grid boundary on the padded
    window every fused step): ``dirichlet0`` zeroes out-of-grid cells,
    ``clamp`` replicates the nearest in-grid cell (Rodinia's clamped
    indexing). Either way the fill happens at *true grid edges only*
    — the leading-axis validity interval (below) is what tells a
    sharded slab where the true grid ends, so shard-interior edges
    keep their exchanged ghost data;
  * the fused-time-step loop (``bt`` in-VMEM steps per HBM pass, halo
    shrinking by ``r`` per step — overlapped blocking, thesis fig. 5-6),
    with per-step scalars threaded to custom updates;
  * auxiliary-operand plumbing: ``source``-role operands are pre-summed
    on the host into one additive grid that is windowed alongside the
    main grid (every variant); ``coeff``-role operands each get their
    own window (and, for the revolving variant, their own revolving
    scratch), boundary-filled once per sweep and handed to the plugin;
  * variant dispatch:
      - ``multioperand`` ("basic"): the input is passed three times with
        left/center/right BlockSpec index maps — 3x HBM read
        amplification;
      - ``revolving`` ("advanced", the shift-register analog §3.2.4.1):
        a persistent VMEM scratch holds the last three tiles across the
        sequential grid, so each tile is read from HBM exactly once.
        In 2D the fused steps then run on **register strips**, the same
        shift register one level down (VMEM panel → vreg strip): rows
        ``[s0 - hr, s0 + S + hr)`` of a window of whole lane tiles are
        loaded, stepped ``bt`` times in registers and cropped to their
        ``S`` own rows. ``S`` (``core.blocking.strip_rows``) fills the
        register file with one strip; a strip whose window holds no
        cell outside the grid skips the boundary fill, which is the
        identity there (see ``_kernel_2d_revolving``).
        For 3D grids the z axis is *streamed* plane-by-plane through a
        rolling plane window (2.5D blocking) — the same shift-register
        idea along z — so both named variants map to the one streaming
        kernel (x-tiles are re-read 3x; z is read once per sweep);
  * ``pallas_call`` assembly: grids, Block/scratch specs, compiler
    params (all experimental-jax symbols come through ``repro.compat``,
    per the README shim policy), padding to lane/sublane tiles and
    cropping back; each ``pallas_call`` carries a stable ``name``
    (``stencil2d_multioperand``, ``stencil2d_revolving``,
    ``stencil3d_stream``, ``stencil_persistent``), which becomes the
    kernel's instruction name in the HLO and in a device trace;
  * the *leading-axis validity interval*: every kernel receives a tiny
    ``(1, 2)`` int32 operand ``[lo, hi)`` bounding the valid rows (2D)
    or planes (3D) of the leading axis. Cells outside the interval are
    treated as outside the grid at *every* fused step — zeroed under
    ``dirichlet0``, replicated-from-the-interval-edge under ``clamp``.
    The bounds may be traced scalars, which is what lets the
    multi-device deep-halo runner (``distributed/halo.py``) mark
    per-device ghost rows and shard padding as outside-grid under a
    single SPMD program;
  * the **batch axis**: a grid of shape ``[B, *grid]`` runs all ``B``
    independent problems in one ``pallas_call`` — the batch is lowered
    as the *outermost* grid dimension, so the (bx, bt) plan, VMEM
    working set and per-slab boundary/validity logic are exactly the
    single-problem ones and each batch slab's arithmetic is
    instruction-identical to a solo run (tests assert bitwise equality
    against a Python loop). The revolving scratches re-initialize at
    tile 0 of every batch row, so one compilation serves the whole
    batch and problems can never read each other's cells.
    ``stencil_call_vmap`` keeps a ``jax.vmap``-over-the-engine fallback
    as a differential oracle for this lowering.

Plugins (see ``stencil2d._apply_2d`` / ``stencil3d._apply_3d``):

  2D: ``apply_fn(win[rows, cols], spec, coeff, scalars) -> [rows, cols]``
      — one time step on a window whose true-grid boundary was just
      re-imposed; ``coeff`` maps coeff-operand names to windows;
  3D: ``apply_fn(window[2r+1, rows, cols], spec, coeff, scalars) ->
      [rows, cols]`` — one time step at the window's center plane. The
      engine owns the z boundary: under ``clamp`` it re-indexes the
      plane window so out-of-grid z taps replicate the nearest valid
      plane; under ``dirichlet0`` out-of-grid planes are zeroed.

Boundary semantics per ``spec.boundary`` (see kernels/ref.py and
docs/stencil_ir.md).
"""
from __future__ import annotations

import dataclasses
import functools
import os

import jax
import jax.numpy as jnp

from repro import compat
from repro.compat import pl, pltpu
from repro.core.blocking import (_SUBLANE, BlockPlan, kernel_vmem_bytes,
                                 lane_halo, persistent_vmem_bytes,
                                 round_up, row_halo, strip_rows,
                                 vmem_limit)
from repro.core.stencil import StencilSpec

VARIANTS_2D = ("revolving", "multioperand")
VARIANTS_3D = ("revolving",)   # one streaming kernel; see module docstring


def variants_for(dims: int, backend: str | None = None) -> tuple[str, ...]:
    """Kernel variants legal for ``dims`` on ``backend`` (None: TPU).

    The GPU (Triton) lowering has no sequential-grid semantics and no
    persistent cross-block scratch, so everything built on them is off
    the table there: the 2D ``revolving`` variant (its shift-register
    scratch survives across x-tiles) and the whole 3D streaming kernel
    (a z pipeline threaded through a rolling scratch window). 2D keeps
    ``multioperand`` — scratch-free, every block independent — which is
    exactly the portability tradeoff docs/portability.md tabulates.
    """
    if backend == "gpu":
        return ("multioperand",) if dims == 2 else ()
    return VARIANTS_2D if dims == 2 else VARIANTS_3D


ENGINE_BACKENDS = ("interpret", "pallas", "gpu")


def check_backend(backend: str) -> str:
    """Validate a *resolved* engine backend. There is no default: a
    caller that names none would otherwise run the interpreter on the
    chip without saying so."""
    if backend not in ENGINE_BACKENDS:
        raise ValueError(
            f"unknown engine backend {backend!r}; expected one of "
            f"{ENGINE_BACKENDS} — 'reference' and 'auto' resolve in "
            f"kernels.ops, not here")
    return backend


# ---------------------------------------------------------------------------
# Shared in-kernel machinery
# ---------------------------------------------------------------------------

def window_mask(tile_idx, bx: int, halo: int, rows: int, true_w: int,
                row_lo, row_hi):
    """Valid-region mask for the [rows, bx + 2*halo] window of tile_idx.

    ``row_lo``/``row_hi`` bound the valid rows (possibly traced scalars);
    rows outside [row_lo, row_hi) are treated as outside the grid.
    """
    width = bx + 2 * halo
    col0 = tile_idx * bx - halo
    cols = col0 + jax.lax.broadcasted_iota(jnp.int32, (rows, width), 1)
    rr = jax.lax.broadcasted_iota(jnp.int32, (rows, width), 0)
    return (cols >= 0) & (cols < true_w) & (rr >= row_lo) & (rr < row_hi)


def boundary_fill(win, boundary: str, tile_idx, bx: int, halo: int,
                  true_w: int, row_lo, row_hi):
    """Re-impose the true-grid boundary on a [rows, width] window.

    ``dirichlet0``: out-of-grid cells read 0. ``clamp``: out-of-grid
    cells read the nearest in-grid cell (edge replicate). Mosaic lowers
    no gather of this shape, so clamp is built from selects: the edge
    columns sit at static window offsets (column 0 only ever falls in
    tile 0's window; column ``true_w - 1`` in at most the two last
    tiles'), and the edge rows of the traced interval ``[row_lo,
    row_hi)`` are picked out by a masked sum over rows, which is exact
    (one term is the row, the rest are zeros). Sharded slabs therefore
    clamp at *global* grid edges only, never at shard edges.
    """
    rows, width = win.shape
    if boundary != "clamp":
        mask = window_mask(tile_idx, bx, halo, rows, true_w, row_lo,
                           row_hi)
        return jnp.where(mask, win, jnp.zeros_like(win))
    col0 = tile_idx * bx - halo
    cols = col0 + jax.lax.broadcasted_iota(jnp.int32, (rows, width), 1)
    left = win[:, halo: halo + 1]
    right = left
    for t in range(-(-true_w // bx)):
        e = true_w - 1 - (t * bx - halo)     # column true_w-1 in tile t
        if 0 <= e < width - 1:               # tile t's window crosses it
            right = jnp.where(tile_idx == t, win[:, e: e + 1], right)
    win = jnp.where(cols < 0, left, jnp.where(cols >= true_w, right, win))
    rr = jax.lax.broadcasted_iota(jnp.int32, (rows, width), 0)
    last = jnp.maximum(row_hi - 1, row_lo)
    zero = jnp.zeros_like(win)
    top = jnp.sum(jnp.where(rr == row_lo, win, zero), axis=0,
                  keepdims=True)
    bot = jnp.sum(jnp.where(rr == last, win, zero), axis=0, keepdims=True)
    return jnp.where(rr < row_lo, top, jnp.where(rr > last, bot, win))


def fused_steps(win, specs, bt: int, apply_fns, fills,
                srcs=None, coeffs=None, scalars=None, unroll=False):
    """``bt`` fused program-group steps on a window.

    ``specs``/``apply_fns``/``fills``/``srcs``/``coeffs``/``scalars``
    hold one entry per *stage* — one sweep of a fused program group
    (a single-sweep call is the one-stage case). Per step, every stage
    re-imposes its own true-grid boundary on its input window
    (fill-between-sweeps), applies its update, then adds its pre-filled
    source sum; after the last fused step the last stage's fill runs
    once more on the result. For one stage this is exactly the
    historical ``fill, (apply, +src, fill) * bt`` sequence, so
    single-sweep execution stays bit-identical; for several stages it
    is bitwise-equal to dispatching the sweeps one at a time, because
    each fill rebuilds out-of-grid cells purely from in-grid cells.

    ``unroll`` traces the ``bt`` steps one after another instead of as
    a loop, so the compiler can schedule one step's tail under the
    next step's head: worth it on a register-sized window, where a
    step is a few hundred bundles.
    """
    M = len(specs)
    if srcs is None:
        srcs = (None,) * M
    if coeffs is None:
        coeffs = (None,) * M
    if scalars is None:
        scalars = (None,) * M

    def body(t, g):
        for m in range(M):
            g = fills[m](g)
            srow = scalars[m][t] if scalars[m] is not None else None
            g = apply_fns[m](g, specs[m], coeffs[m], srow)
            if srcs[m] is not None:
                g = g + srcs[m]
        return g

    if unroll:
        for t in range(bt):
            win = body(t, win)
        return fills[-1](win)
    return fills[-1](jax.lax.fori_loop(0, bt, body, win))


def _z_clamped_window(window, z_out, d_lo, d_hi, r: int):
    """Plane window with z taps re-indexed so planes outside
    [d_lo, d_hi) replicate the nearest valid plane (clamp-z). Built
    from statically-unrolled selects (no gather) so it lowers cleanly.
    """
    hi = jnp.maximum(d_hi - 1, d_lo)
    planes = []
    for j in range(2 * r + 1):
        slot = jnp.clip(z_out - r + j, d_lo, hi) - z_out + r
        acc = jnp.zeros_like(window[0])
        for m in range(2 * r + 1):
            acc = jnp.where(slot == m, window[m], acc)
        planes.append(acc)
    return jnp.stack(planes)


# ---------------------------------------------------------------------------
# 2D kernel bodies
# ---------------------------------------------------------------------------

def _unpack_2d(refs, stages, n_per: int):
    """Split the flat pallas ref list into named per-stage groups.

    ``stages``: one ``(has_src, coeff_meta, has_scal)`` triple per
    fused sweep; ``n_per`` is refs per streamed operand (3 for
    multioperand, 1 for revolving). Ref order: validity limits,
    per-stage scalars, the evolving grid, then per stage its source
    and coeff streams, then the output.
    """
    it = iter(refs)
    lim = next(it)
    scal = [next(it) if has_scal else None for (_, _, has_scal) in stages]
    xg = tuple(next(it) for _ in range(n_per))
    sg, cgs = [], []
    for (has_src, coeff_meta, _) in stages:
        sg.append(tuple(next(it) for _ in range(n_per))
                  if has_src else None)
        cgs.append([tuple(next(it) for _ in range(n_per))
                    for _ in coeff_meta])
    out = next(it)
    return lim, scal, xg, sg, cgs, out, it


def _reader(batched: bool):
    """Ref -> [rows, cols] block view: batched blocks carry a leading
    size-1 batch dim that the kernel body never needs to see."""
    if batched:
        return lambda ref: ref[0]
    return lambda ref: ref[...]


def _kernel_2d_multi(*refs, specs, bx, bt, halo, true_w, stages,
                     apply_fns, batched=False):
    lim_ref, scal_refs, xg, sgs, cgss, o_ref, _ = _unpack_2d(
        refs, stages, 3)
    rd = _reader(batched)
    row_lo, row_hi = lim_ref[0, 0], lim_ref[0, 1]
    i = pl.program_id(1 if batched else 0)

    def window(tri):
        cat = jnp.concatenate([rd(tri[0]), rd(tri[1]), rd(tri[2])],
                              axis=1)
        return cat[:, bx - halo: 2 * bx + halo]

    def fill_for(boundary):
        return lambda w: boundary_fill(w, boundary, i, bx, halo, true_w,
                                       row_lo, row_hi)

    fills = [fill_for(sp.boundary) for sp in specs]
    srcs = [fill_for("dirichlet0")(window(sg)) if sg is not None else None
            for sg in sgs]
    coeffs = [{name: fill_for(bnd)(window(tri))
               for (name, bnd), tri in zip(meta, cgs)} or None
              for (_, meta, _), cgs in zip(stages, cgss)]
    scals = [rd(sr) if sr is not None else None for sr in scal_refs]
    win = fused_steps(window(xg), specs, bt, apply_fns, fills,
                      srcs=srcs, coeffs=coeffs, scalars=scals)
    if batched:
        o_ref[0] = win[:, halo: halo + bx]
    else:
        o_ref[...] = win[:, halo: halo + bx]


def _kernel_2d_revolving(*refs, specs, bx, bt, halo, true_w, stages,
                         apply_fns, strip, batched=False):
    lim_ref, scal_refs, (x_ref,), sgs, cgss, o_ref, it = _unpack_2d(
        refs, stages, 1)
    rd = _reader(batched)
    # stream/scratch order: evolving grid, then per stage [src?]+coeffs.
    streams = [x_ref]
    for sg, cgs in zip(sgs, cgss):
        if sg is not None:
            streams.append(sg[0])
        streams += [tri[0] for tri in cgs]
    bufs = [next(it) for _ in streams]
    row_lo, row_hi = lim_ref[0, 0], lim_ref[0, 1]
    # The batch axis is the *outer* grid dimension, so tiles run
    # 0..nt per batch row and the i == 0 init below re-arms the
    # revolving scratches for every problem — slabs can't leak.
    i = pl.program_id(1 if batched else 0)
    rows = x_ref.shape[-2]
    # Scratch row hr + y holds grid row y; the hr rows above and below
    # the panel stay zero and lie outside the grid.
    hr = row_halo(halo, x_ref.dtype.itemsize)
    hx = lane_halo(halo)

    @pl.when(i == 0)
    def _init():
        for b in bufs:
            b[...] = jnp.zeros_like(b)

    # Shift the revolving buffers left by one tile...
    @pl.when(i > 0)
    def _shift():
        for b in bufs:
            b[:, : 2 * bx] = b[:, bx:]

    # ...and stream in tile i (zero if past the right edge of the grid
    # — the boundary fill recovers clamped values from in-grid cells).
    col0 = i * bx
    cols = col0 + jax.lax.broadcasted_iota(jnp.int32, (rows, bx), 1)
    rr = jax.lax.broadcasted_iota(jnp.int32, (rows, bx), 0)
    inb = (cols < true_w) & (rr >= row_lo) & (rr < row_hi)
    for b, r_in in zip(bufs, streams):
        b[hr: hr + rows, 2 * bx:] = jnp.where(inb, rd(r_in), 0)

    # Compute output tile t = i-1, one strip of rows at a time: rows
    # [s0 - hr, s0 + strip + hr) of the lane-aligned window
    # [bx - hx, 2*bx + hx) run the fused steps in registers, and the
    # strip's own rows and the tile's own columns are kept. The window
    # reaches at least ``halo`` cells past the kept ones on every side,
    # and past a grid edge it holds out-of-grid cells that every fill
    # rebuilds, so what a tap reads beyond the window's edge only
    # reaches the cropped rim: the taps run open (zero-filled), with
    # no clamp select.
    t = i - 1
    cols_inside = (t * bx - hx >= 0) & ((t + 1) * bx + hx <= true_w)
    scals = [rd(sr) if sr is not None else None for sr in scal_refs]
    open_specs = [dataclasses.replace(sp, boundary="dirichlet0")
                  for sp in specs]

    def run_strip(s0, edge):
        def window(b):
            return b[pl.ds(s0, strip + 2 * hr), bx - hx: 2 * bx + hx]

        if edge:
            # The strip's rows are grid rows from s0 - hr: shift the
            # validity interval into the strip's coordinates.
            lo, hi = row_lo - s0 + hr, row_hi - s0 + hr

            def fill_for(boundary):
                return lambda w: boundary_fill(w, boundary, t, bx, hx,
                                               true_w, lo, hi)
        else:
            # No cell of the window lies outside the grid: every fill
            # is the identity.
            def fill_for(boundary):
                return lambda w: w

        fills = [fill_for(sp.boundary) for sp in specs]
        bi = iter(bufs)
        xwin = window(next(bi))
        srcs, coeffs = [], []
        for (has_src, meta, _) in stages:
            srcs.append(fill_for("dirichlet0")(window(next(bi)))
                        if has_src else None)
            coeffs.append({name: fill_for(bnd)(window(next(bi)))
                           for (name, bnd) in meta} or None)
        win = fused_steps(xwin, open_specs, bt, apply_fns, fills,
                          srcs=srcs, coeffs=coeffs, scalars=scals,
                          unroll=True)
        out = win[hr: hr + strip, hx: hx + bx]
        if batched:
            o_ref[0, pl.ds(s0, strip), :] = out
        else:
            o_ref[pl.ds(s0, strip), :] = out

    def strip_body(j, carry):
        # The last strip ends at the panel's last row (it may overlap
        # the one before, which recomputes the same values).
        s0 = pl.multiple_of(jnp.minimum(j * strip, rows - strip),
                            _SUBLANE[x_ref.dtype.itemsize])
        inside = (cols_inside & (s0 - hr >= row_lo)
                  & (s0 + strip + hr <= row_hi))
        pl.when(inside)(lambda: run_strip(s0, edge=False))
        pl.when(jnp.logical_not(inside))(lambda: run_strip(s0, edge=True))
        return carry

    jax.lax.fori_loop(0, -(-rows // strip), strip_body, 0)


# ---------------------------------------------------------------------------
# 3D kernel body: 2.5D blocking, z streamed through a plane pipeline.
# Stage ``s`` holds a rolling window of the last 2r+1 planes of the field
# after ``s+1`` time steps; at z-grid-step ``k`` it consumes the stage
# ``s-1`` window and emits plane ``k - (s+1)*r`` — the FPGA pipeline in
# which each temporal stage lags its producer by ``r`` shift-register
# planes (thesis §5.3, fig. 5-6 b). Coefficient operands and per-step
# scalars (custom updates) are 2D-only; ``core.stencil`` enforces that.
# ---------------------------------------------------------------------------

def _kernel_3d_stream(*refs, specs, bx, bt, halo, true_h, true_w, has_src,
                      apply_fns, batched=False):
    if has_src:
        (lim_ref, xl_ref, xc_ref, xr_ref, sl_ref, sc_ref, sr_ref, o_ref,
         win_ref, src_ref) = refs
    else:
        lim_ref, xl_ref, xc_ref, xr_ref, o_ref, win_ref = refs
    # Batched blocks are (1, 1, rows, bx): drop the batch dim so the
    # plane pipeline below is identical to the single-problem one. The
    # batch axis is the outermost grid dim, so k restarts (and the
    # rolling windows re-zero at k == 0) for every (batch, x-tile).
    rd = (lambda ref: ref[0, 0]) if batched else (lambda ref: ref[0])
    d_lo, d_hi = lim_ref[0, 0], lim_ref[0, 1]
    i = pl.program_id(1 if batched else 0)       # x tile
    k = pl.program_id(2 if batched else 1)       # z pipeline step
    # A fused group cycles its M sweeps through bt program steps:
    # pipeline stage s applies sweep s % M. The 3D fuse rule (see
    # core.stencil._can_fuse) guarantees one radius and one boundary
    # across the group, so every stage lags its producer by the same r.
    M = len(specs)
    n_stages = bt * M
    r = specs[0].radius
    rows = xc_ref.shape[-2]
    boundary = specs[0].boundary
    clamp = boundary == "clamp"

    @pl.when(k == 0)
    def _init():
        win_ref[...] = jnp.zeros_like(win_ref)
        if has_src:
            src_ref[...] = jnp.zeros_like(src_ref)

    def fill_xy(plane):
        # In-plane boundary (y rows / x cols are never sharded, so the
        # bounds are static); the z boundary is owned by the pipeline.
        return boundary_fill(plane, boundary, i, bx, halo, true_w,
                             0, true_h)

    # ---- assemble the input plane window for z = k (stage-0 input) ----
    cat = jnp.concatenate([rd(xl_ref), rd(xc_ref), rd(xr_ref)], axis=1)
    plane = cat[:, bx - halo: 2 * bx + halo]
    xymask = window_mask(i, bx, halo, rows, true_w, 0, true_h)
    zero = jnp.zeros_like(plane)
    zin = (k >= d_lo) & (k < d_hi)
    if clamp:
        # Clamp in xy; out-of-grid z planes may hold anything — the
        # per-stage z re-index below never reads them.
        plane = fill_xy(plane)
    else:
        plane = jnp.where(xymask & zin, plane, zero)

    if has_src:
        # Rolling source-plane buffer (Hotspot3D power): slot halo holds
        # plane k; stage s reads its output plane's source at the
        # *static* slot halo - (s+1)*r. Sources are center-tap only, so
        # they are zero-filled outside the grid in either boundary mode.
        # (Aux operands are single-sweep-only in 3D — fuse rule.)
        scat = jnp.concatenate([rd(sl_ref), rd(sc_ref), rd(sr_ref)], axis=1)
        splane = scat[:, bx - halo: 2 * bx + halo]
        splane = jnp.where(xymask & zin, splane, zero)
        for j in range(halo):
            src_ref[j] = src_ref[j + 1]
        src_ref[halo] = splane

    # ---- pipeline: stage s consumes window[s], emits plane k-(s+1)*r ----
    for s in range(n_stages):
        sp = specs[s % M]
        # push the producer plane into stage s's rolling window
        for j in range(2 * r):
            win_ref[s, j] = win_ref[s, j + 1]
        win_ref[s, 2 * r] = plane
        z_out = k - (s + 1) * r
        stage_win = win_ref[s][...]
        if clamp:
            stage_win = _z_clamped_window(stage_win, z_out, d_lo, d_hi, r)
        updated = apply_fns[s % M](stage_win, sp, None, None)
        if has_src:
            updated = updated + src_ref[halo - (s + 1) * r]
        if clamp:
            plane = fill_xy(updated)
        else:
            plane = jnp.where(xymask & (z_out >= d_lo) & (z_out < d_hi),
                              updated, zero)

    if batched:
        o_ref[0, 0] = plane[:, halo: halo + bx]
    else:
        o_ref[0] = plane[:, halo: halo + bx]


# ---------------------------------------------------------------------------
# pallas_call assembly
# ---------------------------------------------------------------------------

def _limits(lo, hi, true_n: int) -> jax.Array:
    """The (1, 2) int32 leading-axis validity operand [lo, hi)."""
    lo = 0 if lo is None else lo
    hi = true_n if hi is None else hi
    return jnp.stack([jnp.asarray(lo, jnp.int32),
                      jnp.asarray(hi, jnp.int32)]).reshape(1, 2)


def _vmem_limit(specs, rows, bx, bt, halo, n_streams, variant, dtype):
    """Scoped-VMEM limit of one kernel launch: the modeled footprint
    (``core.blocking.kernel_vmem_bytes``) of its costliest fused stage."""
    return vmem_limit(max(
        kernel_vmem_bytes(sp, rows=rows, bx=bx, bt=bt, halo=halo,
                          n_streams=n_streams, variant=variant,
                          itemsize=jnp.dtype(dtype).itemsize)
        for sp in specs))


def _run_2d(x, specs, plan: BlockPlan, bx, bt, variant, backend, sources,
            coeffss, scalarss, apply_fns, valid_lo, valid_hi):
    interpret = backend == "interpret"
    batched = x.ndim == 3
    true_h, true_w = x.shape[-2:]
    hp, wp = plan.padded_rows, plan.padded_width
    pad2 = ((0, 0),) * (x.ndim - 2) + ((0, hp - true_h), (0, wp - true_w))
    xp = jnp.pad(x, pad2)
    # One fused dispatch consumes bt * sum(radii) halo columns: each
    # stage shrinks validity by its own radius, bt times over.
    halo = bt * sum(sp.radius for sp in specs)
    stages = tuple(
        (src is not None,
         tuple((op.name, op.boundary_of(sp))
               for op in sp.coeff_operands),
         scal is not None)
        for sp, src, scal in zip(specs, sources, scalarss))
    rows, nt = plan.padded_rows, plan.n_tiles

    # The batch axis lowers as the outermost grid dimension: every
    # BlockSpec grows a leading size-1 batch block whose index is the
    # batch-grid coordinate, and everything else (plan, scratches,
    # boundary logic) is untouched — one compilation for any B.
    def im(f):
        """Lift a tile-index map to the (possibly batched) grid."""
        return (lambda b, i: (b,) + f(i)) if batched else f

    block = ((1,) if batched else ()) + (rows, bx)
    lim = _limits(valid_lo, valid_hi, true_h)
    lim_spec = pl.BlockSpec((1, 2), lambda *_: (0, 0))
    head_specs = [lim_spec]
    head_args = [lim]
    for scal in scalarss:
        if scal is None:
            continue
        if batched:          # per-problem (B, bt, n_scalars) rows
            head_specs.append(pl.BlockSpec(
                (1,) + scal.shape[1:], lambda b, i: (b, 0, 0)))
        else:
            head_specs.append(pl.BlockSpec(scal.shape,
                                           lambda *_: (0, 0)))
        head_args.append(scal)
    kern_kw = dict(specs=specs, bx=bx, bt=bt, halo=halo, true_w=true_w,
                   stages=stages, apply_fns=apply_fns, batched=batched)
    streamed = [xp]
    for src, cps in zip(sources, coeffss):
        if src is not None:
            streamed.append(jnp.pad(src.astype(x.dtype), pad2))
        streamed += [jnp.pad(c.astype(x.dtype), pad2) for c in cps]
    n_streamed = len(streamed)
    grid = ((x.shape[0],) if batched else ()) + (nt,)
    params = compat.compiler_params_for(
        backend, len(grid), _vmem_limit(specs, rows, bx, bt, halo,
                                        n_streamed, variant, x.dtype))

    if variant == "multioperand":
        kern = functools.partial(_kernel_2d_multi, **kern_kw)
        tri_specs = [
            pl.BlockSpec(block, im(lambda i: (0, jnp.maximum(i - 1, 0)))),
            pl.BlockSpec(block, im(lambda i: (0, i))),
            pl.BlockSpec(block,
                         im(lambda i: (0, jnp.minimum(i + 1, nt - 1)))),
        ]
        out = pl.pallas_call(
            kern,
            grid=grid,
            in_specs=head_specs + tri_specs * n_streamed,
            out_specs=pl.BlockSpec(block, im(lambda i: (0, i))),
            out_shape=jax.ShapeDtypeStruct(xp.shape, xp.dtype),
            compiler_params=params,
            interpret=interpret,
            name="stencil2d_multioperand",
        )(*(head_args + [a for a in streamed for _ in range(3)]))
    elif variant == "revolving":
        kern = functools.partial(
            _kernel_2d_revolving, **kern_kw,
            strip=strip_rows(bx, halo, rows, xp.dtype.itemsize))
        in_spec = pl.BlockSpec(block,
                               im(lambda i: (0, jnp.minimum(i, nt - 1))))
        hr = row_halo(halo, xp.dtype.itemsize)
        scratch = [pltpu.VMEM((rows + 2 * hr, 3 * bx), xp.dtype)
                   for _ in range(n_streamed)]
        out = pl.pallas_call(
            kern,
            grid=grid[:-1] + (nt + 1,),
            in_specs=head_specs + [in_spec] * n_streamed,
            out_specs=pl.BlockSpec(
                block, im(lambda i: (0, jnp.maximum(i - 1, 0)))),
            out_shape=jax.ShapeDtypeStruct(xp.shape, xp.dtype),
            scratch_shapes=scratch,
            compiler_params=params,
            interpret=interpret,
            name="stencil2d_revolving",
        )(*(head_args + streamed))
    else:
        raise ValueError(f"unknown 2D variant {variant!r}; "
                         f"expected one of {VARIANTS_2D}")
    return out[..., :true_h, :true_w]


def _run_3d(x, specs, plan: BlockPlan, bx, bt, variant, backend, sources,
            apply_fns, valid_lo, valid_hi):
    if variant not in VARIANTS_3D:
        raise ValueError(f"unknown 3D variant {variant!r}; "
                         f"expected one of {VARIANTS_3D}")
    interpret = backend == "interpret"
    batched = x.ndim == 4
    true_d, true_h, true_w = x.shape[-3:]
    rows, nt = plan.padded_rows, plan.n_tiles
    M = len(specs)
    r = specs[0].radius       # equal across the fused group (3D rule)
    n_stages = bt * M
    fill = n_stages * r       # pipeline depth == x halo
    source = sources[0] if M == 1 else None
    has_src = source is not None
    pad3 = ((0, 0),) * (x.ndim - 2) + (
        (0, rows - true_h), (0, plan.padded_width - true_w))
    xp = jnp.pad(x, pad3)
    sp = jnp.pad(source.astype(x.dtype), pad3) if has_src else None

    def im(f):
        """Lift an (i, k) index map to the (possibly batched) grid."""
        return (lambda b, i, k: (b,) + f(i, k)) if batched else f

    block = ((1,) if batched else ()) + (1, rows, bx)
    lim = _limits(valid_lo, valid_hi, true_d)
    lim_spec = pl.BlockSpec((1, 2), lambda *_: (0, 0))

    kern = functools.partial(_kernel_3d_stream, specs=specs, bx=bx, bt=bt,
                             halo=fill, true_h=true_h, true_w=true_w,
                             has_src=has_src, apply_fns=apply_fns,
                             batched=batched)
    tri_specs = [
        pl.BlockSpec(block, im(lambda i, k: (
            jnp.minimum(k, true_d - 1), 0, jnp.maximum(i - 1, 0)))),
        pl.BlockSpec(block, im(lambda i, k: (
            jnp.minimum(k, true_d - 1), 0, i))),
        pl.BlockSpec(block, im(lambda i, k: (
            jnp.minimum(k, true_d - 1), 0, jnp.minimum(i + 1, nt - 1)))),
    ]
    scratch = [pltpu.VMEM((n_stages, 2 * r + 1, rows, bx + 2 * fill),
                          xp.dtype)]
    if has_src:
        scratch.append(
            pltpu.VMEM((fill + 1, rows, bx + 2 * fill), xp.dtype))
    grid = ((x.shape[0],) if batched else ()) + (nt, true_d + fill)
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[lim_spec] + tri_specs * (2 if has_src else 1),
        out_specs=pl.BlockSpec(block, im(lambda i, k: (
            jnp.maximum(k - fill, 0), 0, i))),
        out_shape=jax.ShapeDtypeStruct(xp.shape, xp.dtype),
        scratch_shapes=scratch,
        compiler_params=compat.compiler_params_for(
            backend, len(grid),
            _vmem_limit(specs, rows, bx, bt, fill, 1 + has_src,
                        variant, x.dtype)),
        interpret=interpret,
        name="stencil3d_stream",
    )(*((lim, xp, xp, xp, sp, sp, sp) if has_src else (lim, xp, xp, xp)))
    return out[..., :true_d, :true_h, :true_w]


# ---------------------------------------------------------------------------
# Persistent out-of-core kernel: the in-kernel DMA pipeline.
#
# The host-loop pipeline (outofcore/runner.py) overlaps transfers at the
# Python level — ``jax.device_put`` per tile, ``depth`` dispatches in
# flight. This path moves the streaming one level down, the way the FPGA
# designs chain PEs through shift registers (thesis §5.3, arXiv
# 2002.05983): ONE ``pallas_call`` per chunk keeps the chunk slab in HBM
# (``memory_space=ANY``) and DMAs each leading-axis tile's slab HBM→VMEM
# *inside* the kernel, double-buffered, so tile ``i+1``'s load runs
# under tile ``i``'s fused-step compute with no Python round-trip.
#
# Bitwise contract: the in-VMEM slab compute below re-applies the exact
# per-cell expression sequence of the in-core kernels — the same
# ``boundary_fill`` / ``fused_steps`` / plugin applies on the same tap
# values — and slab geometry follows the host-loop runner's clipped-slab
# cone argument (a fixed ``tile + 2*ghost`` DMA window at a clamped
# offset only ever *widens* a slab with real chunk rows, which the crop's
# dependency cone never distinguishes from the host loop's clipped
# slab). ``tests/test_pipelining.py`` pins the equality across
# radius × dims × bt × boundary.
#
# Capability gating mirrors ``variants_for``: the Triton lowering has no
# ``make_async_copy``/ANY-space refs, so ``gpu`` always falls back to
# the host loop; interpret mode is probed once per process (jax's
# interpreter has grown DMA support — where present this path runs for
# real on CPU CI, otherwise it degrades to the host loop with a recorded
# reason).
# ---------------------------------------------------------------------------

_KERNEL_PIPELINE_PROBE: dict = {}


def _probe_kernel_dma() -> tuple:
    """Try a minimal ANY→VMEM→ANY async-copy kernel under interpret."""
    try:
        def kern(x_hbm, o_hbm, buf, sem_in, sem_out):
            cin = pltpu.make_async_copy(x_hbm.at[pl.ds(0, 4)], buf,
                                        sem_in)
            cin.start()
            cin.wait()
            cout = pltpu.make_async_copy(buf, o_hbm.at[pl.ds(0, 4)],
                                         sem_out)
            cout.start()
            cout.wait()

        x = jnp.arange(4 * 128, dtype=jnp.float32).reshape(4, 128)
        out = pl.pallas_call(
            kern,
            grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            out_shape=jax.ShapeDtypeStruct((4, 128), jnp.float32),
            scratch_shapes=[pltpu.VMEM((4, 128), jnp.float32),
                            pltpu.SemaphoreType.DMA,
                            pltpu.SemaphoreType.DMA],
            compiler_params=compat.compiler_params_for("interpret", 1),
            interpret=True,
        )(x)
        if not bool(jnp.array_equal(out, x)):
            return False, "interpret-mode DMA probe returned wrong values"
        return True, ""
    except Exception as e:                      # noqa: BLE001 - gate, not crash
        return False, (f"interpret-mode DMA probe failed: "
                       f"{type(e).__name__}: {e}")


def kernel_pipeline_available(backend: str) -> tuple:
    """(available, reason) for the in-kernel DMA pipeline on ``backend``.

    ``variants_for``-style capability gate: ``gpu`` is never available
    (Triton offers no manual DMA / ANY-space refs), ``pallas`` (real
    TPU) always is, and ``interpret`` is probed once per process.
    ``REPRO_DISABLE_KERNEL_PIPELINE=1`` force-disables it everywhere
    (kill switch for triage; the host loop is always correct).
    """
    if os.environ.get("REPRO_DISABLE_KERNEL_PIPELINE"):
        return False, "disabled via REPRO_DISABLE_KERNEL_PIPELINE"
    if backend == "gpu":
        return False, ("the Triton lowering has no make_async_copy / "
                       "ANY-memory-space refs — host-loop pipeline only "
                       "(docs/portability.md)")
    if backend == "pallas":
        return True, ""
    got = _KERNEL_PIPELINE_PROBE.get("interpret")
    if got is None:
        got = _probe_kernel_dma()
        _KERNEL_PIPELINE_PROBE["interpret"] = got
    return got


def kernel_pipeline_supported(spec: StencilSpec, *, backend: str,
                              batched: bool = False,
                              has_source: bool = False,
                              has_aux: bool = False,
                              has_scalars: bool = False) -> tuple:
    """(supported, reason) for running THIS problem through the
    persistent kernel. Geometry is always representable (the DMA window
    clamps into the chunk), so the gates are backend capability plus the
    operand forms the in-kernel compute does not stream yet."""
    ok, why = kernel_pipeline_available(backend)
    if not ok:
        return False, why
    if spec.dims not in (2, 3):
        return False, f"spec.dims must be 2 or 3, got {spec.dims}"
    if batched:
        return False, ("batched grids ride the host-loop pipeline (the "
                       "whole batch travels on every slab)")
    if has_source or has_aux or has_scalars:
        return False, ("aux/source/scalars operands stream per-slab on "
                       "the host-loop pipeline only")
    return True, ""


def _col_tile(ref, t, bx: int):
    """Columns ``[t*bx, (t+1)*bx)`` of a ``[..., rows, cols]`` VMEM ref
    at a traced tile index (a lane-aligned dynamic slice)."""
    return ref[..., pl.ds(pl.multiple_of(t * bx, bx), bx)]


def _slab_compute_2d(src, dst, row_lo, row_hi, *, spec, bx, bt, true_w,
                     apply_fn):
    """One fused block over a VMEM-resident ``(rows, nt*bx)`` 2D slab
    ref ``src``, written to the same-shape ref ``dst``.

    Per x tile (a ``fori_loop`` with a traced tile index) the window is
    assembled from the three neighbouring column tiles exactly as the
    multioperand kernel assembles it from its three BlockSpec blocks,
    then filled, stepped and cropped with the in-core expression
    sequence; the row limits arrive traced, as in-core.
    """
    nt = src.shape[-1] // bx
    halo = bt * spec.radius

    def tbody(j, carry):
        cat = jnp.concatenate(
            [_col_tile(src, jnp.maximum(j - 1, 0), bx),
             _col_tile(src, j, bx),
             _col_tile(src, jnp.minimum(j + 1, nt - 1), bx)], axis=1)
        win = cat[:, bx - halo: 2 * bx + halo]

        def fill(w):
            return boundary_fill(w, spec.boundary, j, bx, halo, true_w,
                                 row_lo, row_hi)

        win = fused_steps(win, (spec,), bt, (apply_fn,), [fill])
        dst[:, pl.ds(pl.multiple_of(j * bx, bx), bx)] = \
            win[:, halo: halo + bx]
        return carry

    jax.lax.fori_loop(0, nt, tbody, 0)


def _slab_compute_3d(src, dst, win_ref, d_lo, d_hi, *, spec, bx, bt,
                     true_w, apply_fn):
    """One fused block over a VMEM-resident ``(d, rows, nt*bx)`` 3D slab
    ref: the z-streaming plane pipeline of ``_kernel_3d_stream``, run as
    a ``fori_loop`` over x tiles around a ``fori_loop`` over z steps,
    with the rolling stage windows in the ``win_ref`` scratch (re-zeroed
    per x tile, as the in-core kernel re-zeros at ``k == 0``)."""
    d, rows, wp = src.shape
    nt = wp // bx
    r = spec.radius
    fill_d = bt * r
    clamp = spec.boundary == "clamp"

    def zbody(k, i):
        kc = jnp.minimum(k, d - 1)
        cat = jnp.concatenate(
            [_col_tile(src.at[kc], jnp.maximum(i - 1, 0), bx),
             _col_tile(src.at[kc], i, bx),
             _col_tile(src.at[kc], jnp.minimum(i + 1, nt - 1), bx)], axis=1)
        plane = cat[:, bx - fill_d: 2 * bx + fill_d]
        # In-plane bounds are static (y/x are never streamed), exactly
        # as in _kernel_3d_stream; only the z interval is traced.
        xymask = window_mask(i, bx, fill_d, rows, true_w, 0, rows)
        zero = jnp.zeros_like(plane)
        zin = (k >= d_lo) & (k < d_hi)

        def fill_xy(p):
            return boundary_fill(p, spec.boundary, i, bx, fill_d, true_w,
                                 0, rows)

        if clamp:
            plane = fill_xy(plane)
        else:
            plane = jnp.where(xymask & zin, plane, zero)
        for s in range(bt):
            for j in range(2 * r):
                win_ref[s, j] = win_ref[s, j + 1]
            win_ref[s, 2 * r] = plane
            z_out = k - (s + 1) * r
            stage_win = win_ref[s]
            if clamp:
                stage_win = _z_clamped_window(stage_win, z_out, d_lo, d_hi,
                                              r)
            updated = apply_fn(stage_win, spec, None, None)
            if clamp:
                plane = fill_xy(updated)
            else:
                plane = jnp.where(xymask & (z_out >= d_lo) & (z_out < d_hi),
                                  updated, zero)
        dst[jnp.maximum(k - fill_d, 0), :,
            pl.ds(pl.multiple_of(i * bx, bx), bx)] = plane[:, fill_d:
                                                           fill_d + bx]
        return i

    def xbody(i, carry):
        win_ref[...] = jnp.zeros_like(win_ref)
        jax.lax.fori_loop(0, d + fill_d, zbody, i)
        return carry

    jax.lax.fori_loop(0, nt, xbody, 0)


def _kernel_persistent(x_hbm, o_hbm, in_buf, res_buf, *rest, compute,
                       tile, ghost, lead, valid, n_rows, dma_len, n_inner,
                       align):
    """Grid step ``i`` computes tile ``i`` of the chunk; the DMA for
    tile ``i+1``'s slab is started *before* waiting on tile ``i``'s, so
    it lands under tile ``i``'s fused-step compute. ``rest`` holds the
    compute's own scratch (3D stage windows) then the semaphores.

    Every row offset and DMA size is a multiple of ``align`` (2D HBM
    rows are (8, 128)-tiled): the chunk arrives padded so its first
    owned row is aligned, tiles and ghosts are whole multiples, and
    ``valid`` bounds the real chunk rows, which the slab compute reads
    as traced limits — padding rows are outside the grid, exactly like
    the host loop's clipped slab edge."""
    *scratch, in_sems, out_sem = rest
    i = pl.program_id(0)
    slot = i % 2

    def in_off(t):
        # Fixed-size DMA window (pl.ds needs a static size) at a
        # clamped offset: edge tiles widen into real chunk rows, which
        # the crop's dependency cone cannot distinguish from the host
        # loop's clipped slab.
        return pl.multiple_of(
            jnp.clip(lead + t * tile - ghost, 0, n_rows - dma_len), align)

    def copy_in(t, s):
        return pltpu.make_async_copy(
            x_hbm.at[pl.ds(in_off(t), dma_len)], in_buf.at[s],
            in_sems.at[s])

    @pl.when(i == 0)
    def _start_first():
        copy_in(0, 0).start()

    @pl.when(i + 1 < n_inner)
    def _prefetch():
        copy_in(i + 1, 1 - slot).start()

    copy_in(i, slot).wait()
    a0 = in_off(i)
    compute(in_buf.at[slot], res_buf, *scratch, valid[0] - a0,
            valid[1] - a0)
    cp = pltpu.make_async_copy(
        res_buf.at[pl.ds(pl.multiple_of(lead + i * tile - a0, align),
                         tile)],
        o_hbm.at[pl.ds(pl.multiple_of(i * tile, align), tile)], out_sem)
    cp.start()
    cp.wait()


@functools.partial(jax.jit,
                   static_argnames=("spec", "bx", "bt", "tile", "lead",
                                    "owned", "backend", "apply_fn"))
def stencil_call_persistent(chunk: jax.Array, spec: StencilSpec, *,
                            bx: int, bt: int, tile: int, lead: int,
                            owned: int, backend: str,
                            apply_fn=None) -> jax.Array:
    """``bt`` fused steps over a device-resident chunk slab, streamed
    tile-by-tile through VMEM by the persistent in-kernel DMA pipeline.

    ``chunk`` is the chunk's clipped slab (leading-axis rows
    ``[c0 - ghost, c1 + ghost)`` clipped to the grid, like one big
    host-loop slab); ``lead`` is the number of ghost rows before the
    first owned row (0 when the chunk starts at the true grid edge),
    ``owned`` the number of owned rows, and ``tile`` the in-kernel tile
    extent (size it with ``core.blocking.persistent_tile``: two tile
    slabs and one result slab live in VMEM; 2D tiles round to whole
    sublane tiles). Returns the ``(owned, ...)`` computed rows. Gate
    with :func:`kernel_pipeline_supported` first — this entry validates
    but does not fall back.
    """
    if backend not in ("interpret", "pallas"):
        raise ValueError(
            f"stencil_call_persistent supports backends ('interpret', "
            f"'pallas'), got {backend!r} — gate with "
            f"kernel_pipeline_supported and fall back to the host loop")
    dims = spec.dims
    if chunk.ndim != dims:
        raise ValueError(f"chunk rank {chunk.ndim} != spec.dims {dims} "
                         f"(the persistent kernel is unbatched)")
    g = bt * spec.radius
    if g > bx:
        raise ValueError(f"fused halo {g} (bt={bt} x radius "
                         f"{spec.radius}) exceeds the tile width bx={bx}")
    chunk_len = chunk.shape[0]
    if not 1 <= tile <= chunk_len:
        raise ValueError(f"tile must be in [1, {chunk_len}], got {tile}")
    if not (0 <= lead and 1 <= owned and lead + owned <= chunk_len):
        raise ValueError(f"invalid chunk geometry: lead={lead} "
                         f"owned={owned} chunk_len={chunk_len}")
    align = _SUBLANE[chunk.dtype.itemsize] if dims == 2 else 1
    tile = max(align, tile - tile % align)
    ghost = round_up(g, align)
    front = -lead % align
    n_inner = -(-owned // tile)
    n_rows = round_up(max(front + chunk_len, front + lead + n_inner * tile),
                      align)
    dma_len = min(tile + 2 * ghost, n_rows)
    true_w = chunk.shape[-1]
    wp = round_up(true_w, bx)
    xp = jnp.pad(chunk, ((front, n_rows - front - chunk_len),)
                 + ((0, 0),) * (dims - 2) + ((0, wp - true_w),))
    slab = (dma_len,) + xp.shape[1:]
    if apply_fn is None:
        if dims == 2:
            from repro.kernels.stencil2d import _apply_2d as apply_fn
        else:
            from repro.kernels.stencil3d import _apply_3d as apply_fn
    slab_compute = _slab_compute_2d if dims == 2 else _slab_compute_3d
    compute = functools.partial(slab_compute, spec=spec, bx=bx, bt=bt,
                                true_w=true_w, apply_fn=apply_fn)
    scratch = [pltpu.VMEM((2,) + slab, xp.dtype),
               pltpu.VMEM(slab, xp.dtype)]
    if dims == 3:
        scratch.append(pltpu.VMEM(
            (bt, 2 * spec.radius + 1, xp.shape[1], bx + 2 * g), xp.dtype))
    kern = functools.partial(
        _kernel_persistent, compute=compute, tile=tile, ghost=ghost,
        lead=front + lead, valid=(front, front + chunk_len),
        n_rows=n_rows, dma_len=dma_len, n_inner=n_inner, align=align)
    limit = vmem_limit(persistent_vmem_bytes(
        spec, xp.shape[1:], bx=bx, bt=bt, tile=tile,
        itemsize=xp.dtype.itemsize))
    out = pl.pallas_call(
        kern,
        grid=(n_inner,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct((n_inner * tile,) + xp.shape[1:],
                                       xp.dtype),
        scratch_shapes=scratch + [pltpu.SemaphoreType.DMA((2,)),
                                  pltpu.SemaphoreType.DMA],
        compiler_params=compat.compiler_params_for(backend, 1, limit),
        interpret=backend == "interpret",
        name="stencil_persistent",
    )(xp)
    return out[:owned, ..., :true_w]


@functools.partial(jax.jit,
                   static_argnames=("specs", "bx", "bt", "variant",
                                    "backend", "apply_fns"))
def stencil_call_program(x: jax.Array, specs, *, bx: int, bt: int,
                         backend: str, variant: str = "revolving",
                         source: jax.Array | None = None, aux=None,
                         scalars=None, apply_fns=None,
                         valid_lo=None, valid_hi=None) -> jax.Array:
    """Run ``bt`` fused program steps of a fused sweep group.

    ``specs`` is the spec tuple of one legal fuse group (see
    ``core.stencil._can_fuse``); each program step applies every spec
    once, in order, with each spec's own true-grid boundary re-imposed
    before its apply (fill-between-sweeps) — bitwise-equal to
    dispatching the sweeps one at a time. One dispatch consumes a
    ``bt * sum(radii)`` halo.

    ``aux`` maps the union of all sweeps' declared operand names to
    same-shape grids (a name declared by several sweeps shares one
    grid). ``scalars`` is a tuple with one entry per spec: ``None`` or
    that sweep's ``(bt, n_scalars)`` (or per-problem ``(B, bt,
    n_scalars)``) values. ``apply_fns``: one plugin per spec (``None``
    entries default to the matching stencil module's IR apply).
    ``source`` is the legacy single-spec additive grid.

    ``valid_lo``/``valid_hi``: leading-axis validity interval [lo, hi)
    — rows (2D) / planes (3D) outside it behave as outside the grid
    at every fused step (zero or edge-replicate per each spec's
    boundary), and the result's rows (planes) outside it are not part
    of the answer: the 2D revolving kernel leaves zeros in a clamped
    strip that lies wholly outside. May be traced scalars; defaults to
    the full extent. Used by ``distributed/halo.py`` to mark ghost halos
    and shard padding under one SPMD program.

    **Batched execution**: ``x`` of rank ``dims + 1`` is a batch of
    ``B`` independent problems sharing one program and grid shape,
    lowered as the outermost Pallas grid dimension (module docstring);
    every aux operand must then be ``[B, *grid]`` too. Each problem's
    result is bitwise-identical to its solo run.
    """
    check_backend(backend)
    specs = tuple(specs)
    if not specs:
        raise ValueError("specs must hold at least one StencilSpec")
    M = len(specs)
    dims = specs[0].dims
    if backend == "gpu":
        legal = variants_for(dims, "gpu")
        if not legal:
            raise NotImplementedError(
                "the 3D streaming kernel needs sequential-grid "
                "semantics and persistent scratch, which the Triton "
                "lowering does not offer; the 'gpu' backend is 2D-only "
                "(docs/portability.md tabulates the matrix)")
        if variant not in legal:
            raise ValueError(
                f"variant {variant!r} is not available on the 'gpu' "
                f"backend (its revolving scratch must persist across "
                f"grid blocks — a TPU sequential-grid capability); "
                f"legal: {legal}")
        if compat.platform() != "gpu":
            raise RuntimeError(
                f"engine backend 'gpu' requires a GPU host platform, "
                f"but jax.default_backend() is "
                f"{compat.platform()!r}; use 'interpret' (the oracle) "
                f"or 'auto' here")
    if any(sp.dims != dims for sp in specs):
        raise ValueError("all fused specs must share one dims")
    if source is not None and M != 1:
        raise ValueError("legacy `source` is single-spec only; declare "
                         "source-role aux operands instead")
    if M > 1 and dims == 3:
        r0, b0 = specs[0].radius, specs[0].boundary
        for sp in specs:
            if (sp.radius != r0 or sp.boundary != b0 or sp.aux
                    or sp.n_scalars or sp.layout == "custom"):
                raise ValueError(
                    "3D fused groups need equal radii, one boundary, "
                    "star/box layouts and no aux/scalars (see "
                    "core.stencil._can_fuse)")
    if x.ndim not in (dims, dims + 1):
        raise ValueError(
            f"grid rank {x.ndim} != spec.dims {dims} (or "
            f"{dims + 1} with a leading batch axis)")
    batched = x.ndim == dims + 1
    if batched and x.shape[0] == 0:
        raise ValueError("batched grid must have at least one problem")
    halo = bt * sum(sp.radius for sp in specs)
    if halo > bx:
        raise ValueError(
            f"fused halo {halo} (bt={bt} x radii {[sp.radius for sp in specs]}) "
            f"exceeds the tile width bx={bx}")
    label = specs[0].name if M == 1 else "+".join(sp.name for sp in specs)
    aux = dict(aux) if aux else {}
    declared = []
    for sp in specs:
        for op in sp.aux:
            if op.name not in declared:
                declared.append(op.name)
    missing = [n for n in declared if n not in aux]
    if missing:
        raise ValueError(f"spec {label!r} requires aux operands "
                         f"{missing}")
    extra = [n for n in aux if n not in declared]
    if extra:
        raise ValueError(f"unknown aux operands {extra} for spec "
                         f"{label!r} (declared: {declared})")
    for n, a in aux.items():
        if a.shape != x.shape:
            raise ValueError(f"aux operand {n!r} shape {a.shape} != grid "
                             f"shape {x.shape}")
    if scalars is None:
        scalars = (None,) * M
    scalars = tuple(scalars)
    if len(scalars) != M:
        raise ValueError(f"scalars must hold one entry per spec ({M}), "
                         f"got {len(scalars)}")

    sources, coeffss, scalarss = [], [], []
    for m, sp in enumerate(specs):
        scal = scalars[m]
        srcs = [aux[op.name] for op in sp.source_operands]
        if m == 0 and source is not None:
            srcs.append(source)
        combined = None
        if srcs:
            combined = srcs[0]
            for s in srcs[1:]:
                combined = combined + s
        sources.append(combined)
        coeffss.append([aux[op.name] for op in sp.coeff_operands])
        if sp.n_scalars:
            if scal is None:
                raise ValueError(f"spec {sp.name!r} requires scalars of "
                                 f"shape ({bt}, {sp.n_scalars})")
            scal = jnp.asarray(scal, jnp.float32)
            if batched:
                B = x.shape[0]
                if scal.ndim == 3:
                    if scal.shape[0] != B:
                        raise ValueError(
                            f"scalars batch dim {scal.shape[0]} != grid "
                            f"batch dim {B}")
                    scal = scal.reshape(B, bt, sp.n_scalars)
                else:     # shared across the batch: broadcast per problem
                    scal = jnp.broadcast_to(
                        scal.reshape(bt, sp.n_scalars),
                        (B, bt, sp.n_scalars))
            else:
                scal = scal.reshape(bt, sp.n_scalars)
            scalarss.append(scal)
        else:
            if scal is not None:
                raise ValueError("scalars passed but spec.n_scalars == 0")
            scalarss.append(None)

    plan = BlockPlan(specs[0], x.shape[-dims:], bx=bx, bt=bt,
                     itemsize=x.dtype.itemsize)
    if apply_fns is None:
        apply_fns = (None,) * M
    if len(apply_fns) != M:
        raise ValueError(f"apply_fns must hold one entry per spec ({M}), "
                         f"got {len(apply_fns)}")
    if dims == 2:
        from repro.kernels.stencil2d import _apply_2d
        apply_fns = tuple(f if f is not None else _apply_2d
                          for f in apply_fns)
        return _run_2d(x, specs, plan, bx, bt, variant, backend,
                       sources, coeffss, scalarss, apply_fns,
                       valid_lo, valid_hi)
    from repro.kernels.stencil3d import _apply_3d
    apply_fns = tuple(f if f is not None else _apply_3d
                      for f in apply_fns)
    return _run_3d(x, specs, plan, bx, bt, variant, backend, sources,
                   apply_fns, valid_lo, valid_hi)


def stencil_call(x: jax.Array, spec: StencilSpec, *, bx: int, bt: int,
                 backend: str, variant: str = "revolving",
                 source: jax.Array | None = None, aux=None,
                 scalars: jax.Array | None = None,
                 apply_fn=None, valid_lo=None, valid_hi=None) -> jax.Array:
    """Run ``bt`` fused time steps of ``spec`` over a 2D or 3D grid.

    The single-sweep front door — a thin wrapper over
    :func:`stencil_call_program` with a one-spec group, kept because
    nearly every call site runs one sweep. All semantics (aux operands,
    legacy ``source``, per-step ``scalars``, validity interval, batch
    axis) are documented there; the lowering is bit-identical to the
    pre-program engine.
    """
    return stencil_call_program(
        x, (spec,), bx=bx, bt=bt, variant=variant, backend=backend,
        source=source, aux=aux,
        scalars=None if scalars is None else (scalars,),
        apply_fns=None if apply_fn is None else (apply_fn,),
        valid_lo=valid_lo, valid_hi=valid_hi)


def stencil_call_vmap(x: jax.Array, spec: StencilSpec, *, bx: int, bt: int,
                      backend: str, variant: str = "revolving",
                      source: jax.Array | None = None, aux=None,
                      scalars: jax.Array | None = None,
                      apply_fn=None) -> jax.Array:
    """Differential oracle for the native batched lowering.

    Runs the batch through ``jax.vmap`` of the *single-problem* engine
    (Pallas's batching rule also prepends a grid dimension, but through
    an entirely independent code path), so a bug in the hand-rolled
    batch lowering cannot hide: tests assert the two are bitwise equal.
    Not a serving path — use ``stencil_call`` with a batched grid.
    """
    if x.ndim != spec.dims + 1:
        raise ValueError(f"stencil_call_vmap needs a [B, *grid] input of "
                         f"rank {spec.dims + 1}, got rank {x.ndim}")
    B = x.shape[0]
    aux = dict(aux) if aux else None
    if spec.n_scalars and scalars is not None:
        scalars = jnp.asarray(scalars, jnp.float32)
        if scalars.ndim != 3:       # shared: same (bt, n) for every slab
            scalars = jnp.broadcast_to(
                scalars.reshape(bt, spec.n_scalars),
                (B, bt, spec.n_scalars))

    def call(x1, src1, aux1, scal1):
        return stencil_call(x1, spec, bx=bx, bt=bt, variant=variant,
                            backend=backend, source=src1, aux=aux1,
                            scalars=scal1, apply_fn=apply_fn)

    in_axes = (0,
               None if source is None else 0,
               None if aux is None else {k: 0 for k in aux},
               None if scalars is None else 0)
    return jax.vmap(call, in_axes=in_axes)(x, source, aux, scalars)
