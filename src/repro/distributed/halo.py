"""Multi-device deep-halo stencil execution (shard_map + ppermute).

The thesis's combined spatial+temporal blocking is single-device; this
module is the scale-out step taken by the multi-FPGA follow-up work on
high-order stencils (Zohouri et al., arXiv:2002.05983) and the
structured-mesh solver designs of Kamalakkannan et al.
(arXiv:2101.01177): partition the grid *spatially* across devices and
exchange **deep halos** — depth ``r * bt`` — once per fused time block,
so temporal blocking survives distribution.

Scheme (one sweep = ``bt`` fused steps):

    device i owns leading-axis slice [i*S, (i+1)*S) of the grid
    (rows for 2D, z-planes for 3D; S = ceil(extent / n))

         neighbor i-1                 neighbor i+1
        ┌───────────┐                ┌───────────┐
        │ bottom h  │ ──ppermute──▶  │   top h   │ ──ppermute──▶ ...
        └───────────┘                └───────────┘
              │          ┌────────────────┐          │
              └────────▶ │ h │ shard S │ h│ ◀────────┘
                         └────────────────┘
                         run single-device engine on the slab
                         (bt fused steps), crop the center S

Every *operand* shards the same way: the main grid, the legacy
``source`` grid, and each aux operand declared by the spec (Hotspot's
power term, variable-coefficient fields) is split along the leading
axis and has its (step-constant) halos exchanged once per call.
Per-step scalars (custom updates) are replicated to every device.

Exactness: the slab result equals the global result wherever the
dependency cone (``bt`` steps x radius ``r`` = depth ``h``) stays inside
the slab — precisely the cropped center. Grid edges and shard padding
are handled by the engine's *leading-axis validity interval*
(``valid_lo``/``valid_hi``): ghost rows outside the global grid behave
as outside-grid at every fused step — zeroed under ``dirichlet0``,
edge-replicated under ``clamp``. Crucially, the boundary mode therefore
applies at **true grid edges only**: rows a device receives from its
neighbors sit *inside* the validity interval, so shard-interior edges
are never clamped or zeroed — they keep their exchanged ghost data.
This reproduces the ``kernels/ref.py`` contract bit-for-bit (up to
float association) for any device count and any (shard-unaligned) grid
size, in either boundary mode.

Overlap: with ``overlap=True`` each sweep computes the shard *interior*
(which needs no halo) on a slab that is ready immediately, while the
ppermutes for the two edge strips are in flight — the async-collective
pattern of ``distributed/overlap.py`` (XLA turns the early ppermutes
into collective-permute-start/done pairs that run under the interior
compute). The two ``3h``-deep edge strips are then finished from the
arrived halos. Both schedules are numerically identical; tests assert
it.

Batched grids (``x: [B, *grid]``, the engine's leading batch axis) add
a second partitioning choice, and the runner always prefers the
cheaper one:

  * **batch-axis sharding** — when ``B % n_devices == 0`` every device
    owns ``B / n`` *whole* problems and runs the single-device batched
    engine on them: no halos, no ppermutes, no redundant slab compute,
    perfect scaling. This is why the serving front-end buckets to
    device-divisible batch sizes;
  * **grid sharding** — otherwise the grid's leading axis (array axis
    1) is sharded exactly as in the unbatched case: every device holds
    the full batch of its slab rows/planes, and the deep-halo exchange
    carries ``B`` boundary slices per neighbor.

``shard_strategy`` names the choice; tests pin both the preference and
the parity of each path against a loop of single-problem runs.
"""
from __future__ import annotations

import warnings

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import compat
from repro.core.blocking import shard_extent
from repro.core.stencil import StencilSpec
from repro.kernels import engine

AXIS = "shard"

# Sentinel name for the legacy (spec-undeclared) source operand.
_LEGACY_SRC = "__source__"


def max_bt(spec: StencilSpec, extent: int, n_devices: int) -> int:
    """Largest temporal degree whose halo fits one shard (h = r*bt <= S)."""
    return max(1, shard_extent(extent, n_devices) // spec.radius)


def shard_strategy(shape, spec: StencilSpec, n_devices: int) -> str:
    """How ``stencil_run_sharded`` will partition ``shape``.

    ``"batch"`` when a leading batch axis divides the device count
    evenly — whole problems per device, no halo exchange at all — else
    ``"grid"`` (leading *grid* axis sharded with deep halos). The
    preference is strict: batch-axis sharding is never slower, so a
    divisible batch always takes it.
    """
    batched = len(shape) == spec.dims + 1
    if batched and n_devices > 1 and shape[0] % n_devices == 0:
        return "batch"
    return "grid"


def _sl(a, lo, hi, ax: int):
    """``a[lo:hi]`` along axis ``ax`` (None bounds = open end)."""
    idx = [slice(None)] * a.ndim
    idx[ax] = slice(lo, hi)
    return a[tuple(idx)]


def _device_mesh(n_devices: int, devices=None) -> Mesh:
    devs = list(devices) if devices is not None else jax.devices()
    if len(devs) < n_devices:
        raise ValueError(
            f"n_devices={n_devices} but only {len(devs)} devices visible "
            f"(hint: XLA_FLAGS=--xla_force_host_platform_device_count=N)")
    return Mesh(np.array(devs[:n_devices]), (AXIS,))


def exchange_packed(send_top: jax.Array, send_bot: jax.Array, n: int,
                    axis_name: str = AXIS):
    """ppermute *already-packed* boundary strips to both neighbors.

    The collective half of ``exchange_halos``, split out so the strips
    can come straight from the engine dispatch that computed them
    (fused halo packing: ``_sweep(send_depth=...)`` carves the next
    sweep's source strips from its own engine outputs, skipping the
    slice off the re-assembled shard). ``send_top``/``send_bot`` are
    this device's top/bottom strips; returns ``(from_above,
    from_below)``: the previous device's bottom strip and the next
    device's top strip. Edge devices receive zeros (ppermute's behavior
    for uncovered destinations) — those rows sit outside the engine's
    validity interval, so the boundary mode (zero / clamp) is what
    actually applies there.
    """
    down = [(i, i + 1) for i in range(n - 1)]   # my bottom h -> next dev
    up = [(i, i - 1) for i in range(1, n)]      # my top h    -> prev dev
    from_above = jax.lax.ppermute(send_bot, axis_name, down)
    from_below = jax.lax.ppermute(send_top, axis_name, up)
    return from_above, from_below


def exchange_halos(xs: jax.Array, h: int, n: int, axis_name: str = AXIS,
                   ax: int = 0):
    """ppermute the ``h``-deep boundary slices of ``xs`` to both
    neighbors — ``exchange_packed`` over strips sliced off the shard.

    Returns ``(from_above, from_below)`` as above. ``ax``: the sharded
    axis within each array (1 for batched grids, whose axis 0 is the
    batch riding along whole).
    """
    return exchange_packed(_sl(xs, None, h, ax), _sl(xs, -h, None, ax),
                           n, axis_name)


def gather_slab(slabs, bounds, start: int, end: int, *, ax: int = 0,
                owner: int | None = None):
    """Assemble global leading-axis rows ``[start, end)`` from
    per-device **host-resident** slab buffers — the tile-granular
    exchange entry point of the composed out-of-core × multi-device
    runner (``outofcore.stencil_run_outofcore(n_devices > 1)``).

    This is ``exchange_packed`` replayed one memory level up: where
    the in-core sharded runner ppermutes ``r*bt``-deep strips between
    device HBMs once per sweep, here each *tile* dispatch pulls
    exactly the rows its clipped slab needs from whichever host
    buffers own them — its own shard's rows plus up to ``r*bt``
    foreign rows per side (more when a ghost is deeper than a
    neighbor's whole slab: the walk spans as many owners as the range
    crosses, so tiny shards under deep fused blocks stay exact).

    ``slabs[d]`` holds the rows ``bounds[d] = (lo, hi)`` of the global
    grid along array axis ``ax`` (``ax=1`` for batched grids).
    Returns ``(rows, foreign)``: the contiguous assembly — a zero-copy
    view when a single buffer covers the range — and the number of
    rows pulled from buffers other than ``bounds[owner]`` (0 when
    ``owner`` is None), the runner's halo-traffic accounting.
    """
    if not (0 <= start < end):
        raise ValueError(f"need 0 <= start < end, got [{start}, {end})")
    pieces = []
    foreign = covered = 0
    for d, (lo, hi) in enumerate(bounds):
        s, e = max(start, lo), min(end, hi)
        if s >= e:
            continue
        pieces.append(_sl(slabs[d], s - lo, e - lo, ax))
        covered += e - s
        if owner is not None and d != owner:
            foreign += e - s
    if covered != end - start:
        raise ValueError(
            f"rows [{start}, {end}) not fully covered by slab bounds "
            f"{list(bounds)} ({covered} of {end - start} rows found)")
    if len(pieces) == 1:
        return pieces[0], foreign
    return np.concatenate(pieces, axis=ax), foreign


def _engine_call(slab, specs, bx, bts, variant, backend, extras, scals,
                 lo, hi):
    """Run the single-device engine on one slab.

    ``specs``: the fuse group's spec tuple (a 1-tuple for plain
    single-spec runs). ``extras`` maps operand names (aux names + the
    legacy-source sentinel) to slabs. ``scals``: per-spec scalars
    tuple, or None.
    """
    extras = dict(extras)
    src = extras.pop(_LEGACY_SRC, None)
    return engine.stencil_call_program(
        slab, specs, bx=bx, bt=bts, variant=variant, backend=backend,
        source=src, aux=extras or None, scalars=scals,
        valid_lo=lo, valid_hi=hi)


def _sweep(xs, specs, *, bx, bts, variant, backend, idx, n, S, extent,
           overlap, axis_name, extras, scals, ax=0, halos=None,
           send_depth=None):
    """One blocked sweep (``bts`` fused steps of the ``specs`` group)
    on this device's shard.

    ``extras``: list of ``(name, from_above, from_below, shard)`` for
    every operand the group reads — step-constant operands arrive with
    halos pre-exchanged at max depth, evolving-field operands with
    halos the caller exchanged just before this dispatch (``slabs``
    below only takes the innermost ``h`` slices, so any depth >= h
    works). ``scals``: per-spec tuple of this sweep's ``(bts,
    n_scalars)`` slices (or ``(B, bts, n_scalars)`` per-problem rows),
    or None. ``ax``: the sharded axis within each array — 0 for plain
    grids, 1 for ``[B, *grid]`` batches (the validity interval the
    engine receives is about the *grid* leading axis either way, which
    is exactly axis ``ax``).

    ``halos``: this sweep's ``(from_above, from_below)`` at depth
    ``h = bts * sum(radius)``, already exchanged by the caller; when
    None the sweep issues its own ``exchange_halos`` (the program
    runner's mode). ``send_depth``: fused halo packing — when not
    None, also return the ``send_depth``-deep top/bottom strips of the
    *updated* shard, carved directly from the engine outputs that
    produced the edges (no slice off the re-assembled shard), so the
    caller can ``exchange_packed`` them for the next sweep. Requires
    ``send_depth <= h`` (the schedule is non-increasing, so the next
    sweep's depth always qualifies). Returns ``out`` when
    ``send_depth`` is None, else ``(out, (send_top, send_bot))``.
    """
    h = bts * sum(sp.radius for sp in specs)
    row0 = idx * S                    # global coordinate of shard row 0

    def slabs(lo_sl, hi_sl):
        """Operand slabs spanning [lo_sl, hi_sl) in halo+shard+halo
        coordinates (0 = h rows above the shard top)."""
        out = {}
        for name, ea, eb, es in extras:
            full = jnp.concatenate(
                [_sl(ea, -h, None, ax), es, _sl(eb, None, h, ax)], axis=ax)
            out[name] = _sl(full, lo_sl, hi_sl, ax)
        return out

    if not (overlap and S >= 2 * h):
        fa, fb = (exchange_halos(xs, h, n, axis_name, ax)
                  if halos is None else halos)
        slab = jnp.concatenate([fa, xs, fb], axis=ax)
        lo = jnp.clip(h - row0, 0, S + 2 * h)
        hi = jnp.clip(extent - row0 + h, 0, S + 2 * h)
        out = _engine_call(slab, specs, bx, bts, variant, backend,
                           slabs(0, S + 2 * h), scals, lo, hi)
        if send_depth is None:
            return _sl(out, h, h + S, ax)
        # Slab output rows [h, h+S) are the owned shard; its top/bottom
        # send_depth rows come straight off the engine output.
        return _sl(out, h, h + S, ax), (
            _sl(out, h, h + send_depth, ax),
            _sl(out, h + S - send_depth, h + S, ax))

    # Overlapped schedule: kick off the halo ppermutes, compute the
    # interior (independent of them), then finish the two edge strips.
    fa, fb = (exchange_halos(xs, h, n, axis_name, ax)
              if halos is None else halos)
    if S > 2 * h:      # interior rows [h, S-h) need no halo at all
        hi_own = jnp.clip(extent - row0, 0, S)
        interior = [_sl(_engine_call(
            xs, specs, bx, bts, variant, backend,
            {name: es for name, _, _, es in extras},
            scals, 0, hi_own), h, S - h, ax)]
    else:              # S == 2h: the two edge strips cover the shard
        interior = []
    tslab = jnp.concatenate([fa, _sl(xs, None, 2 * h, ax)],
                            axis=ax)                      # rows [-h, 2h)
    bslab = jnp.concatenate([_sl(xs, -2 * h, None, ax), fb],
                            axis=ax)                      # rows [S-2h, S+h)
    lo_t = jnp.clip(h - row0, 0, 3 * h)
    hi_t = jnp.clip(extent - row0 + h, 0, 3 * h)
    top_out = _engine_call(tslab, specs, bx, bts, variant, backend,
                           slabs(0, 3 * h), scals, lo_t, hi_t)
    top = _sl(top_out, h, 2 * h, ax)
    lo_b = jnp.clip(2 * h - row0 - S, 0, 3 * h)
    hi_b = jnp.clip(extent - row0 - S + 2 * h, 0, 3 * h)
    bot_out = _engine_call(bslab, specs, bx, bts, variant, backend,
                           slabs(S - h, S + 2 * h), scals, lo_b, hi_b)
    bot = _sl(bot_out, h, 2 * h, ax)
    out = jnp.concatenate([top] + interior + [bot], axis=ax)
    if send_depth is None:
        return out
    # The top edge dispatch's output rows [h, 2h) are owned shard rows
    # [0, h), so the next sweep's send_top is its rows [h, h+d); the
    # bottom dispatch's rows [h, 2h) are shard rows [S-h, S), so
    # send_bot is its rows [2h-d, 2h). Both ppermutes can therefore
    # start the moment the edge strips finish — before the shard is
    # even re-assembled — and hide under the next interior compute.
    return out, (_sl(top_out, h, h + send_depth, ax),
                 _sl(bot_out, 2 * h - send_depth, 2 * h, ax))


def stencil_run_sharded(x: jax.Array, spec: StencilSpec, n_steps: int, *,
                        n_devices: int, bx: int = 256, bt: int = 1,
                        variant: str = "revolving", backend: str,
                        source: jax.Array | None = None, aux=None,
                        scalars: jax.Array | None = None, devices=None,
                        overlap: bool = True,
                        axis_name: str = AXIS) -> jax.Array:
    """``n_steps`` stencil steps with the grid sharded over ``n_devices``.

    Splits the leading axis over a 1D device mesh, exchanges depth-
    ``r*bt`` halos once per ``bt``-step block, runs the single-device
    engine on each ``halo+shard+halo`` slab and crops. Numerically
    identical to ``kernels.ops.stencil_run`` on one device for any
    ``bt`` (``bt`` is clamped so the halo fits one shard). ``source``
    and every ``aux`` operand are step-constant, so their halos are
    exchanged once per call, not once per sweep; ``scalars`` (``
    (n_steps, n_scalars)``, custom updates) are replicated and sliced
    per sweep.

    A ``[B, *grid]`` batch prefers **batch-axis sharding** (whole
    problems per device, no halo traffic) whenever ``B % n_devices ==
    0`` and falls back to sharding the grid's leading axis — array
    axis 1 — otherwise (module docstring; ``shard_strategy`` names the
    choice). Per-problem scalars ``(B, n_steps, k)`` shard with the
    batch in the first case and replicate in the second.
    """
    if x.ndim not in (spec.dims, spec.dims + 1):
        raise ValueError(f"grid rank {x.ndim} != spec.dims {spec.dims} "
                         f"(or {spec.dims + 1} with a leading batch axis)")
    if n_devices < 1:
        raise ValueError(f"n_devices must be >= 1, got {n_devices}")
    batched = x.ndim == spec.dims + 1
    strategy = shard_strategy(x.shape, spec, n_devices)
    ga = 1 if batched else 0          # the grid's leading axis
    extent = x.shape[ga]
    n = n_devices
    if strategy == "batch":
        S = extent                    # every device sees whole problems
    else:
        S = shard_extent(extent, n)
        if spec.radius > S:
            # Even bt=1 needs an r-deep halo; the boundary slices a
            # shard sends its neighbors cannot be deeper than the shard
            # itself. Silently continuing would mis-assemble the slabs,
            # so refuse.
            raise ValueError(
                f"stencil radius {spec.radius} exceeds the {S}-deep "
                f"shard a {n}-way split of the {extent}-deep leading "
                f"axis leaves per device; reduce n_devices "
                f"(<= {extent // spec.radius})")
        bt = min(bt, max_bt(spec, extent, n))
    bt = max(1, min(bt, n_steps or 1))
    h_max = spec.halo(bt)
    full, rem = divmod(n_steps, bt)
    schedule = [bt] * full + ([rem] if rem else [])

    # Mirror engine.stencil_call's operand validation: a typo'd or
    # undeclared aux name must fail loudly here too, not silently drop
    # an operand from the sharded computation.
    aux = dict(aux) if aux else {}
    declared = [op.name for op in spec.aux]
    unknown = [nm for nm in aux if nm not in declared]
    if unknown:
        raise ValueError(f"unknown aux operands {unknown} for spec "
                         f"{spec.name!r} (declared: {declared})")
    for nm, arr in aux.items():
        if arr.shape != x.shape:
            raise ValueError(f"aux operand {nm!r} shape {arr.shape} != "
                             f"grid shape {x.shape}")
    extra_names = []
    extra_arrays = []
    if source is not None:
        extra_names.append(_LEGACY_SRC)
        extra_arrays.append(source)
    for op in spec.aux:
        if op.name not in aux:
            raise ValueError(f"spec {spec.name!r} requires aux operands "
                             f"{declared}")
        extra_names.append(op.name)
        extra_arrays.append(aux[op.name])
    extra_names = tuple(extra_names)

    if scalars is not None:
        scalars = jnp.asarray(scalars, jnp.float32)
        if batched and scalars.ndim == 3:
            scalars = scalars.reshape(x.shape[0], n_steps, -1)
        else:
            scalars = scalars.reshape(n_steps, -1)
    per_problem_scal = scalars is not None and scalars.ndim == 3

    if strategy == "batch":
        pad = None                    # B % n == 0: nothing to pad
        xp = x
    else:
        pad = [(0, 0)] * x.ndim
        pad[ga] = (0, S * n - extent)
        xp = jnp.pad(x, pad)
    args = (xp,) + tuple(a.astype(x.dtype) if pad is None
                         else jnp.pad(a.astype(x.dtype), pad)
                         for a in extra_arrays)
    if scalars is not None:
        args += (scalars,)

    mesh = _device_mesh(n, devices)
    runner = _sharded_runner(
        spec, mesh, key=(spec, xp.shape, str(xp.dtype), bx,
                         tuple(schedule), variant, backend, n, S,
                         extent, overlap, axis_name, extra_names,
                         scalars is not None,
                         None if scalars is None else scalars.shape,
                         strategy, ga,
                         tuple(int(d.id) for d in np.asarray(
                             mesh.devices).flat)),
        h_max=h_max, schedule=schedule, bx=bx, variant=variant,
        backend=backend, n=n, S=S, extent=extent, overlap=overlap,
        axis_name=axis_name, extra_names=extra_names,
        has_scalars=scalars is not None,
        per_problem_scal=per_problem_scal, strategy=strategy, ga=ga)
    out = runner(*args)
    if strategy == "batch":
        return out
    return _sl(out, None, extent, ga)


def _placed(fn, mesh: Mesh, in_specs):
    """``fn`` with every argument first placed on ``mesh`` as its
    shard_map ``in_specs`` say: jit does not move an array that is
    committed to one device onto the mesh by itself."""
    shardings = tuple(NamedSharding(mesh, s) for s in in_specs)

    def call(*args):
        return fn(*(jax.device_put(a, s) for a, s in zip(args, shardings)))
    return call


# jitted shard_map programs memoized per static configuration: without
# this, every call (each autotuner timing repeat, every step block of a
# caller's loop) would rebuild the closure and retrace from scratch.
_RUNNERS: dict = {}


def _sharded_runner(spec, mesh, *, key, h_max, schedule, bx, variant,
                    backend, n, S, extent, overlap, axis_name,
                    extra_names, has_scalars, per_problem_scal=False,
                    strategy="grid", ga=0):
    fn = _RUNNERS.get(key)
    if fn is not None:
        return fn
    n_extras = len(extra_names)
    # Shared/per-problem scalar slicing must match the single-device
    # path exactly, so reuse its helper rather than re-deriving it.
    from repro.kernels.ops import _tslice as _tsl

    if strategy == "batch":
        # Whole problems per device: run the single-device *batched*
        # engine on this device's B/n problems. No halos, no
        # ppermutes, no redundant slab compute — the default validity
        # interval already covers the full (unsharded) grid.
        def body(xs, *rest):
            scal = rest[n_extras] if has_scalars else None
            extras_d = dict(zip(extra_names, rest[:n_extras]))
            off = 0
            for bts in schedule:
                xs = _engine_call(
                    xs, (spec,), bx, bts, variant, backend, extras_d,
                    (_tsl(scal, off, off + bts),) if scal is not None
                    else None, None, None)
                off += bts
            return xs

        in_specs = (P(axis_name),) * (1 + n_extras)
        if has_scalars:
            # Per-problem scalar rows shard with their problems;
            # shared scalars replicate.
            in_specs += (P(axis_name) if per_problem_scal else P(),)
        out_spec = P(axis_name)
    else:
        def body(xs, *rest):
            idx = jax.lax.axis_index(axis_name)
            shards = rest[:n_extras]
            scal = rest[n_extras] if has_scalars else None
            extras = []
            for name, es in zip(extra_names, shards):
                ea, eb = exchange_halos(es, h_max, n, axis_name, ga)
                extras.append((name, ea, eb, es))
            # Fused halo packing: only the first exchange slices the
            # input shard. Every later sweep receives strips carved by
            # the previous sweep from its own engine outputs
            # (send_depth), valid because the schedule's depths are
            # non-increasing (the remainder sweep comes last).
            hs = [bts * spec.radius for bts in schedule]
            fa, fb = exchange_halos(xs, hs[0], n, axis_name, ga)
            off = 0
            for t, bts in enumerate(schedule):
                h_next = hs[t + 1] if t + 1 < len(schedule) else 0
                xs, (st, sb) = _sweep(
                    xs, (spec,), bx=bx, bts=bts, variant=variant,
                    backend=backend, idx=idx, n=n, S=S,
                    extent=extent, overlap=overlap,
                    axis_name=axis_name, extras=extras,
                    scals=((_tsl(scal, off, off + bts),)
                           if scal is not None else None), ax=ga,
                    halos=(fa, fb), send_depth=h_next)
                if h_next:
                    fa, fb = exchange_packed(st, sb, n, axis_name)
                off += bts
            return xs

        # The sharded axis is the grid's leading axis: array axis ga
        # (batched grids keep their whole batch on every device).
        shard_p = P(*([None] * ga + [axis_name]))
        in_specs = (shard_p,) * (1 + n_extras)
        if has_scalars:
            in_specs += (P(),)
        out_spec = shard_p

    fn = _placed(jax.jit(compat.shard_map(
        body, mesh=mesh, in_specs=in_specs,
        out_specs=out_spec, check_vma=False)), mesh, in_specs)
    _RUNNERS[key] = fn
    return fn


# ---------------------------------------------------------------------------
# Program runner: a StencilProgram sharded over devices. Fuse groups
# dispatch exactly as in kernels.ops.stencil_program_run; the new
# wrinkle is that a group may read *evolving* fields written by earlier
# groups, whose halos must be re-exchanged before every dispatch (the
# pre-exchange-once trick only applies to step-constant inputs).
# ---------------------------------------------------------------------------

def stencil_program_run_sharded(fields: dict, program, n_steps: int, *,
                                n_devices: int, bx: int = 256, bt: int = 1,
                                variant: str = "revolving",
                                backend: str, inputs=None,
                                scalars=None, devices=None,
                                overlap: bool = True, fuse: bool = True,
                                axis_name: str = AXIS) -> dict:
    """``n_steps`` program steps with every field sharded over devices.

    The program analog of ``stencil_run_sharded``: per program step,
    every fuse group runs as one slab dispatch (``fuse=False`` forces
    one dispatch per sweep). A fully-fused program temporally blocks
    ``bt`` steps per dispatch with halo depth ``bt * sum(radii)``;
    multi-group programs are forced to ``bt=1`` because their sweeps
    must alternate every step. Step-constant ``inputs`` have their
    halos exchanged once per call at max depth; evolving fields are
    exchanged per dispatch at the current depth, right after the group
    that last wrote them. ``scalars``: dict mapping a sweep name to its
    ``(n_steps, n_scalars)`` values (per-problem ``(B, n_steps, k)``
    over a batch-sharded batch).

    Returns the fields dict. Unbatched grids shard the leading grid
    axis; a ``[B, *grid]`` batch shards whole problems when ``B %
    n_devices == 0`` and otherwise falls back — with a warning — to
    grid sharding of the grid's leading axis (array axis 1, the whole
    batch riding on every device; per-problem scalars replicate).
    """
    from repro.core.stencil import StencilProgram
    from repro.kernels.ops import _tslice as _tsl
    if not isinstance(program, StencilProgram):
        raise TypeError(f"expected a StencilProgram, got {type(program)}")
    if n_devices < 1:
        raise ValueError(f"n_devices must be >= 1, got {n_devices}")
    fields = dict(fields)
    missing = [f for f in program.fields if f not in fields]
    if missing:
        raise ValueError(f"program {program.name!r} evolves fields "
                         f"{missing} that were not provided")
    inputs = dict(inputs) if inputs else {}
    need = [nm for nm in program.input_names if nm not in inputs]
    if need:
        raise ValueError(f"program {program.name!r} requires inputs "
                         f"{need}")
    dims = program.dims
    field_names = program.fields
    input_names = program.input_names
    primary = fields[field_names[0]]
    if primary.ndim not in (dims, dims + 1):
        raise ValueError(f"grid rank {primary.ndim} != program dims "
                         f"{dims} (or {dims + 1} with a leading batch "
                         f"axis)")
    for nm, arr in list(fields.items()) + list(inputs.items()):
        if arr.shape != primary.shape:
            raise ValueError(f"operand {nm!r} shape {arr.shape} != "
                             f"primary field shape {primary.shape}")
    batched = primary.ndim == dims + 1
    n = n_devices

    groups = (program.fuse_groups() if fuse
              else tuple((s,) for s in program.sweeps))
    if len(groups) > 1:
        bt = 1                      # groups must alternate every step
    group_meta = []
    for g in groups:
        aux_names = tuple(dict.fromkeys(
            op.name for s in g for op in s.spec.aux))
        scal_keys = tuple(s.name if s.spec.n_scalars else None for s in g)
        group_meta.append((tuple(s.spec for s in g), g[0].field,
                           aux_names, scal_keys,
                           sum(s.spec.radius for s in g)))
    group_meta = tuple(group_meta)
    max_gr = max(m[4] for m in group_meta)

    ga = 0
    if batched and primary.shape[0] % n == 0:
        strategy, extent, S = "batch", primary.shape[0], primary.shape[0]
    else:
        if batched:
            # Grid sharding is legal for any B (the whole batch rides
            # on every device, array axis 1 is split) — it just trades
            # zero halo traffic for some, so say so instead of erroring.
            warnings.warn(
                f"batched sharded program run with B="
                f"{primary.shape[0]} not divisible by n_devices={n}: "
                f"falling back from batch-axis to grid sharding (array "
                f"axis 1; same results, halo traffic instead of none). "
                f"Pad the batch to a multiple of {n} to restore "
                f"batch-axis sharding.", stacklevel=2)
        strategy = "grid"
        ga = 1 if batched else 0
        extent = primary.shape[ga]
        S = shard_extent(extent, n)
        if max_gr > S:
            raise ValueError(
                f"fused group radius {max_gr} exceeds the {S}-deep "
                f"shard a {n}-way split of the {extent}-deep leading "
                f"axis leaves per device; reduce n_devices "
                f"(<= {extent // max_gr})")
        bt = min(bt, max(1, S // max_gr))
    bt = max(1, min(bt, n_steps or 1))
    h_max = bt * max_gr
    full, rem = divmod(n_steps, bt)
    schedule = tuple([bt] * full + ([rem] if rem else []))

    scalars = dict(scalars) if scalars else {}
    scal_names = tuple(s.name for s in program.sweeps if s.spec.n_scalars)
    unknown = [k for k in scalars if k not in scal_names]
    if unknown:
        raise ValueError(f"scalars given for sweeps {unknown} that take "
                         f"no scalars (expected: {list(scal_names)})")
    need = [k for k in scal_names if k not in scalars]
    if need:
        raise ValueError(f"program {program.name!r} requires scalars "
                         f"for sweeps {need}")
    scal_arrays = []
    per_scal = []
    for k in scal_names:
        a = jnp.asarray(scalars[k], jnp.float32)
        if a.ndim == 3:
            # Per-problem values: shard with their problems under
            # batch-axis sharding, replicate whole under grid sharding
            # (every device holds the full batch there).
            a = a.reshape(primary.shape[0], n_steps, -1)
            per_scal.append(strategy == "batch")
        else:
            a = a.reshape(n_steps, -1)
            per_scal.append(False)
        scal_arrays.append(a)

    if strategy == "grid" and S * n != extent:
        pad = [(0, 0)] * primary.ndim
        pad[ga] = (0, S * n - extent)
        padf = lambda a: jnp.pad(a, pad)
    else:
        padf = lambda a: a
    dt = primary.dtype
    args = tuple(padf(fields[f].astype(dt)) for f in field_names)
    args += tuple(padf(inputs[nm].astype(dt)) for nm in input_names)
    args += tuple(scal_arrays)

    mesh = _device_mesh(n, devices)
    key = ("program", program, tuple(a.shape for a in args),
           str(dt), bx, schedule, variant, backend, n, S, extent,
           overlap, axis_name, fuse, strategy, ga, tuple(per_scal),
           tuple(int(d.id) for d in np.asarray(mesh.devices).flat))
    runner = _program_sharded_runner(
        program, mesh, key=key, group_meta=group_meta, h_max=h_max,
        schedule=schedule, bx=bx, variant=variant, backend=backend,
        n=n, S=S, extent=extent, overlap=overlap, axis_name=axis_name,
        field_names=field_names, input_names=input_names,
        scal_names=scal_names, per_scal=tuple(per_scal),
        strategy=strategy, ga=ga)
    outs = runner(*args)
    if strategy == "grid" and S * n != extent:
        outs = tuple(_sl(o, None, extent, ga) for o in outs)
    return dict(zip(field_names, outs))


def _program_sharded_runner(program, mesh, *, key, group_meta, h_max,
                            schedule, bx, variant, backend, n, S,
                            extent, overlap, axis_name, field_names,
                            input_names, scal_names, per_scal, strategy,
                            ga=0):
    fn = _RUNNERS.get(key)
    if fn is not None:
        return fn
    from repro.kernels.ops import _tslice as _tsl
    nf, ni = len(field_names), len(input_names)

    def group_scals(scal_d, scal_keys, off, bts):
        if not any(k is not None for k in scal_keys):
            return None
        return tuple(_tsl(scal_d[k], off, off + bts)
                     if k is not None else None for k in scal_keys)

    if strategy == "batch":
        # Whole problems per device: the single-device batched engine
        # needs no halos, so aux operands pass through unchanged.
        def body(*arrs):
            fs = dict(zip(field_names, arrs[:nf]))
            ins = dict(zip(input_names, arrs[nf:nf + ni]))
            scal_d = dict(zip(scal_names, arrs[nf + ni:]))
            off = 0
            for bts in schedule:
                for specs, fld, aux_names, scal_keys, _ in group_meta:
                    extras = {nm: (fs[nm] if nm in fs else ins[nm])
                              for nm in aux_names}
                    fs[fld] = _engine_call(
                        fs[fld], specs, bx, bts, variant, backend,
                        extras, group_scals(scal_d, scal_keys, off, bts),
                        None, None)
                off += bts
            return tuple(fs[f] for f in field_names)

        in_specs = (P(axis_name),) * (nf + ni)
        in_specs += tuple(P(axis_name) if p else P() for p in per_scal)
        out_specs = (P(axis_name),) * nf
    else:
        def body(*arrs):
            idx = jax.lax.axis_index(axis_name)
            fs = dict(zip(field_names, arrs[:nf]))
            ins = dict(zip(input_names, arrs[nf:nf + ni]))
            scal_d = dict(zip(scal_names, arrs[nf + ni:]))
            ins_ex = {}
            for nm in input_names:     # step-constant: exchange once
                ea, eb = exchange_halos(ins[nm], h_max, n, axis_name, ga)
                ins_ex[nm] = (ea, eb, ins[nm])
            off = 0
            # Each dispatch still exchanges at its own depth (halos=
            # None): consecutive groups update *different* fields, so
            # packed strips from group k's output are not the strips
            # group k+1 needs. Threading packs across same-field
            # dispatches of successive sweeps is future work.
            for bts in schedule:
                for specs, fld, aux_names, scal_keys, g_r in group_meta:
                    h = bts * g_r
                    extras = []
                    for nm in aux_names:
                        if nm in fs:   # evolving: exchange fresh value
                            ea, eb = exchange_halos(fs[nm], h, n,
                                                    axis_name, ga)
                            extras.append((nm, ea, eb, fs[nm]))
                        else:
                            extras.append((nm,) + ins_ex[nm])
                    fs[fld] = _sweep(
                        fs[fld], specs, bx=bx, bts=bts, variant=variant,
                        backend=backend, idx=idx, n=n, S=S,
                        extent=extent, overlap=overlap,
                        axis_name=axis_name, extras=extras,
                        scals=group_scals(scal_d, scal_keys, off, bts),
                        ax=ga)
                off += bts
            return tuple(fs[f] for f in field_names)

        # The sharded axis is the grid's leading axis: array axis ga
        # (a batched grid-sharded fallback keeps its whole batch on
        # every device).
        shard_p = P(*([None] * ga + [axis_name]))
        in_specs = (shard_p,) * (nf + ni)
        in_specs += (P(),) * len(scal_names)
        out_specs = (shard_p,) * nf

    fn = _placed(jax.jit(compat.shard_map(
        body, mesh=mesh, in_specs=in_specs,
        out_specs=out_specs, check_vma=False)), mesh, in_specs)
    _RUNNERS[key] = fn
    return fn
