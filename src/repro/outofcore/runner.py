"""Host-streaming tiled stencil execution (out-of-core subsystem).

The thesis's combined spatial+temporal blocking exists so input size
never restricts the accelerator: tiles stream from external DRAM
through on-chip block RAM with overlapped halos (§5.3). Every path in
this repo so far still required the full grid (plus halos) to fit in
device HBM; this module removes that restriction by replaying the same
design one memory level up — **host memory plays the FPGA's external
DRAM, device HBM plays the block RAM**:

    host grid (numpy, arbitrarily large)
      │  leading-axis tile i, with ghost = r*bt slices per side
      ▼
    ┌──────────── device slab: [ghost │ tile │ ghost] ────────────┐
    │ engine.stencil_call(bt fused steps — a self-contained        │
    │ in-core problem: slabs are clipped to the grid, so the       │
    │ default validity interval / boundary handling apply as-is)   │
    └──────────────────────────┬──────────────────────────────────┘
                               │ crop the center ``tile`` slices
      host output grid  ◀──────┘  (double-buffered readback)

Exactness (the deep-halo cone argument, re-used): after ``s`` of the
``bt`` fused steps, a slab slice is exact iff its dependency cone —
``s`` steps x radius ``r`` — stayed inside the slab; the ghost depth
``r*bt`` is exactly the cone of the full block, so the cropped center
is exact. Slabs are **clipped to the grid, never padded**: each slab
is a self-contained smaller in-core problem whose array edges either
*coincide* with true grid edges (first/last tile — the engine's
boundary handling applies there, exactly as in-core, so the boundary
mode acts at true grid edges only) or lie a full ghost depth away
from the owned center (interior seams — whatever the boundary mode
fabricates at a seam decays by ``r`` slices per fused step and never
reaches the crop). Because every slab call is the *same jit graph*
the in-core path compiles — the engine's leading-axis validity
interval at its default full extent, with identical trace-time
constants — results are **bitwise equal** to ``ops.stencil_run`` for
any tile size, ``bt``, radius, dimensionality and boundary mode;
``tests/test_outofcore.py`` asserts it and the benchmark's ``--smoke``
gate re-checks it. (The halo runner instead *shifts* the validity
interval over zero-padded ghosts — semantically equivalent, but a
shifted interval compiles top-edge clamp taps through different XLA
ops, which measures as 1-ulp drift: fine under the sharded runner's
float-tolerance contract, fatal to the bitwise one here.)

Unlike the sharded runner there is no ``ghost <= tile`` constraint:
slabs are sliced straight from the host-resident grid, so the ghost
may be arbitrarily deeper than the tile it wraps (tiny tiles under
tiny budgets stay exact, just slow).

Overlap: slabs are uploaded with ``jax.device_put`` and dispatched
asynchronously; up to ``depth`` tiles stay in flight before the oldest
result is materialized back to the host, so tile ``i+1``'s upload and
compute run under tile ``i``'s readback (double buffering at
``depth=2``). On real hardware the slab buffer is donated to the
engine call so the device reuses it for the output; under
``interpret`` donation is skipped (CPU donation just warns and
copies).

Streaming semantics match the halo runner exactly: every aux operand
(and the legacy ``source``) slices per tile alongside the grid with
the same ghost depth; per-step ``scalars`` slice per sweep (shared
``(n_steps, k)``) or per problem (``(B, n_steps, k)``); a ``[B,
*grid]`` batch tiles the *grid's* leading axis (array axis 1) with the
whole batch riding on every slab.

``n_devices > 1`` composes this runner with the deep-halo partition
of ``distributed/halo.py``: each device owns a contiguous slab of the
leading axis (``shard_extent`` — the same partition rule the in-core
sharded runner uses) held in a per-device **host** buffer, and streams
that slab's tiles through the identical clipped-slab machinery above,
interleaved round-robin so all devices compute concurrently with
``depth`` tiles in flight per device. Halos are exchanged at **tile
granularity**: each tile's clipped slab is assembled by
``distributed.halo.gather_slab`` from whichever neighbors' host
buffers own its ``r*bt``-deep ghost rows (the host-resident analog of
the sharded runner's packed ppermute — and since ghosts come from
host buffers, not a neighbor's device shard, there is still no
``ghost <= shard`` constraint). Every slab is clipped, never padded,
so each dispatch is the *same jit graph* the single-device path
compiles — which is why the composed path inherits the bitwise
contract unchanged (``tests/test_outofcore_sharded.py`` pins it under
a forced 4-device host platform). Grid size is then bounded only by
aggregate host RAM; see ``docs/outofcore.md``.
"""
from __future__ import annotations

import time
from collections import OrderedDict, deque
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.blocking import (TilePlan, incore_resident_bytes,
                                 plan_tiles, shard_extent,
                                 shard_resident_bytes)
from repro.core.stencil import StencilSpec
from repro.kernels import engine
from repro.kernels.ops import _tslice


def route_decision(spec: StencilSpec, grid_shape, itemsize: int,
                   hbm_budget: Optional[int], batch: int = 1,
                   extra_streams: int = 0,
                   n_devices: int = 1, bt: int = 1) -> Tuple[bool, int]:
    """(route out-of-core?, effective budget) — the ONE predicate
    ``ops.stencil_run``, ``ops.stencil_program_run``, ``autotune.plan``
    and the serving dispatcher all consult. Keeping it here (rather
    than each caller re-deriving the default budget + threshold) means
    they can never disagree — a jitted in-core dispatcher whose traced
    ``stencil_run`` decides "out-of-core" would crash converting a
    tracer to numpy.

    ``n_devices``: the budget is *per device*, and a sharded run holds
    ~1/n of the working set per device, so the comparison is against
    ``blocking.shard_resident_bytes``: one shard's owned slices *plus
    the ``r*bt``-deep ghost slices it carries per side* — the ghost
    charge is what keeps the threshold honest near the boundary, where
    the bare division underestimates per-device residency by
    ``2*r*bt/S`` and would keep an in-core sharded path that OOMs. A
    20 GB grid sharded 4 ways still keeps its in-core deep-halo path
    on 16 GiB devices (ghosts are a few percent); only when even a
    ghost-charged shard overflows does the run stream out-of-core —
    now composed with the mesh rather than refused
    (``stencil_run_outofcore(n_devices > 1)``).
    """
    if hbm_budget is None:
        from repro.core.perf_model import V5E
        hbm_budget = V5E.hbm_bytes
    per_device = shard_resident_bytes(
        spec, tuple(grid_shape), itemsize, n_devices=max(n_devices, 1),
        bt=bt, batch=batch, extra_streams=extra_streams)
    return per_device > hbm_budget, hbm_budget


def exceeds_budget(spec: StencilSpec, grid_shape, itemsize: int,
                   hbm_budget: int, batch: int = 1,
                   extra_streams: int = 0, n_devices: int = 1,
                   bt: int = 1) -> bool:
    """Whether an in-core run of this problem (sharded when
    ``n_devices > 1``, ghost-charged per shard) would overflow the HBM
    budget — a thin wrapper over ``route_decision`` so there is
    exactly one definition of the threshold."""
    return route_decision(spec, grid_shape, itemsize, hbm_budget,
                          batch, extra_streams, n_devices, bt)[0]


# Jitted slab dispatchers, LRU-bounded: one compilation serves every
# tile of every sweep with the same (bts, slab shape) — the key holds
# the slab-determining dims only (leading extent excluded), so grids
# differing only in total height share entries. The bound keeps a
# long-lived serving process (many distinct specs/shapes) from
# accumulating compiled executables forever.
_DISPATCHERS: OrderedDict = OrderedDict()
_DISPATCHER_CAP = 64


def _dispatcher(key, spec, bx, bts, variant, backend, aux_names,
                donate):
    fn = _DISPATCHERS.get(key)
    if fn is not None:
        _DISPATCHERS.move_to_end(key)
        return fn

    def call(slab, src, aux_list, scal):
        aux = dict(zip(aux_names, aux_list)) or None
        return engine.stencil_call(slab, spec, bx=bx, bt=bts,
                                   variant=variant, backend=backend,
                                   source=src, aux=aux, scalars=scal)

    # Donate the input slab so the device reuses its HBM for the
    # output — halving the steady-state footprint on real hardware.
    # Interpret/CPU donation is a no-op that warns, so skip it there.
    fn = jax.jit(call, donate_argnums=(0,) if donate else ())
    _DISPATCHERS[key] = fn
    if len(_DISPATCHERS) > _DISPATCHER_CAP:
        _DISPATCHERS.popitem(last=False)
    return fn


def _kernel_tile(spec, slab_shape, bx, bt, backend, tile) -> int:
    """The persistent kernel's in-kernel tile: the host tile, cut down
    until its DMA slabs fit the device's VMEM budget."""
    from repro.core.blocking import persistent_tile
    from repro.core.perf_model import device_spec_for
    return persistent_tile(spec, tuple(slab_shape), bx=bx, bt=bt,
                           vmem_budget=device_spec_for(backend).vmem_bytes,
                           limit=tile)


def _slab(a: np.ndarray, start: int, end: int, ax: int) -> np.ndarray:
    """``a[start:end]`` along ``ax`` — slabs are *clipped* to the grid,
    never padded (see the module docstring's exactness note)."""
    idx = [slice(None)] * a.ndim
    idx[ax] = slice(start, end)
    return a[tuple(idx)]


def resolve_tile(x_shape, spec: StencilSpec, *, bx: int, bt: int,
                 itemsize: int, hbm_budget: int, depth: int = 2,
                 extra_streams: int = 0) -> Optional[TilePlan]:
    """The TilePlan ``stencil_run_outofcore`` will use for this problem
    (None when it fits in-core). Splits a ``[B, *grid]`` shape into
    (batch, grid) before sizing."""
    shape = tuple(int(s) for s in x_shape)
    batch = shape[0] if len(shape) == spec.dims + 1 else 1
    grid = shape[1:] if len(shape) == spec.dims + 1 else shape
    return plan_tiles(spec, grid, bx=bx, bt=bt, hbm_budget=hbm_budget,
                      itemsize=itemsize, batch=batch, depth=depth,
                      extra_streams=extra_streams)


def stencil_run_outofcore(x, spec: StencilSpec, n_steps: int, *,
                          bx: int, bt: int, backend: str,
                          variant: str = "revolving",
                          tile: int | None = None,
                          hbm_budget: int | None = None,
                          source=None, aux=None, scalars=None,
                          depth: int = 2, pipeline: str = "host",
                          n_devices: int = 1, devices=None,
                          metrics: dict | None = None) -> np.ndarray:
    """``n_steps`` stencil steps with the grid resident on the *host*.

    The grid (and every operand) lives in host memory; the device only
    ever holds ``depth`` slabs of ``ghost + tile + ghost`` leading
    slices at a time. ``tile`` pins the tile extent directly;
    otherwise it is sized against ``hbm_budget`` via
    ``core.blocking.plan_tiles`` (largest tile whose double-buffered
    working set fits). Returns a **host** (numpy) array — the result
    may not fit on the device either.

    ``pipeline`` selects where the tile streaming happens (see
    docs/pipelining.md):

    * ``"host"`` (default) — the Python loop above: one engine dispatch
      per tile, ``jax.device_put`` double buffering at ``depth``.
    * ``"kernel"`` — tiles are grouped into device-sized *chunks* and
      each chunk runs as ONE persistent ``pallas_call``
      (``engine.stencil_call_persistent``) that DMAs tile slabs
      HBM→VMEM inside the kernel, double-buffered, so tile ``i+1``'s
      load overlaps tile ``i``'s fused-step compute without a Python
      round-trip. Falls back to ``"host"`` (with the reason recorded
      in ``metrics``) when ``engine.kernel_pipeline_supported`` says
      the backend or operand form cannot take it.

    ``n_devices > 1`` composes this runner with the deep-halo
    partition (module docstring): each device owns a contiguous
    ``shard_extent`` slab of the leading axis in its own host buffer
    and streams that slab's tiles — round-robin across devices, so
    they compute concurrently with ``depth`` tiles in flight each —
    with every tile slab assembled at tile granularity by
    ``distributed.halo.gather_slab`` (neighbor host buffers supply the
    ``r*bt``-deep ghost rows). Same bitwise contract, either pipeline
    mode; ``devices`` pins the device list (default ``jax.devices()``).

    ``metrics``, when a dict is passed, is filled in place with a
    per-run breakdown: the pipeline actually used (+ requested form and
    fallback reason), tile/chunk geometry, dispatch counts, ``wall_s``,
    and — at ``depth <= 1``, where phases are serialized so the split
    is attributable — ``upload_s`` / ``compute_s`` / ``readback_s``
    (``None`` at higher depths: overlap makes per-phase walls lie).
    Always carries ``n_devices`` / ``slab_extents`` /
    ``halo_rows_exchanged`` / ``halo_bytes_exchanged`` (the live
    device count, per-device owned extents, and tile-granular
    halo-exchange volume — zeros and ``[extent]`` on one device).

    Bitwise-equal to ``ops.stencil_run(x, spec, n_steps, bx=bx, bt=bt,
    variant=variant)`` for every supported spec **in either pipeline
    mode**; the in-core engine on a forced-small budget is the
    differential oracle in tests.
    """
    engine.check_backend(backend)
    if x.ndim not in (spec.dims, spec.dims + 1):
        raise ValueError(f"grid rank {x.ndim} != spec.dims {spec.dims} "
                         f"(or {spec.dims + 1} with a leading batch axis)")
    if n_steps < 1:
        raise ValueError(f"n_steps must be >= 1, got {n_steps}")
    batched = x.ndim == spec.dims + 1
    ga = 1 if batched else 0            # the grid's leading axis
    # Private host copy: the two buffers below ping-pong between
    # sweeps, so writing into a caller-owned (or device-backed,
    # possibly read-only) array is never safe.
    cur = np.array(x)
    dtype = cur.dtype
    grid_shape = cur.shape[1:] if batched else cur.shape
    extent = grid_shape[0]
    B = cur.shape[0] if batched else 1

    if tile is None:
        if hbm_budget is None:
            raise ValueError("pass tile= or hbm_budget= (nothing to "
                             "size tiles against otherwise)")
        tp = resolve_tile(cur.shape, spec, bx=bx, bt=bt,
                          itemsize=dtype.itemsize,
                          hbm_budget=hbm_budget, depth=depth,
                          extra_streams=int(source is not None))
        tile = extent if tp is None else tp.tile
    if not 1 <= tile <= extent:
        raise ValueError(f"tile must be in [1, {extent}], got {tile}")

    # Operand order mirrors engine.stencil_call: legacy source first
    # (engine pre-sums sources; order is value-irrelevant but keeping
    # one convention makes the dispatcher key stable), then every
    # declared aux operand, validated as loudly as the engine would.
    aux = dict(aux) if aux else {}
    declared = [op.name for op in spec.aux]
    unknown = [nm for nm in aux if nm not in declared]
    if unknown:
        raise ValueError(f"unknown aux operands {unknown} for spec "
                         f"{spec.name!r} (declared: {declared})")
    missing = [nm for nm in declared if nm not in aux]
    if missing:
        raise ValueError(f"spec {spec.name!r} requires aux operands "
                         f"{missing}")
    for nm, arr in aux.items():
        if arr.shape != cur.shape:
            raise ValueError(f"aux operand {nm!r} shape {arr.shape} != "
                             f"grid shape {cur.shape}")
    has_src = source is not None
    src_host = np.asarray(source, dtype) if has_src else None
    aux_names = tuple(declared)
    aux_host = [np.asarray(aux[nm], dtype) for nm in aux_names]

    if scalars is not None:
        scalars = np.asarray(scalars, np.float32)
        if batched and scalars.ndim == 3:
            scalars = scalars.reshape(B, n_steps, -1)
        else:
            scalars = scalars.reshape(n_steps, -1)

    bt = max(1, min(bt, n_steps))
    full, rem = divmod(n_steps, bt)
    schedule = [bt] * full + ([rem] if rem else [])
    donate = backend != "interpret"
    nxt = np.empty_like(cur)
    n_tiles = -(-extent // tile)

    if pipeline not in ("host", "kernel"):
        raise ValueError(f"pipeline must be 'host' or 'kernel', got "
                         f"{pipeline!r}")
    requested = pipeline
    fallback_reason = ""
    if pipeline == "kernel":
        ok, why = engine.kernel_pipeline_supported(
            spec, backend=backend, batched=batched,
            has_source=has_src, has_aux=bool(aux_names),
            has_scalars=scalars is not None)
        if not ok:
            pipeline, fallback_reason = "host", why

    timing = metrics is not None
    # Per-phase walls are only attributable when phases are serialized;
    # at depth > 1 upload/compute/readback deliberately overlap, so
    # only the aggregate wall is reported there.
    phased = timing and depth <= 1
    acc = {"upload_s": 0.0, "compute_s": 0.0, "readback_s": 0.0,
           "n_dispatches": 0, "n_chunks": 0}
    wall0 = time.perf_counter()

    if n_devices > 1:
        return _stream_sharded(
            cur=cur, spec=spec, schedule=schedule, scalars=scalars,
            bx=bx, variant=variant, backend=backend, tile=tile,
            hbm_budget=hbm_budget, src_host=src_host,
            aux_host=aux_host, aux_names=aux_names, has_src=has_src,
            depth=depth, pipeline=pipeline, requested=requested,
            fallback_reason=fallback_reason, n_devices=n_devices,
            devices=devices, ga=ga, extent=extent,
            grid_shape=grid_shape, dtype=dtype, donate=donate,
            timing=timing, phased=phased, acc=acc, wall0=wall0,
            metrics=metrics)

    off = 0
    for bts in schedule:
        g = spec.halo(bts)
        scal = (_tslice(scalars, off, off + bts)
                if scalars is not None else None)
        scal_dev = None if scal is None else jnp.asarray(scal)
        in_flight: deque = deque()

        def drain_one():
            t0, t1, start, out = in_flight.popleft()
            rb0 = time.perf_counter()
            host = np.asarray(out)      # blocks on this tile only
            acc["readback_s"] += time.perf_counter() - rb0
            src = [slice(None)] * host.ndim
            src[ga] = slice(t0 - start, t1 - start)   # owned slices
            dst = [slice(None)] * nxt.ndim
            dst[ga] = slice(t0, t1)
            nxt[tuple(dst)] = host[tuple(src)]

        if pipeline == "kernel":
            # Tiles group into device-sized chunks; each chunk is ONE
            # persistent pallas_call streaming its tiles through VMEM.
            # Sizing: a chunk in flight holds its clipped input slab
            # (~K*tile + 2g slices) plus its owned output (K*tile), and
            # ``depth`` chunks are in flight at once.
            per_slice = (int(np.prod(grid_shape[1:], dtype=np.int64))
                         * dtype.itemsize)
            if hbm_budget is not None:
                slices = hbm_budget // (max(depth, 1) * per_slice)
                K = max(1, int((slices - 2 * g) // (2 * tile)))
            else:
                K = n_tiles
            K = min(K, n_tiles)
            n_chunks = -(-n_tiles // K)
            ktile = _kernel_tile(spec, cur.shape[1:], bx, bts, backend,
                                 tile)
            acc["n_chunks"] = n_chunks
            acc["tiles_per_chunk"] = K
            for ci in range(n_chunks):
                c0 = ci * K * tile
                c1 = min(c0 + K * tile, extent)
                start = max(c0 - g, 0)
                end = min(c1 + g, extent)
                up0 = time.perf_counter()
                chunk = jax.device_put(_slab(cur, start, end, ga))
                if phased:
                    jax.block_until_ready(chunk)
                acc["upload_s"] += time.perf_counter() - up0
                cp0 = time.perf_counter()
                out = engine.stencil_call_persistent(
                    chunk, spec, bx=bx, bt=bts,
                    tile=min(ktile, end - start), lead=c0 - start,
                    owned=c1 - c0, backend=backend)
                if phased:
                    jax.block_until_ready(out)
                acc["compute_s"] += time.perf_counter() - cp0
                acc["n_dispatches"] += 1
                # The persistent call returns exactly the owned slices,
                # so the drain's crop is the identity (start == t0).
                in_flight.append((c0, c1, c0, out))
                if len(in_flight) >= depth:
                    drain_one()
            while in_flight:
                drain_one()
            cur, nxt = nxt, cur
            off += bts
            continue

        for ti in range(n_tiles):
            t0 = ti * tile
            t1 = min(t0 + tile, extent)
            # The slab is *clipped* to the grid, never ghost-padded:
            # each slab is a self-contained smaller in-core problem
            # whose array edges either coincide with true grid edges
            # (first/last tile — engine boundary handling applies
            # there, exactly as in-core) or lie >= ghost slices away
            # from the owned center (interior edges — whatever the
            # boundary mode fabricates there decays by r slices per
            # fused step and never reaches the crop). This is what
            # makes the result *bitwise* equal to the in-core engine:
            # every slab call is the same jit graph the in-core path
            # compiles, just on a shorter leading axis. (Presenting
            # ghost slices through a shifted validity interval instead
            # is semantically equivalent but compiles top-edge clamp
            # taps through different XLA ops — measured 1-ulp drift.)
            start = max(t0 - g, 0)
            end = min(t1 + g, extent)
            up0 = time.perf_counter()
            slab = jax.device_put(_slab(cur, start, end, ga))
            src_slab = (jax.device_put(_slab(src_host, start, end, ga))
                        if has_src else None)
            aux_slabs = [jax.device_put(_slab(a, start, end, ga))
                         for a in aux_host]
            if phased:
                jax.block_until_ready((slab, src_slab, aux_slabs))
            acc["upload_s"] += time.perf_counter() - up0
            # Key = everything that determines the compiled program:
            # slab length + the non-leading dims (the grid's total
            # leading extent deliberately excluded — same-slab grids
            # of different heights share one compilation).
            other_dims = cur.shape[:ga] + cur.shape[ga + 1:]
            dispatch = _dispatcher(
                (spec, bx, bts, variant, backend, aux_names, donate,
                 has_src, end - start, other_dims, str(dtype),
                 None if scal is None else scal.shape),
                spec, bx, bts, variant, backend, aux_names, donate)
            cp0 = time.perf_counter()
            out = dispatch(slab, src_slab, aux_slabs, scal_dev)
            if phased:
                jax.block_until_ready(out)
            acc["compute_s"] += time.perf_counter() - cp0
            acc["n_dispatches"] += 1
            in_flight.append((t0, t1, start, out))
            if len(in_flight) >= depth:
                drain_one()
        while in_flight:
            drain_one()
        cur, nxt = nxt, cur
        off += bts

    if timing:
        metrics.update(
            pipeline_requested=requested, pipeline=pipeline,
            fallback_reason=fallback_reason, tile=int(tile),
            depth=int(depth), n_tiles=int(n_tiles),
            n_sweeps=len(schedule),
            n_dispatches=acc["n_dispatches"],
            wall_s=time.perf_counter() - wall0,
            upload_s=acc["upload_s"] if phased else None,
            compute_s=acc["compute_s"] if phased else None,
            readback_s=acc["readback_s"] if phased else None,
            n_devices=1, slab_extents=[int(extent)],
            halo_rows_exchanged=0, halo_bytes_exchanged=0)
        if pipeline == "kernel":
            metrics["n_chunks"] = acc["n_chunks"]
            metrics["tiles_per_chunk"] = acc["tiles_per_chunk"]
    return cur


def _stream_sharded(*, cur, spec, schedule, scalars, bx, variant,
                    backend, tile, hbm_budget, src_host, aux_host,
                    aux_names, has_src, depth, pipeline, requested,
                    fallback_reason, n_devices, devices, ga, extent,
                    grid_shape, dtype, donate, timing, phased, acc,
                    wall0, metrics):
    """The composed sweep loop: per-device slab streaming with
    tile-granular halo exchange (``stencil_run_outofcore`` with
    ``n_devices > 1`` — validation, planning and operand prep happen
    there; this is only the tile traffic).

    Topology: device ``d`` owns global leading-axis rows ``[d*S,
    min((d+1)*S, extent))`` (``S = shard_extent`` — the in-core
    sharded runner's partition rule) in its own **host** buffer pair
    (``cur``/``nxt`` ping-pong, exactly like the solo loop's full-grid
    pair). Every tile dispatch is the solo loop verbatim — clipped
    slab, same ``_dispatcher`` LRU, same engine jit graph, hence the
    same bitwise contract — except the slab rows come from
    ``halo.gather_slab`` over all owners (the tile-granular exchange;
    interior tiles touch only their own buffer) and ``device_put``
    pins the slab to the owning device, which is what makes the shared
    jitted dispatcher execute there (jax placement-driven dispatch).
    Tiles interleave round-robin across devices so all devices compute
    concurrently, draining when ``depth`` tiles per live device are in
    flight. Step-constant ``source``/aux operands slice from the full
    host arrays — numerically identical to pre-exchanged halos, as in
    the in-core sharded runner.
    """
    from repro.distributed.halo import _device_mesh, gather_slab
    mesh_devs = np.asarray(_device_mesh(n_devices, devices).devices)
    devs = [d for d in mesh_devs.flat]
    S = shard_extent(extent, n_devices)
    bounds = []
    for d in range(n_devices):
        lo, hi = d * S, min((d + 1) * S, extent)
        if lo >= hi:
            break               # short grid: trailing devices own nothing
        bounds.append((lo, hi))
    n_live = len(bounds)
    devs = devs[:n_live]
    cur_slabs = [np.array(_slab(cur, lo, hi, ga)) for lo, hi in bounds]
    nxt_slabs = [np.empty_like(s) for s in cur_slabs]
    tiles_d = [-(-(hi - lo) // tile) for lo, hi in bounds]
    halo_rows = 0
    # Bytes of one global leading slice across the primary grid only
    # (batch included): the unit of halo-exchange accounting.
    per_slice_b = (cur.size // extent) * dtype.itemsize

    off = 0
    for bts in schedule:
        g = spec.halo(bts)
        scal = (_tslice(scalars, off, off + bts)
                if scalars is not None else None)
        scal_devs = (None if scal is None else
                     [jax.device_put(jnp.asarray(scal), dv)
                      for dv in devs])
        in_flight: deque = deque()

        def drain_one():
            d, t0, t1, start, out = in_flight.popleft()
            rb0 = time.perf_counter()
            host = np.asarray(out)      # blocks on this tile only
            acc["readback_s"] += time.perf_counter() - rb0
            lo = bounds[d][0]
            src = [slice(None)] * host.ndim
            src[ga] = slice(t0 - start, t1 - start)   # owned slices
            dst = [slice(None)] * host.ndim
            dst[ga] = slice(t0 - lo, t1 - lo)         # slab-local rows
            nxt_slabs[d][tuple(dst)] = host[tuple(src)]

        if pipeline == "kernel":
            # Per-device chunks of K tiles, each ONE persistent
            # pallas_call on its owner — sizing as in the solo loop.
            per_slice = (int(np.prod(grid_shape[1:], dtype=np.int64))
                         * dtype.itemsize)
            if hbm_budget is not None:
                slices = hbm_budget // (max(depth, 1) * per_slice)
                K = max(1, int((slices - 2 * g) // (2 * tile)))
            else:
                K = max(tiles_d)
            K = min(K, max(tiles_d))
            chunks_d = [-(-t // K) for t in tiles_d]
            ktile = _kernel_tile(spec, cur.shape[ga + 1:], bx, bts,
                                 backend, tile)
            acc["n_chunks"] = sum(chunks_d)
            acc["tiles_per_chunk"] = K
            for ci in range(max(chunks_d)):
                for d in range(n_live):
                    if ci >= chunks_d[d]:
                        continue
                    lo, hi = bounds[d]
                    c0 = lo + ci * K * tile
                    c1 = min(c0 + K * tile, hi)
                    start = max(c0 - g, 0)
                    end = min(c1 + g, extent)
                    rows, foreign = gather_slab(cur_slabs, bounds,
                                                start, end, ax=ga,
                                                owner=d)
                    halo_rows += foreign
                    up0 = time.perf_counter()
                    chunk = jax.device_put(rows, devs[d])
                    if phased:
                        jax.block_until_ready(chunk)
                    acc["upload_s"] += time.perf_counter() - up0
                    cp0 = time.perf_counter()
                    out = engine.stencil_call_persistent(
                        chunk, spec, bx=bx, bt=bts,
                        tile=min(ktile, end - start), lead=c0 - start,
                        owned=c1 - c0, backend=backend)
                    if phased:
                        jax.block_until_ready(out)
                    acc["compute_s"] += time.perf_counter() - cp0
                    acc["n_dispatches"] += 1
                    # Persistent calls return exactly the owned rows,
                    # so the drain's crop is the identity (start == c0).
                    in_flight.append((d, c0, c1, c0, out))
                    if len(in_flight) >= depth * n_live:
                        drain_one()
            while in_flight:
                drain_one()
        else:
            for ti in range(max(tiles_d)):
                for d in range(n_live):
                    if ti >= tiles_d[d]:
                        continue
                    lo, hi = bounds[d]
                    t0 = lo + ti * tile
                    t1 = min(t0 + tile, hi)
                    start = max(t0 - g, 0)
                    end = min(t1 + g, extent)
                    rows, foreign = gather_slab(cur_slabs, bounds,
                                                start, end, ax=ga,
                                                owner=d)
                    halo_rows += foreign
                    up0 = time.perf_counter()
                    slab = jax.device_put(rows, devs[d])
                    src_slab = (jax.device_put(
                        _slab(src_host, start, end, ga), devs[d])
                        if has_src else None)
                    aux_slabs = [jax.device_put(
                        _slab(a, start, end, ga), devs[d])
                        for a in aux_host]
                    if phased:
                        jax.block_until_ready((slab, src_slab,
                                               aux_slabs))
                    acc["upload_s"] += time.perf_counter() - up0
                    other_dims = cur.shape[:ga] + cur.shape[ga + 1:]
                    dispatch = _dispatcher(
                        (spec, bx, bts, variant, backend, aux_names,
                         donate, has_src, end - start, other_dims,
                         str(dtype),
                         None if scal is None else scal.shape),
                        spec, bx, bts, variant, backend, aux_names,
                        donate)
                    cp0 = time.perf_counter()
                    out = dispatch(slab, src_slab, aux_slabs,
                                   None if scal_devs is None
                                   else scal_devs[d])
                    if phased:
                        jax.block_until_ready(out)
                    acc["compute_s"] += time.perf_counter() - cp0
                    acc["n_dispatches"] += 1
                    in_flight.append((d, t0, t1, start, out))
                    if len(in_flight) >= depth * n_live:
                        drain_one()
            while in_flight:
                drain_one()
        cur_slabs, nxt_slabs = nxt_slabs, cur_slabs
        off += bts

    result = (cur_slabs[0] if n_live == 1
              else np.concatenate(cur_slabs, axis=ga))
    if timing:
        metrics.update(
            pipeline_requested=requested, pipeline=pipeline,
            fallback_reason=fallback_reason, tile=int(tile),
            depth=int(depth), n_tiles=int(sum(tiles_d)),
            n_sweeps=len(schedule),
            n_dispatches=acc["n_dispatches"],
            wall_s=time.perf_counter() - wall0,
            upload_s=acc["upload_s"] if phased else None,
            compute_s=acc["compute_s"] if phased else None,
            readback_s=acc["readback_s"] if phased else None,
            n_devices=n_live,
            slab_extents=[int(hi - lo) for lo, hi in bounds],
            halo_rows_exchanged=int(halo_rows),
            halo_bytes_exchanged=int(halo_rows) * per_slice_b)
        if pipeline == "kernel":
            metrics["n_chunks"] = acc["n_chunks"]
            metrics["tiles_per_chunk"] = acc["tiles_per_chunk"]
    return result
