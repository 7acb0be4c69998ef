"""Spatial + temporal blocking planner (thesis §5.3.1 / §5.3.2, TPU form).

The thesis combines:
  * spatial blocking — 1D blocking in x for 2D stencils, 2.5D (block x,
    stream z... here: block x, stream z, keep full y) for 3D — with blocks
    *overlapped* by the halo so no input-size restriction exists, and
  * temporal blocking — ``bt`` fused time steps per pass, growing the halo
    to ``bt * radius`` and cutting HBM sweeps by ``bt``.

This module does the (pure, hardware-independent) bookkeeping: tile
counts, halo widths, redundancy ratios, VMEM footprints and HBM traffic.
``core.perf_model`` turns these numbers into time.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

from repro.core.stencil import StencilSpec

_LANE = 128     # TPU lane width
_SUBLANE = {4: 8, 2: 16}   # sublane count by itemsize


def aux_streams(spec: StencilSpec, source: bool = False) -> int:
    """Operand streams the engine runs alongside the main grid: one per
    coeff operand, plus one for all source operands together, a
    caller's ``source=`` grid among them (the engine pre-sums sources
    into a single additive grid — see engine.stencil_call)."""
    n_src = sum(op.role == "source" for op in spec.aux)
    return (len(spec.aux) - n_src) + int(source or n_src > 0)


def round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def shard_extent(extent: int, n_devices: int) -> int:
    """Leading-axis slice owned per device when ``distributed/halo.py``
    shards a grid ``n_devices`` ways (grid padded to ``n * S``).

    The single source of the partition rule: the runner's bt clamp and
    radius guard, ``perf_model.select_config``'s halo-fits-shard
    pruning, and ``perf_model.stencil_roofline``'s slab-recompute
    factor must all agree on it.
    """
    return math.ceil(extent / n_devices)


@dataclasses.dataclass(frozen=True)
class BlockPlan:
    """A fully-resolved blocking configuration for one stencil sweep."""

    spec: StencilSpec
    grid_shape: Tuple[int, ...]   # (H, W) for 2D; (D, H, W) for 3D
    bx: int                       # x-tile width (last axis), lane-aligned
    bt: int                       # fused time steps
    itemsize: int = 4

    def __post_init__(self):
        if len(self.grid_shape) != self.spec.dims:
            raise ValueError("grid_shape rank must equal spec.dims")
        if self.bx % _LANE != 0:
            raise ValueError(f"bx must be a multiple of {_LANE}")
        if self.bt < 1:
            raise ValueError("bt >= 1")
        if self.halo > self.bx:
            # window assembly uses the two neighbor tiles only (thesis's
            # shift register holds one block row per side).
            raise ValueError(f"halo {self.halo} exceeds tile width {self.bx}")

    # ---- geometry -----------------------------------------------------

    @property
    def halo(self) -> int:
        return self.spec.halo(self.bt)

    @property
    def width(self) -> int:
        return self.grid_shape[-1]

    @property
    def rows(self) -> int:
        """y extent (kept fully resident in VMEM, thesis fig. 5-4)."""
        return self.grid_shape[-2]

    @property
    def depth(self) -> int:
        if self.spec.dims != 3:
            raise ValueError("depth only defined for 3D plans")
        return self.grid_shape[0]

    @property
    def n_tiles(self) -> int:
        return math.ceil(self.width / self.bx)

    @property
    def padded_width(self) -> int:
        return self.n_tiles * self.bx

    @property
    def padded_rows(self) -> int:
        return round_up(self.rows, _SUBLANE[self.itemsize])

    @property
    def window_width(self) -> int:
        """Columns held live per tile: bx + 2*halo (thesis fig. 5-5)."""
        return self.bx + 2 * self.halo

    # ---- cost bookkeeping ---------------------------------------------

    @property
    def redundancy(self) -> float:
        """Redundant-compute ratio from overlapped halos (thesis §5.4).

        Average cells computed per useful cell. Each fused step computes
        the full window; validity shrinks by r per step, so the average
        overcompute per step is (bx + 2*(bt - t)*r)/bx summed over steps.
        """
        r, bx, bt = self.spec.radius, self.bx, self.bt
        total = sum(bx + 2 * (bt - t) * r for t in range(1, bt + 1))
        return total / (bx * bt)

    @property
    def cells(self) -> int:
        n = 1
        for s in self.grid_shape:
            n *= s
        return n

    def flops_per_sweep(self, include_redundancy: bool = True) -> float:
        """FLOPs for one pass of ``bt`` time steps over the grid."""
        base = self.cells * self.spec.flops_per_cell * self.bt
        return base * (self.redundancy if include_redundancy else 1.0)

    def useful_flops_per_sweep(self) -> float:
        return self.flops_per_sweep(include_redundancy=False)

    @property
    def n_aux(self) -> int:
        """Operand *streams* the engine runs alongside the main grid
        (:func:`aux_streams`)."""
        return aux_streams(self.spec)

    def hbm_bytes_per_sweep(self, read_amplification: float = 1.0) -> float:
        """HBM traffic for one pass: one read of every input operand
        (the grid + each aux operand, all streamed tile-by-tile) + one
        write of the grid.

        ``read_amplification`` models kernel variants: the simple
        3-neighbor-operand kernel reads each tile 3x (amp=3); the
        revolving-buffer kernel (the thesis's shift register analog)
        reads each tile once (amp=1). Aux operands stream through the
        same BlockSpecs, so the amplification applies to them too.
        """
        reads = read_amplification * (1.0 + self.n_aux)
        return self.cells * self.itemsize * (reads + 1.0)

    @property
    def leading(self) -> int:
        """Extent of the leading axis — the one ``distributed/halo.py``
        shards (y for 2D, z for 3D)."""
        return self.grid_shape[0]

    def halo_bytes_per_exchange(self) -> int:
        """Bytes a device receives per sweep when the grid is sharded
        along the leading axis: two ``halo``-deep boundary slices
        (one per neighbor), each covering the full non-leading extent.
        Grows with ``bt`` (deeper halos) while the number of exchanges
        shrinks as ``ceil(n_steps / bt)`` — the tradeoff the
        device-aware autotuner searches."""
        per_slice = self.cells // self.leading
        return 2 * self.halo * per_slice * self.itemsize

    def vmem_bytes(self, variant: str = "revolving") -> int:
        """Per-core VMEM the Pallas kernel of ``variant`` allocates (see
        :func:`kernel_vmem_bytes`). The default, revolving, is the
        smaller 2D variant; the 3D kernel ignores ``variant``."""
        return kernel_vmem_bytes(
            self.spec, rows=self.rows, bx=self.bx, bt=self.bt,
            halo=self.halo, n_streams=1 + self.n_aux, variant=variant,
            itemsize=self.itemsize)

    def sweeps(self, n_steps: int) -> int:
        """Grid passes needed for ``n_steps`` total time steps."""
        return math.ceil(n_steps / self.bt)


# ---------------------------------------------------------------------------
# Register strips of the 2D revolving kernel. Its fused steps run on a
# strip of ``S`` output rows at a time, loaded with ``row_halo`` extra
# rows above and below, on a window of whole lane tiles: ``lane_halo``
# columns either side of the output tile. ``S`` is sized so that one
# strip-sized value fills at most the 64-vreg register file.
# ---------------------------------------------------------------------------

_STRIP_VREGS = 64


def row_halo(halo: int, itemsize: int = 4) -> int:
    """Rows loaded above and below a strip: the fused halo rounded up to
    the sublane tile."""
    return round_up(halo, _SUBLANE[itemsize])


def lane_halo(halo: int) -> int:
    """Columns computed either side of an output tile: the fused halo
    rounded up to whole lane tiles."""
    return round_up(halo, _LANE)


def strip_rows(bx: int, halo: int, rows: int, itemsize: int = 4) -> int:
    """Output rows per strip of the 2D revolving kernel.

    The largest multiple of the sublane tile whose loaded strip
    (``S + 2 * row_halo`` rows of ``bx + 2 * lane_halo`` lanes) fits
    ``_STRIP_VREGS`` vector registers, but at least ``2 * row_halo``
    (so the rows loaded twice never outnumber the rows kept) and at
    most the panel's ``rows`` (a panel shorter than one strip runs as
    one strip).
    """
    sub = _SUBLANE[itemsize]
    hr = row_halo(halo, itemsize)
    tiles = (bx + 2 * lane_halo(halo)) // _LANE
    fit = _STRIP_VREGS * sub // tiles - 2 * hr
    return min(max(fit - fit % sub, 2 * hr, sub), round_up(rows, sub))


def edge_strips(rows: int, strip: int, halo: int, lo: int, hi: int,
                itemsize: int = 4) -> int:
    """Strips of a ``rows``-row panel whose loaded rows reach outside
    the valid rows ``[lo, hi)``: the strips of a tile inside the grid's
    columns that take the kernel's boundary path."""
    hr = row_halo(halo, itemsize)
    starts = (min(j * strip, rows - strip)
              for j in range(-(-rows // strip)))
    return sum(s - hr < lo or s + strip + hr > hi for s in starts)


# ---------------------------------------------------------------------------
# VMEM model. Mosaic keeps every value of a kernel body that outgrows
# the registers in VMEM, so a kernel's footprint is its pipelined blocks
# and scratch plus a number of live window-sized values that grows with
# the tap count. The 2D revolving kernel's values are strip-sized, and
# its blocks and scratch hold all of its full-height VMEM; the other
# kernels' values are full-height.
# The coefficients below are fitted (rounded up) to the smallest
# ``vmem_limit_bytes`` at which each kernel compiles for a TPU v5e,
# found by bisection at 1024 rows and checked linear in rows up to
# 8192 (16384 for the revolving 2D kernel); tests/test_tpu_compile.py
# holds the model to within ``VMEM_MODEL_MARGIN`` of the compiler on
# both sides.
# ---------------------------------------------------------------------------

VMEM_MODEL_MARGIN = 0.10
# Mosaic's fixed internal scratch, and the granularity of the limit.
_VMEM_SLACK = 2 * 2 ** 20


def _taps(spec: StencilSpec) -> int:
    if spec.layout == "star":
        return 2 * spec.dims * spec.radius + 1
    return (2 * spec.radius + 1) ** spec.dims


def _window_values(spec: StencilSpec, bt: int, n_streams: int) -> float:
    """Live window-sized values of one fused step (2D: per window, a
    row strip in the revolving kernel; 3D: per plane)."""
    if spec.dims == 2:
        return (2.5 + _taps(spec) / 2 + (bt > 1)
                + 2 * (spec.boundary == "clamp") + (n_streams - 1))
    return (2 * spec.radius + 2) * _taps(spec) / (6 * spec.radius + 1) \
        + 2 * (n_streams - 1)


def _panel_vmem_bytes(spec: StencilSpec, *, rows: int, bx: int, bt: int,
                      halo: int, n_streams: int, blocks: int,
                      itemsize: int) -> int:
    """VMEM of a 2D kernel whose window values are full-height: the
    ``blocks`` lanes of full-height blocks and scratch, plus the live
    window values."""
    col = round_up(rows, _SUBLANE[itemsize]) * itemsize   # bytes per lane
    win = round_up(bx + 2 * halo, _LANE)
    values = _window_values(spec, bt, n_streams) * win
    return int(col * (blocks + values))


def kernel_vmem_bytes(spec: StencilSpec, *, rows: int, bx: int, bt: int,
                      halo: int, n_streams: int, variant: str,
                      itemsize: int = 4) -> int:
    """VMEM one in-core engine kernel allocates.

    ``rows`` is the full-height row panel (2D) or plane height (3D),
    ``halo`` the fused halo, ``n_streams`` the streamed operands (grid
    + aux streams). Counted: every BlockSpec block double-buffered (three
    neighbour blocks per stream for the multioperand and 3D kernels, one
    for revolving) and the output block; the revolving ``3*bx`` scratch
    per stream, ``2 * row_halo`` rows taller than the panel; the 3D
    stage windows and source ring; and the live window values
    (``_window_values``), all lane-padded: full-height for the
    multioperand and 3D kernels, strip-sized (``strip_rows``) for the
    revolving one.
    """
    if spec.dims == 2 and variant == "revolving":
        col = round_up(rows, _SUBLANE[itemsize]) * itemsize
        hr = row_halo(halo, itemsize)
        strip = strip_rows(bx, halo, rows, itemsize)
        lanes = bx + 2 * lane_halo(halo)
        # Double-buffered input blocks, the output block, and one
        # full-height tile: the masked stream-in.
        blocks = col * (n_streams * 2 * bx + 2 * bx + bx)
        scratch = n_streams * 3 * bx * (col + 2 * hr * itemsize)
        values = (_window_values(spec, bt, n_streams) * lanes
                  * (strip + 2 * hr) * itemsize)
        return int(blocks + scratch + values)
    if spec.dims == 2:
        return _panel_vmem_bytes(
            spec, rows=rows, bx=bx, bt=bt, halo=halo, n_streams=n_streams,
            blocks=n_streams * 3 * 2 * bx + 2 * bx, itemsize=itemsize)
    col = round_up(rows, _SUBLANE[itemsize]) * itemsize   # bytes per lane
    win = round_up(bx + 2 * halo, _LANE)
    values = _window_values(spec, bt, n_streams) * win
    stages = bt * (2 * spec.radius + 1) * win
    ring = (halo + 1) * win * (n_streams > 1)
    blocks = n_streams * 3 * 2 * bx + 2 * bx
    return int(col * (blocks + stages + ring + values))


def persistent_vmem_bytes(spec: StencilSpec, slab_shape: Tuple[int, ...],
                          *, bx: int, bt: int, tile: int,
                          itemsize: int = 4) -> int:
    """VMEM of the persistent out-of-core kernel
    (``engine.stencil_call_persistent``): two DMA slabs of ``tile +
    2*ghost`` leading rows and one result slab, each ``slab_shape`` (the
    non-leading, lane-padded dims), plus one x tile's full-height window
    values (and the 3D stage windows)."""
    g = spec.halo(bt)
    align = _SUBLANE[itemsize] if spec.dims == 2 else 1
    rows = round_up(tile, align) + 2 * round_up(g, align)
    per_row = itemsize
    for s in slab_shape[:-1]:
        per_row *= s
    wp = round_up(slab_shape[-1], bx)
    slabs = 3 * rows * per_row * wp
    if spec.dims == 2:
        tile_vmem = _panel_vmem_bytes(spec, rows=rows, bx=bx, bt=bt,
                                      halo=g, n_streams=1, blocks=7 * bx,
                                      itemsize=itemsize)
    else:
        tile_vmem = kernel_vmem_bytes(spec, rows=slab_shape[0], bx=bx,
                                      bt=bt, halo=g, n_streams=1,
                                      variant="revolving",
                                      itemsize=itemsize)
    return slabs + tile_vmem


def persistent_tile(spec: StencilSpec, slab_shape: Tuple[int, ...], *,
                    bx: int, bt: int, vmem_budget: int, limit: int,
                    itemsize: int = 4) -> int:
    """The largest in-kernel tile (<= ``limit`` leading rows) whose
    persistent-kernel VMEM fits ``vmem_budget``; raises when not even
    one row fits."""
    def fits(t):
        return persistent_vmem_bytes(spec, slab_shape, bx=bx, bt=bt,
                                     tile=t, itemsize=itemsize) <= vmem_budget
    if not fits(1):
        raise ValueError(
            f"no persistent-kernel tile of a {slab_shape} slab (bx={bx}, "
            f"bt={bt}) fits the {vmem_budget}-byte VMEM budget")
    lo, hi = 1, limit
    while lo < hi:
        mid = (lo + hi + 1) // 2
        if fits(mid):
            lo = mid
        else:
            hi = mid - 1
    return lo


def vmem_limit(model_bytes: int) -> int:
    """The scoped-VMEM limit a kernel asks Mosaic for: its modeled
    footprint plus slack, in whole MiB."""
    return round_up(int(model_bytes) + _VMEM_SLACK, 2 ** 20)


def incore_resident_bytes(spec: StencilSpec, grid_shape: Tuple[int, ...],
                          itemsize: int = 4, batch: int = 1,
                          extra_streams: int = 0) -> int:
    """Device-HBM working set of an *in-core* run of ``spec``.

    What must be resident at once: the input grid, the output grid,
    and one grid per **declared** aux operand — residency counts every
    operand individually (the engine's pre-summing of source operands
    saves VMEM *streams*, not HBM residency, so this is deliberately
    not ``BlockPlan.n_aux``). ``extra_streams`` covers caller-side
    operands the spec cannot see (the legacy ``source=`` kwarg). Each
    array counts ``B`` times over for a batched dispatch. Lane/sublane
    padding is ignored (it is < 1% at out-of-core sizes); this is the
    number the HBM budget is compared against to decide whether a
    problem needs the out-of-core path (``repro.outofcore``).
    """
    cells = batch
    for s in grid_shape:
        cells *= s
    return cells * itemsize * (2 + len(spec.aux) + extra_streams)


def shard_resident_bytes(spec: StencilSpec, grid_shape: Tuple[int, ...],
                         itemsize: int = 4, *, n_devices: int = 1,
                         bt: int = 1, batch: int = 1,
                         extra_streams: int = 0) -> int:
    """Per-device HBM working set of an in-core *sharded* run.

    ``incore_resident_bytes`` split over the deep-halo partition rule
    (``shard_extent``) — but a shard is not 1/n of the grid: every
    device also holds the ``r*bt``-deep ghost slices its slab carries
    per side, for every resident stream. Near the routing threshold
    that ghost charge is the difference between an in-core sharded run
    that fits and one that OOMs, so the out-of-core routing predicate
    (``outofcore.route_decision``) must use this, not the bare
    division. Capped at the whole grid: a clipped first/last slab (or
    a ghost deeper than the grid) never holds more than everything.
    """
    resident = incore_resident_bytes(spec, grid_shape, itemsize, batch,
                                     extra_streams)
    if n_devices <= 1:
        return resident
    extent = grid_shape[0]
    # Exact by construction: resident = extent * (bytes per leading
    # slice across all streams).
    per_slice = resident // extent
    slab = shard_extent(extent, n_devices) + 2 * spec.halo(bt)
    return per_slice * min(slab, extent)


@dataclasses.dataclass(frozen=True)
class TilePlan:
    """Out-of-core decomposition: leading-axis tiles + deep ghosts.

    The host array plays the FPGA's external DRAM and device HBM plays
    its block RAM (thesis §5.3's "no input-size restriction" claim,
    re-landed one memory level up): the grid's *leading* axis (rows for
    2D, z-planes for 3D — the same axis ``distributed/halo.py``
    shards) is cut into ``tile``-deep slices, and each slice streams
    through the device as a ``ghost + tile + ghost`` slab, where
    ``ghost = r * bt`` is the dependency cone of one fused time block.
    Unlike the sharded runner there is **no** ``ghost <= tile``
    constraint: slabs are sliced from the full host-resident grid, so
    ghosts may be arbitrarily deeper than the tile they wrap.

    ``tile`` is the leading-axis extent each slab *owns* (the cropped
    center); ``batch`` scales every per-slab byte count for a
    ``[B, *grid]`` batched grid (tiles stream the whole batch of one
    slice — exactly how the halo runner grid-shards batches).
    """

    spec: StencilSpec
    grid_shape: Tuple[int, ...]   # per-problem grid (no batch axis)
    bx: int
    bt: int
    tile: int                     # leading-axis rows/planes per tile
    itemsize: int = 4
    batch: int = 1
    # Caller-side operand grids the spec cannot see (the legacy
    # ``source=`` kwarg): each is sliced and uploaded per tile exactly
    # like a declared operand, so it must count in every byte total.
    extra_streams: int = 0

    def __post_init__(self):
        if len(self.grid_shape) != self.spec.dims:
            raise ValueError("grid_shape rank must equal spec.dims")
        if not 1 <= self.tile <= self.grid_shape[0]:
            raise ValueError(
                f"tile must be in [1, {self.grid_shape[0]}] "
                f"(the leading-axis extent), got {self.tile}")
        if self.batch < 1:
            raise ValueError("batch >= 1")

    @property
    def ghost(self) -> int:
        """Ghost depth per side: the ``r * bt`` dependency cone."""
        return self.spec.halo(self.bt)

    @property
    def leading(self) -> int:
        return self.grid_shape[0]

    @property
    def n_tiles(self) -> int:
        return math.ceil(self.leading / self.tile)

    @property
    def slab_extent(self) -> int:
        """Leading extent of every device slab: ghost + tile + ghost
        (fixed across tiles so one engine compilation serves all)."""
        return self.tile + 2 * self.ghost

    @property
    def _per_slice(self) -> int:
        """Cells per unit of leading extent (batch included)."""
        cells = self.batch
        for s in self.grid_shape[1:]:
            cells *= s
        return cells

    @property
    def n_operands(self) -> int:
        """Input arrays sliced and uploaded per tile besides the grid:
        one slab per **declared** aux operand (each is its own resident
        array — residency is not ``BlockPlan.n_aux``, which collapses
        pre-summed source streams) plus ``extra_streams``."""
        return len(self.spec.aux) + self.extra_streams

    def device_bytes(self, depth: int = 2) -> int:
        """HBM held by ``depth`` tiles in flight (double buffering).

        Per in-flight tile: the input slab, one slab per operand, and
        the output slab. ``depth=2`` is the steady state of the
        double-buffered loop — tile ``i``'s result is still on device
        while tile ``i+1``'s transfer and compute proceed.
        """
        per_tile = self.slab_extent * self._per_slice * self.itemsize \
            * (2 + self.n_operands)
        return depth * per_tile

    def host_bytes_per_sweep(self) -> int:
        """Host<->device traffic for one ``bt``-step pass over the grid:
        every tile uploads its ``ghost+tile+ghost`` slab once per input
        array and downloads its ``tile``-deep result."""
        up = self.n_tiles * self.slab_extent * (1 + self.n_operands)
        down = self.leading          # owned slices come back exactly once
        return (up + down) * self._per_slice * self.itemsize

    @property
    def transfer_amplification(self) -> float:
        """Host-read amplification from overlapped ghosts:
        ``(tile + 2*ghost) / tile`` — the out-of-core analog of the
        halo runner's slab-recompute factor. Larger tiles amortize it."""
        return self.slab_extent / self.tile

    def sweeps(self, n_steps: int) -> int:
        return math.ceil(n_steps / self.bt)


def plan_tiles(spec: StencilSpec, grid_shape: Tuple[int, ...], *,
               bx: int, bt: int, hbm_budget: int, itemsize: int = 4,
               batch: int = 1, depth: int = 2,
               extra_streams: int = 0) -> Optional[TilePlan]:
    """Size leading-axis tiles against a device-HBM budget.

    Returns ``None`` when the whole problem fits in-core under
    ``hbm_budget`` (no tiling needed). Otherwise returns the TilePlan
    with the **largest** tile whose ``depth``-buffered working set fits
    the budget — in the transfer model, bigger tiles are strictly
    better (ghost re-upload amortizes as ``(tile + 2*ghost)/tile``), so
    the only search is over ``bt`` (done by the autotuner, which trades
    ghost depth against sweep count). Raises when even a 1-slice tile
    cannot fit, naming the budget and the minimum it would take.
    """
    if incore_resident_bytes(spec, grid_shape, itemsize, batch,
                             extra_streams) <= hbm_budget:
        return None
    lo, hi = 1, grid_shape[0]

    def fits(tile: int) -> bool:
        return TilePlan(spec, grid_shape, bx=bx, bt=bt, tile=tile,
                        itemsize=itemsize, batch=batch,
                        extra_streams=extra_streams,
                        ).device_bytes(depth) <= hbm_budget

    if not fits(lo):
        need = TilePlan(spec, grid_shape, bx=bx, bt=bt, tile=1,
                        itemsize=itemsize, batch=batch,
                        extra_streams=extra_streams).device_bytes(depth)
        raise ValueError(
            f"no out-of-core tiling of {grid_shape} (bt={bt}, batch="
            f"{batch}) fits hbm_budget={hbm_budget}: even a 1-slice "
            f"tile needs {need} bytes (ghost depth {spec.halo(bt)} per "
            f"side, {depth}-deep buffering); lower bt or raise the "
            f"budget")
    while lo < hi:                     # largest tile that fits (bisect)
        mid = (lo + hi + 1) // 2
        if fits(mid):
            lo = mid
        else:
            hi = mid - 1
    return TilePlan(spec, grid_shape, bx=bx, bt=bt, tile=lo,
                    itemsize=itemsize, batch=batch,
                    extra_streams=extra_streams)


def candidate_plans(spec: StencilSpec, grid_shape: Tuple[int, ...],
                    vmem_budget: int,
                    itemsize: int = 4) -> list[BlockPlan]:
    """Enumerate legal (bx, bt) configurations under the VMEM budget
    (a device's ``TpuSpec.vmem_bytes``), by the modeled footprint of
    the revolving (smaller) kernel variant.

    This is the search space the thesis's §5.4 model prunes so only a
    handful of configurations ever reach the (hours-long) place-and-route
    step; here the expensive step it saves is XLA compilation + dry-run.
    """
    out = []
    width = grid_shape[-1]
    bx = _LANE
    while bx <= max(_LANE, round_up(width, _LANE)):
        for bt in (1, 2, 3, 4, 6, 8, 12, 16):
            try:
                plan = BlockPlan(spec, grid_shape, bx=bx, bt=bt,
                                 itemsize=itemsize)
            except ValueError:
                continue
            if plan.vmem_bytes() <= vmem_budget:
                out.append(plan)
        bx *= 2
    return out
