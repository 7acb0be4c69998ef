"""TPU roofline performance model (thesis §5.4, adapted per DESIGN.md §2).

The thesis's model predicts run time of a blocked stencil pipeline from
(block size, vectorization, temporal degree, f_max) and is used to prune
the parameter space before place-and-route. Our adaptation predicts run
time from three roofline terms and prunes the (bx, bt) space before
compilation — and the *same three terms* are what EXPERIMENTS.md reports
for every (architecture x mesh) dry-run cell:

    t_compute    = FLOPs / (chips * peak_flops)
    t_memory     = HBM bytes / (chips * hbm_bw)
    t_collective = collective bytes / (chips * link_bw)

    t_predicted  = max(...)   (bulk-synchronous; overlap modeled by max)
"""
from __future__ import annotations

import dataclasses
from typing import Iterable, Sequence

import jax

from repro.core.blocking import (BlockPlan, TilePlan, candidate_plans,
                                 incore_resident_bytes, shard_extent)
from repro.core.stencil import StencilSpec


@dataclasses.dataclass(frozen=True)
class TpuSpec:
    """Hardware constants (defaults: TPU v5e-class, per assignment)."""

    name: str = "tpu-v5e"
    peak_flops_bf16: float = 197e12      # MXU, bf16
    peak_flops_f32: float = 98.5e12      # MXU, f32 (half-rate)
    vpu_flops_f32: float = 3.9e12        # VPU estimate: 8x128 lanes, FMA, ~950MHz, 2 issue
    hbm_bw: float = 819e9                # bytes/s
    ici_bw: float = 50e9                 # bytes/s per link
    ici_links: int = 4                   # 2D torus: 4 links/chip
    vmem_capacity: int = 128 * 2 ** 20   # physical VMEM per core
    hbm_bytes: int = 16 * 2 ** 30
    tdp_watts: float = 170.0             # modeled only (DESIGN.md §8)
    # Host-side cost of launching one kernel (dispatch + queueing).
    # This is what batching amortizes: B problems per launch pay it
    # once, so small-grid occupancy rises with B (the serving
    # front-end's whole reason to exist).
    dispatch_overhead_s: float = 5e-6
    # Host<->device bandwidth (PCIe-class). This is the out-of-core
    # path's roofline: when a grid exceeds hbm_bytes, every sweep
    # streams it over this link — the TPU analog of the thesis FPGA's
    # external-DRAM channel, one memory level further out than HBM.
    host_bw: float = 16e9

    @property
    def vmem_bytes(self) -> int:
        """The VMEM budget block plans are sized against: capacity
        less the headroom Mosaic keeps for its own scratch."""
        return self.vmem_capacity - VMEM_HEADROOM


VMEM_HEADROOM = 8 * 2 ** 20
V5E = TpuSpec()
# A "next generation" part for the thesis's Stratix 10 projection analog
# (§5.7.3): ~2.3x compute, ~3.3x HBM of v5e — v5p-class constants.
V5P_PROJECTION = TpuSpec(name="tpu-v5p-projection",
                         peak_flops_bf16=459e12, peak_flops_f32=229.5e12,
                         vpu_flops_f32=9.2e12, hbm_bw=2765e9, ici_bw=100e9,
                         vmem_capacity=128 * 2 ** 20,
                         hbm_bytes=95 * 2 ** 30,
                         tdp_watts=350.0)

# ---------------------------------------------------------------------------
# Per-backend device specs (the portability study's "one source, many
# backends, continuously measured"). The same TpuSpec-shaped constants
# describe whichever device an engine backend runs on; the autotuner
# keys its cache on the spec's name, so a plan tuned against one
# device's ratios can never be misread as another's (cache schema v7,
# docs/portability.md).
# ---------------------------------------------------------------------------

# Server-class x86 host: the interpret/reference backends' device. The
# compute/bandwidth ratios are what matter to the model prior (AVX-class
# vector FLOPs vs DDR bandwidth); the VMEM budget and hbm_bytes
# deliberately match V5E's so a plan picked here is one the chip
# accepts and the *default* in-core/out-of-core routing threshold
# (outofcore.route_decision) is one number everywhere.
CPU_HOST = TpuSpec(name="cpu-host",
                   peak_flops_bf16=2e12, peak_flops_f32=1e12,
                   vpu_flops_f32=0.5e12, hbm_bw=100e9,
                   ici_bw=25e9, ici_links=1,
                   hbm_bytes=16 * 2 ** 30,
                   tdp_watts=250.0, dispatch_overhead_s=20e-6,
                   host_bw=100e9)   # "host streaming" is a memcpy here

# A100-class part for the Pallas/Triton GPU lowering (where present).
# Stencils are CUDA-core (not tensor-core) work, mirroring the VPU
# reasoning on TPU; the VMEM capacity models the L2 + SMEM budget a
# block plan should fit.
GPU_GENERIC = TpuSpec(name="gpu-a100-class",
                      peak_flops_bf16=312e12, peak_flops_f32=19.5e12,
                      vpu_flops_f32=19.5e12, hbm_bw=1555e9,
                      ici_bw=300e9, ici_links=1,
                      vmem_capacity=48 * 2 ** 20,
                      hbm_bytes=40 * 2 ** 30,
                      tdp_watts=400.0, dispatch_overhead_s=8e-6,
                      host_bw=25e9)

# ``jax.devices()[0].device_kind`` -> spec of the TPUs the ``pallas``
# backend can run on (VMEM per core from the Pallas TPU docs).
TPU_SPECS = {
    "TPU v5 lite": V5E,
}

# The other engine backends (kernels/ops.py dispatch) -> device spec.
DEVICE_SPECS = {
    "interpret": CPU_HOST,
    "reference": CPU_HOST,
    "gpu": GPU_GENERIC,
}


def device_spec_for(backend: str) -> TpuSpec:
    """The device spec a resolved engine backend runs against.

    ``pallas`` looks the chip up by ``jax.devices()[0].device_kind`` in
    ``TPU_SPECS``. An unknown kind or backend raises: planning against
    another chip's VMEM and bandwidth would pick plans the compiler
    refuses or rank them wrongly.
    """
    if backend == "pallas":
        kind = jax.devices()[0].device_kind
        if kind not in TPU_SPECS:
            raise ValueError(
                f"no device spec for TPU kind {kind!r}; known kinds: "
                f"{sorted(TPU_SPECS)} (add one to perf_model.TPU_SPECS)")
        return TPU_SPECS[kind]
    if backend not in DEVICE_SPECS:
        raise ValueError(f"no device spec for backend {backend!r}; known: "
                         f"{['pallas'] + sorted(DEVICE_SPECS)}")
    return DEVICE_SPECS[backend]


@dataclasses.dataclass(frozen=True)
class RooflineTerms:
    """The three times (seconds) + provenance. `dominant` names the max."""

    t_compute: float
    t_memory: float
    t_collective: float
    flops: float
    hbm_bytes: float
    collective_bytes: float
    # Modeled dispatch time (launches x per-launch overhead). Not part
    # of t_predicted (that stays the pure roofline max); it feeds the
    # occupancy term below and the batch-aware tuner ranking.
    t_dispatch: float = 0.0
    # Out-of-core only: host<->device streaming time (slab uploads +
    # result downloads over TpuSpec.host_bw) and the bytes behind it.
    # Like t_dispatch these stay out of t_predicted (which remains the
    # pure on-device roofline); rank out-of-core candidates with
    # ``t_outofcore`` and report ``exposed_transfer_fraction``.
    t_host: float = 0.0
    host_bytes: float = 0.0
    # The schedule these terms were priced under. ``overlap``: the halo
    # runner's interior/edge schedule hides collectives under local
    # work (overlap=False — ops.stencil_run(overlap=False) — runs
    # exchange then compute back-to-back, so the collective is fully
    # exposed). ``transfer_overlap``: the out-of-core runner's
    # double-buffered loop hides host streaming under device compute
    # (depth=1 serializes the phases, so the transfer is fully
    # exposed). The exposed-fraction properties below account for the
    # schedule actually chosen instead of assuming perfect overlap.
    overlap: bool = True
    transfer_overlap: bool = True

    @property
    def t_predicted(self) -> float:
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def dominant(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def roofline_fraction(self) -> float:
        """Fraction of the dominant roofline actually achieved if the
        program runs exactly at t_predicted (1.0 = on the roof)."""
        t = self.t_predicted
        return 0.0 if t == 0 else max(self.t_compute, self.t_memory) / t if (
            self.t_collective == t) else 1.0

    @property
    def device_busy_fraction(self) -> float:
        """Modeled fraction of wall-clock the device spends computing
        rather than waiting on dispatch — the occupancy a batched
        launch raises on small grids (1.0 = pipeline never drains)."""
        t = self.t_predicted
        return 0.0 if t == 0 else t / (t + self.t_dispatch)

    @property
    def t_outofcore(self) -> float:
        """Modeled wall time of an out-of-core run. Double-buffered
        (``transfer_overlap=True``): transfers overlap compute, so
        whichever side is slower sets the pace —
        ``max(on-device roofline, host streaming)``. Serialized
        (``depth=1``): the phases run back-to-back and simply add."""
        if not self.transfer_overlap:
            return self.t_predicted + self.t_host
        return max(self.t_predicted, self.t_host)

    @property
    def exposed_transfer_fraction(self) -> float:
        """Modeled fraction of run time spent in *exposed* (un-hidden)
        host<->device streaming, under the schedule actually chosen:
        with the double-buffered overlap only the excess of t_host over
        the on-device roofline shows; a serialized (``depth=1``) run
        exposes the whole transfer. 0 for in-core runs; -> 1 as the
        host link becomes the bottleneck."""
        t = self.t_outofcore
        if t == 0:
            return 0.0
        if not self.transfer_overlap:
            return self.t_host / t
        return max(0.0, self.t_host - self.t_predicted) / t

    @property
    def exposed_collective_fraction(self) -> float:
        """Modeled fraction of run time spent in *exposed* (un-hidden)
        communication, under the schedule actually chosen: with the
        halo runner's interior/edge overlap only the excess of
        t_collective over max(t_compute, t_memory) shows; an
        ``overlap=False`` run (exchange, then compute, back-to-back)
        exposes the whole collective."""
        if not self.overlap:
            wall = max(self.t_compute, self.t_memory) + self.t_collective
            return 0.0 if wall == 0 else self.t_collective / wall
        t = self.t_predicted
        if t == 0:
            return 0.0
        return max(0.0, self.t_collective
                   - max(self.t_compute, self.t_memory)) / t


def stencil_roofline(plan: BlockPlan, n_steps: int, tpu: TpuSpec = V5E,
                     chips: int = 1, read_amplification: float = 1.0,
                     halo_exchange: bool = False,
                     batch: int = 1, overlap: bool = True) -> RooflineTerms:
    """Roofline terms for running ``n_steps`` of a stencil under ``plan``.

    ``halo_exchange``: when the grid is sharded over ``chips`` along its
    leading axis (``distributed/halo.py``), each sweep ppermutes two
    ``halo``-deep boundary slices per device — the collective term the
    thesis (single-FPGA) didn't need — and every device recomputes its
    ``halo+shard+halo`` slab, scaling the local compute/HBM terms by
    ``(S + 2*halo)/S``. Raising ``bt`` deepens the halos (more
    redundancy) but cuts the number of exchanges — the tradeoff the
    device-aware tuner resolves. Stencils are VPU work on TPU, so the
    compute roof is vpu_flops_f32.

    ``batch``: ``B`` independent problems per dispatch (the engine's
    leading batch axis). The work terms scale by ``B``; the number of
    *launches* does not — that asymmetry is the modeled occupancy win
    (``RooflineTerms.device_busy_fraction``) batching buys small grids.

    ``overlap``: whether the sharded runner's interior/edge schedule
    (hide the exchange under interior compute) is in effect — rides on
    the returned terms so ``exposed_collective_fraction`` models the
    schedule actually chosen (``overlap=False`` exposes the whole
    collective).
    """
    sweeps = plan.sweeps(n_steps)
    flops = batch * plan.flops_per_sweep() * sweeps
    hbm = batch * plan.hbm_bytes_per_sweep(read_amplification) * sweeps
    coll = 0.0
    if halo_exchange and chips > 1:
        shard = shard_extent(plan.leading, chips)
        slab = (shard + 2 * plan.halo) / shard  # per-device recompute
        flops *= slab
        hbm *= slab
        coll = batch * plan.halo_bytes_per_exchange() * sweeps
    return RooflineTerms(
        t_compute=flops / (chips * tpu.vpu_flops_f32),
        t_memory=hbm / (chips * tpu.hbm_bw),
        t_collective=coll / tpu.ici_bw if coll else 0.0,
        flops=flops, hbm_bytes=hbm, collective_bytes=coll,
        t_dispatch=sweeps * tpu.dispatch_overhead_s,
        overlap=overlap)


def outofcore_roofline(tile_plan: TilePlan, n_steps: int,
                       tpu: TpuSpec = V5E,
                       read_amplification: float = 1.0,
                       transfer_overlap: bool = True,
                       n_devices: int = 1) -> RooflineTerms:
    """Roofline terms for a host-streaming out-of-core run.

    ``n_devices > 1`` models the composed runner (each device streams
    its own leading-axis slab's tiles concurrently): the device-side
    and host-streaming *times* divide by the device count — the byte
    and flop totals stay aggregate — the per-tile dispatch term does
    NOT (launches issue from one host thread), and the tile-granular
    halo exchange adds a collective term: ``2*ghost`` slices per
    interior seam per sweep, charged at ``tpu.ici_bw`` like the
    in-core sharded model, composing with ``t_host`` through
    ``t_outofcore`` (``t_collective`` raises the predicted device-side
    envelope the host link must hide under).

    On-device terms are the in-core ones (each slab runs the unchanged
    single-device engine), plus the host<->device streaming term: every
    sweep uploads each tile's ``ghost+tile+ghost`` slab per operand
    stream and downloads the ``tile``-deep result
    (``TilePlan.host_bytes_per_sweep``), all over ``tpu.host_bw``.
    Rank tile shapes by ``t_outofcore`` (transfers overlap compute in
    the double-buffered loop) and report ``exposed_transfer_fraction``
    — the out-of-core analog of the halo runner's exposed-communication
    fraction. Raising ``bt`` cuts sweeps (fewer host passes) at the
    price of deeper ghosts; raising ``tile`` amortizes the ghost
    re-upload — the two knobs the budget-aware autotuner searches.

    ``transfer_overlap``: whether the runner's double buffering
    (``depth >= 2``) is in effect — rides on the returned terms so
    ``t_outofcore``/``exposed_transfer_fraction`` model the schedule
    actually chosen (``depth=1`` serializes upload/compute/readback
    and exposes the whole transfer).
    """
    plan = BlockPlan(tile_plan.spec, tile_plan.grid_shape,
                     bx=tile_plan.bx, bt=tile_plan.bt,
                     itemsize=tile_plan.itemsize)
    base = stencil_roofline(plan, n_steps, tpu, chips=1,
                            read_amplification=read_amplification,
                            batch=tile_plan.batch)
    # Ghost recompute: every slab computes (and moves through HBM) its
    # full tile+2*ghost extent, not just the owned tile — the same
    # slab factor the halo model charges (stencil_roofline's
    # halo_exchange path). Without it the model under-prices deep-bt
    # candidates, whose disproportionally deep ghosts are exactly the
    # cost being traded against fewer host passes.
    amp = tile_plan.transfer_amplification
    sweeps = tile_plan.sweeps(n_steps)
    host = float(tile_plan.host_bytes_per_sweep()) * sweeps
    # Per-tile launches, not per-sweep: the dispatch term scales with
    # the tile count (another reason small tiles lose).
    t_disp = sweeps * tile_plan.n_tiles * tpu.dispatch_overhead_s
    n = max(1, min(n_devices, tile_plan.leading))
    coll = 0
    if n > 1:
        coll = (sweeps * 2 * tile_plan.ghost * (n - 1)
                * tile_plan._per_slice * tile_plan.itemsize)
    return dataclasses.replace(
        base,
        t_compute=base.t_compute * amp / n,
        t_memory=base.t_memory * amp / n,
        flops=base.flops * amp,
        hbm_bytes=base.hbm_bytes * amp,
        t_host=host / tpu.host_bw / n,
        host_bytes=host,
        t_collective=(coll / tpu.ici_bw if coll
                      else base.t_collective),
        collective_bytes=coll if coll else base.collective_bytes,
        t_dispatch=t_disp,
        transfer_overlap=transfer_overlap)


def predict_gcells_per_s(plan: BlockPlan, n_steps: int, tpu: TpuSpec = V5E,
                         chips: int = 1,
                         read_amplification: float = 1.0) -> float:
    terms = stencil_roofline(plan, n_steps, tpu, chips, read_amplification)
    cell_updates = plan.cells * n_steps
    return cell_updates / terms.t_predicted / 1e9


def predict_gflops(plan: BlockPlan, n_steps: int, tpu: TpuSpec = V5E,
                   chips: int = 1, read_amplification: float = 1.0) -> float:
    """Useful GFLOP/s (thesis reports useful FLOPs, not redundant ones)."""
    terms = stencil_roofline(plan, n_steps, tpu, chips, read_amplification)
    return plan.useful_flops_per_sweep() * plan.sweeps(n_steps) \
        / terms.t_predicted / 1e9


def select_config(spec: StencilSpec, grid_shape, n_steps: int,
                  tpu: TpuSpec = V5E, top_k: int = 3,
                  read_amplification: float = 1.0,
                  vmem_budget: int | None = None,
                  n_devices: int = 1, batch: int = 1,
                  hbm_budget: int | None = None,
                  itemsize: int = 4) -> list[BlockPlan]:
    """The §5.4 pruning step: rank all legal (bx, bt) by predicted time.

    Returns the ``top_k`` fastest plans; only these need be compiled and
    measured (the thesis: 'minimize the number of configurations that
    need to be placed and routed'). With ``n_devices > 1`` the grid is
    sharded along its leading axis: plans whose deep halo does not fit
    one shard are illegal, and ranking includes the halo-exchange
    collective term plus the per-device slab recompute. ``batch``
    scales the work terms (B problems per dispatch) and the ranking
    charges each plan its modeled dispatch time, so on small grids —
    where launches, not the roofline, dominate — deeper ``bt`` (fewer
    launches) wins on merit.

    **HBM budget**: an in-core plan keeps the whole grid (plus output
    and every aux stream) resident, so no (bx, bt) choice can shrink
    its device working set — if that working set exceeds ``hbm_budget``
    (default ``tpu.hbm_bytes``), *no* in-core plan is legal and this
    raises, naming the out-of-core path as the remedy. This is the
    guarantee that ``select_config`` never returns a plan whose
    working set exceeds the device's HBM; ``kernels/autotune.py``
    catches the same condition up front and plans tiles instead.
    """
    hbm = hbm_budget if hbm_budget is not None else tpu.hbm_bytes
    resident = incore_resident_bytes(
        spec, tuple(grid_shape), itemsize=itemsize, batch=batch)
    if n_devices > 1:
        resident = -(-resident // n_devices)     # per-device shard
    if resident > hbm:
        raise ValueError(
            f"in-core working set {resident} bytes of grid {grid_shape}"
            f"{f' x batch {batch}' if batch > 1 else ''} exceeds the "
            f"HBM budget {hbm}: no (bx, bt) plan can fit it — route "
            f"through the out-of-core runner (repro.outofcore / "
            f"ops.stencil_run(..., hbm_budget=...)) instead")
    budget = vmem_budget if vmem_budget is not None else tpu.vmem_bytes
    if n_devices == 1:
        plans = candidate_plans(spec, grid_shape, vmem_budget=budget)
    else:
        # Sharded: the VMEM working set is the per-device slab
        # (shard + 2*halo of the leading axis), not the global grid,
        # and the deep halo must fit inside one shard.
        shard = shard_extent(grid_shape[0], n_devices)
        plans = []
        for p in candidate_plans(spec, grid_shape,
                                 vmem_budget=float("inf")):
            if p.halo > shard:
                continue
            slab_shape = (shard + 2 * p.halo,) + tuple(grid_shape[1:])
            slab = BlockPlan(spec, slab_shape, bx=p.bx, bt=p.bt,
                             itemsize=p.itemsize)
            if slab.vmem_bytes() <= budget:
                plans.append(p)
    if not plans:
        raise ValueError("no legal plan fits VMEM"
                         + (f" with its halo inside a {n_devices}-way shard"
                            if n_devices > 1 else ""))
    def _rank(p: BlockPlan) -> float:
        terms = stencil_roofline(p, n_steps, tpu, chips=n_devices,
                                 read_amplification=read_amplification,
                                 halo_exchange=n_devices > 1, batch=batch)
        return terms.t_predicted + terms.t_dispatch

    plans.sort(key=_rank)
    return plans[:top_k]


def modeled_power_efficiency(gflops: float, tpu: TpuSpec = V5E) -> float:
    """GFLOP/s per Watt, *modeled* from TDP-class constants (DESIGN.md §8)."""
    return gflops / tpu.tdp_watts


# ---------------------------------------------------------------------------
# Generic (non-stencil) roofline used by launch/roofline.py for the LM cells.
# ---------------------------------------------------------------------------

def lm_roofline(hlo_flops: float, hlo_bytes: float, collective_bytes: float,
                chips: int, tpu: TpuSpec = V5E,
                compute_dtype: str = "bf16") -> RooflineTerms:
    peak = tpu.peak_flops_bf16 if compute_dtype == "bf16" else tpu.peak_flops_f32
    return RooflineTerms(
        t_compute=hlo_flops / (chips * peak),
        t_memory=hlo_bytes / (chips * tpu.hbm_bw),
        t_collective=collective_bytes / (chips * tpu.ici_bw * tpu.ici_links),
        flops=hlo_flops, hbm_bytes=hlo_bytes,
        collective_bytes=collective_bytes)


def model_flops_train(n_params_active: float, tokens: float) -> float:
    """MODEL_FLOPS = 6 * N_active * D (per assignment §Roofline)."""
    return 6.0 * n_params_active * tokens


def model_flops_decode(n_params_active: float, tokens: float) -> float:
    """Decode is forward-only: 2 * N_active * D."""
    return 2.0 * n_params_active * tokens
