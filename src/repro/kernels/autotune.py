"""Stencil autotuner: model-pruned, measurement-grounded, disk-cached.

This is the thesis's §5.4 tuning flow made a first-class subsystem:

  1. **prior** — ``core.perf_model.select_config`` ranks all legal
     ``(bx, bt)`` under the VMEM budget by the three-term roofline model
     (the thesis's "prune before place-and-route" step);
  2. **ground truth** — the shortlisted candidates (crossed with the
     engine's kernel variants) are actually executed and timed; the
     empirically fastest per-time-step configuration wins (the thesis's
     "place and route only the shortlist, then measure");
  3. **cache** — *measured* winners persist on disk keyed by
     ``(spec, shape, dtype, backend, vmem_budget, tpu, n_devices)`` so
     the search runs once per problem class per machine
     (``REPRO_AUTOTUNE_CACHE`` overrides the location; default
     ``<checkout>/.cache/autotune.json``). Model-prior choices are never
     persisted: they are cheap to recompute and must not shadow a later
     forced measurement.

The search is **device-count-aware**: with ``n_devices > 1`` the grid
is sharded along its leading axis by ``distributed/halo.py``, so the
shortlist drops plans whose deep halo (``r * bt``) exceeds one shard,
the model ranks with the halo-exchange collective term and the
per-device slab recompute factor, and measured candidates are timed
through the sharded runner. Raising ``bt`` buys fewer exchanges at the
price of deeper (more redundant) halos; the crossover moves with the
device count, which is why ``n_devices`` is part of the cache key.

``plan(shape, spec)`` is the single entry point used by
``kernels.ops``, the Rodinia apps, and ``benchmarks/rodinia.py``.
"""
from __future__ import annotations

import dataclasses
import json
import logging
import os
import pathlib
import tempfile
import time
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.blocking import (BlockPlan, TilePlan, plan_tiles,
                                 shard_resident_bytes)
from repro.core.perf_model import (TpuSpec, V5E, device_spec_for,
                                   outofcore_roofline, select_config)
from repro.core.stencil import StencilSpec

_LOG = logging.getLogger("repro.autotune")

_CACHE_VERSION = 9   # v9: out-of-core × multi-device plans exist —
# an over-budget grid with n_devices > 1 now PLANS (per-device slab
# tiles, ghost-charged shard residency in the routing predicate)
# instead of raising, so the (nd, hb) key combination maps to a
# different ranking: v8 entries for sharded shapes were ranked under
# the bare-division threshold and must drop rather than be misread.
# v8: the out-of-core pipeline mode joins the key
# (|pl{host|kernel}) — the persistent in-kernel DMA pipeline
# (engine.stencil_call_persistent) amortizes dispatches over whole
# chunks, so its winning (bx, bt, tile) need not match the host loop's
# and the two modes must never share entries.
# v7: the device spec defaults per *backend*
# (``perf_model.device_spec_for``: pallas→V5E, interpret/reference→
# CPU_HOST, gpu→GPU_GENERIC) instead of V5E everywhere, so the spec
# name the key carries — and the ranking behind each winner — changed
# for every non-pallas entry. v6: multi-sweep StencilPrograms join the
# key space — a program entry's head is ``program.cache_token()``
# (every sweep's name/field/spec fields), so two programs over
# identical grids can never share a winner. v5 grew the HBM budget
# (|hb{n}) and winners may carry an out-of-core tile size ("tile");
# v4 added the batch size (|B{n}), v3 the IR fields (boundary, tap
# layout, aux-operand signature, n_scalars), v2 |nd{n_devices}. A
# version mismatch drops the whole file (with a logged
# found-vs-expected notice) — a v6 entry must never be *misread* as an
# answer ranked under the wrong device model (nor a v5 one for a
# program).
# Grids above this cell count are never timed on the host — the model
# prior picks alone (measuring a 8192^2 interpret-mode sweep on CPU
# would dwarf the run it is meant to speed up).
_MEASURE_CELL_LIMIT = 4 * 2 ** 20


@dataclasses.dataclass(frozen=True)
class TunedPlan:
    """A fully-resolved (bx, bt, variant) choice + its provenance."""

    bx: int
    bt: int
    variant: str
    source: str                      # "cache" | "measured" | "model"
    block_plan: BlockPlan
    # (bx, bt) -> best measured seconds per *time step* (empty when the
    # choice came from the model prior or the cache).
    timings: Dict[Tuple[int, int], float] = dataclasses.field(
        default_factory=dict, compare=False)
    # Out-of-core only: the leading-axis tile extent the plan was
    # ranked (and possibly measured) with — None for in-core plans.
    # ``ops.stencil_run`` re-derives the same tile deterministically
    # (``plan_tiles`` picks the largest fit), so this is provenance
    # plus a cache round-trip, not a second source of truth.
    tile: Optional[int] = None
    # (bx, bt, variant) -> why that measured candidate could not run
    # (e.g. the compiler refused its VMEM). Empty unless measured.
    failures: Dict[Tuple[int, int, str], str] = dataclasses.field(
        default_factory=dict, compare=False)


# ---------------------------------------------------------------------------
# Disk cache
# ---------------------------------------------------------------------------

def cache_path() -> pathlib.Path:
    env = os.environ.get("REPRO_AUTOTUNE_CACHE")
    if env:
        return pathlib.Path(env)
    from repro.compile_cache import CHECKOUT
    return CHECKOUT / ".cache" / "autotune.json"


# Parsed cache files memoized per path so resolving a plan in a loop
# does not pay a file read + JSON parse per iteration.
_MEM: dict = {}


def _load_cache() -> dict:
    path = str(cache_path())
    if path in _MEM:
        return _MEM[path]
    try:
        with open(path) as f:
            data = json.load(f)
    except OSError:
        data = {}                    # no cache file yet: a normal miss
    except ValueError as e:
        # A truncated write, editor mishap, or plain garbage must not
        # crash planning — the cache is an accelerator, never a
        # dependency. Same found-vs-expected discipline as the version
        # mismatch below: say what was found and what happens next.
        _LOG.warning(
            "autotune cache %s is not valid JSON (%s); found corrupt "
            "bytes where version %s entries were expected — ignoring "
            "the file, all plans re-tune on demand (benchmarks/run.py "
            "--retune forces a full re-search; see docs/autotuning.md)",
            path, e, _CACHE_VERSION)
        data = {}
    if not isinstance(data, dict):
        _LOG.warning(
            "autotune cache %s holds a JSON %s but this build expects "
            "a version %s object of winners; ignoring the file, all "
            "plans re-tune on demand (see docs/autotuning.md)",
            path, type(data).__name__, _CACHE_VERSION)
        data = {}
    if data and data.get("version") != _CACHE_VERSION:
        # Name both versions so "why did everything re-tune?" is
        # answerable from the log (docs/autotuning.md points --retune
        # guidance at this message).
        _LOG.warning(
            "autotune cache %s holds version %s but this build expects "
            "version %s; dropping all cached winners (they will "
            "re-measure on demand — benchmarks/run.py --retune forces "
            "a full re-search; see docs/autotuning.md)",
            path, data.get("version"), _CACHE_VERSION)
        data = {}
    # Entry-level hardening: a hand-edited file can hold the right
    # version yet malformed winners; dropping just those keeps every
    # intact entry serving.
    bad = [k for k, v in data.items()
           if k != "version" and not (isinstance(v, dict)
                                      and {"bx", "bt", "variant"}
                                      <= set(v))]
    if bad:
        _LOG.warning(
            "autotune cache %s: dropping %d malformed entr%s (expected "
            "{bx, bt, variant} objects): %s — the rest of the cache "
            "still serves; dropped keys re-tune on demand",
            path, len(bad), "y" if len(bad) == 1 else "ies", bad)
        for k in bad:
            del data[k]
    _MEM[path] = data
    return data


def _store_cache(data: dict) -> None:
    path = cache_path()
    _MEM[str(path)] = data
    try:
        path.parent.mkdir(parents=True, exist_ok=True)
        data["version"] = _CACHE_VERSION
        fd, tmp = tempfile.mkstemp(dir=path.parent, suffix=".tmp")
        with os.fdopen(fd, "w") as f:
            json.dump(data, f, indent=1, sort_keys=True)
        os.replace(tmp, path)
    except OSError:
        pass  # caching is best-effort; never fail the computation


def clear_cache() -> None:
    _MEM.pop(str(cache_path()), None)
    try:
        cache_path().unlink()
    except OSError:
        pass


def _key(spec, shape, dtype: str, backend: str,
         vmem_budget: int, tpu_name: str, n_devices: int = 1,
         batch: int = 1, hbm_budget: int | None = None,
         extra_streams: int = 0, head: str | None = None,
         pipeline: str = "host") -> str:
    sh = "x".join(str(s) for s in shape)
    # IR fields: boundary mode and tap layout change the kernel's work
    # per cell; the aux-operand signature and per-step scalar count
    # change its operand streaming — a tuned answer transfers to none
    # of them (docs/autotuning.md has the full schema). ``shape`` is
    # the *grid* shape; the batch size rides separately (|B{n}) because
    # a B-problem dispatch amortizes launches differently than a grid
    # B-times taller. ``hb`` is the HBM budget the plan was sized
    # against (device default when unset): a budget that forces
    # out-of-core tiling changes both the winning (bx, bt) and the
    # tile that rides with it, so budgets must never share entries.
    # A caller-side legacy ``source=`` grid streams exactly like a
    # declared source operand, so it appends a trailing "s" to the
    # aux signature rather than growing the schema another field.
    # ``head`` overrides the leading name field — StencilPrograms pass
    # their ``cache_token()`` (per-sweep name/field/spec fields), the
    # v6 schema extension. ``pipeline`` is the out-of-core streaming
    # mode the plan will run under (|pl{mode}, v8): the in-kernel DMA
    # pipeline amortizes dispatches over whole chunks, so its winner
    # must never answer for the host loop or vice versa.
    aux_sig = ",".join([op.role[0] for op in spec.aux]
                       + ["s"] * extra_streams) or "-"
    ir = (f"b{spec.boundary}|L{spec.layout}|ax{aux_sig}|"
          f"sc{spec.n_scalars}")
    name = head if head is not None else spec.name
    return (f"{name}|d{spec.dims}|r{spec.radius}|{ir}|{sh}|{dtype}|"
            f"{backend}|vm{vmem_budget}|{tpu_name}|B{batch}|"
            f"nd{n_devices}|hb{'-' if hbm_budget is None else hbm_budget}"
            f"|pl{pipeline}")


# ---------------------------------------------------------------------------
# Search
# ---------------------------------------------------------------------------

def _variants_for(spec: StencilSpec, backend: str) -> tuple[str, ...]:
    if backend == "reference":
        return ("revolving",)    # the oracle has no kernel variants
    from repro.kernels import engine
    return engine.variants_for(spec.dims)


def _measure(x, spec, plans, variants, backend, timer,
             repeats: int = 2, n_devices: int = 1,
             hbm_budget: int | None = None, extra_streams: int = 0,
             program=None, pipeline: str = "host"):
    """Time each (plan, variant); return (winner, winner_variant,
    {(bx, bt): best seconds-per-step}, {(bx, bt, variant): failure}).
    With ``n_devices > 1`` each candidate is one sweep of the sharded
    deep-halo runner (collective cost included); with an
    ``hbm_budget`` the run auto-routes through the out-of-core runner,
    so tile streaming cost is *in* the measurement. A candidate that
    cannot run — the compiler refuses it, too few devices are visible —
    leaves the race with its reason recorded and logged. With a
    ``program`` each candidate is ``p.bt`` program steps of
    ``ops.stencil_program_run``."""
    from repro.kernels import ops
    timings: Dict[Tuple[int, int], float] = {}
    failures: Dict[Tuple[int, int, str], str] = {}
    best = (None, None, float("inf"))
    # Specs that declare operands still race: synthesize zero aux grids
    # and unit scalars of the declared shapes (timing does not care
    # about the values, only the streaming and arithmetic they cost).
    # ``extra_streams`` likewise synthesizes the caller's legacy
    # ``source=`` grid, so its streaming cost is in the measurement.
    aux = {op.name: jnp.zeros_like(x) for op in spec.aux} or None
    src = jnp.zeros_like(x) if extra_streams else None
    for p in plans:
        for v in variants:
            def run(p=p, v=v):
                if program is not None:
                    fields = {f: x for f in program.fields}
                    ins = {n: x for n in program.input_names} or None
                    scals = {s.name: jnp.ones((p.bt, s.spec.n_scalars),
                                              jnp.float32)
                             for s in program.sweeps
                             if s.spec.n_scalars} or None
                    return jax.block_until_ready(
                        ops.stencil_program_run(
                            fields, program, p.bt, inputs=ins,
                            scalars=scals, bx=p.bx, bt=p.bt,
                            backend=backend, variant=v,
                            n_devices=n_devices,
                            hbm_budget=hbm_budget))
                scal = (jnp.ones((p.bt, spec.n_scalars), jnp.float32)
                        if spec.n_scalars else None)
                # jax.block_until_ready (not the method): the
                # out-of-core route returns a host numpy array.
                return jax.block_until_ready(ops.stencil_run(
                    x, spec, p.bt, bx=p.bx, bt=p.bt, backend=backend,
                    variant=v, source=src, aux=aux, scalars=scal,
                    n_devices=n_devices, hbm_budget=hbm_budget,
                    pipeline=pipeline))
            try:
                run()  # warm-up / compile
            except Exception as e:   # noqa: BLE001 - recorded, not hidden
                failures[(p.bx, p.bt, v)] = f"{type(e).__name__}: {e}"
                _LOG.warning("autotune candidate bx=%d bt=%d %s failed: "
                             "%s", p.bx, p.bt, v,
                             failures[(p.bx, p.bt, v)])
                continue
            dt = float("inf")
            for _ in range(repeats):
                t0 = timer()
                run()
                dt = min(dt, timer() - t0)
            per_step = dt / p.bt
            key = (p.bx, p.bt)
            timings[key] = min(timings.get(key, float("inf")), per_step)
            if per_step < best[2]:
                best = (p, v, per_step)
    return best[0], best[1], timings, failures


def plan(shape, spec, *, dtype="float32",
         backend: str = "auto", n_steps: int = 16, top_k: int = 3,
         measure: bool | None = None, use_cache: bool = True,
         vmem_budget: int | None = None, tpu: TpuSpec | None = None,
         n_devices: int = 1, hbm_budget: int | None = None,
         extra_streams: int = 0, pipeline: str = "host",
         timer: Callable[[], float] = time.perf_counter) -> TunedPlan:
    """Resolve the best (bx, bt, variant) for one stencil problem.

    ``measure=None`` (default) measures iff the grid is small enough to
    time on this host (< ``_MEASURE_CELL_LIMIT`` cells) and the backend
    is a real one — ``interpret`` is a correctness harness whose
    wall-clock says nothing about the compiled kernel, so it defaults
    to the model prior. ``False`` takes the model prior's top choice;
    ``True`` forces measurement.

    ``n_devices``: tune for the deep-halo sharded runner instead of a
    single device — the shortlist keeps only plans whose halo fits one
    shard, the model prior weighs halo redundancy against exchange
    frequency, and measurement times the sharded path.

    ``shape`` of rank ``spec.dims + 1`` is a ``[B, *grid]`` batch: the
    block plan covers one problem (the batch is an outer grid
    dimension, so (bx, bt) legality is per-problem), B joins the cache
    key, the model ranks with B-scaled work + amortized dispatch, and
    measurement times the actual batched dispatch. When the batch
    divides the device count the sharded runner splits the batch axis
    (whole problems per device, no halo traffic), so the model prices
    the per-device slice without a collective term.

    ``hbm_budget``: device HBM available to this problem (default
    ``tpu.hbm_bytes``). ``extra_streams`` counts caller-side operand
    grids the spec cannot see (the legacy ``source=`` kwarg) so the
    tuner sizes, measures and caches the same problem the run will
    actually route. When the in-core working set — grid + output +
    every operand — exceeds the budget, planning goes
    **budget-aware**: each
    VMEM-legal (bx, bt) is paired with the largest leading-axis tile
    whose double-buffered slab working set fits
    (``core.blocking.plan_tiles``) and ranked by the out-of-core
    roofline (``perf_model.outofcore_roofline``: on-device terms vs
    host-streaming term, overlap modeled by max) — deeper ``bt`` buys
    fewer host passes at the price of deeper ghosts, the out-of-core
    version of the thesis's temporal-blocking tradeoff. The winning
    tile rides on ``TunedPlan.tile`` and in the cache value; the
    budget joins the cache key (``|hb{n}``).

    ``spec`` may also be a ``core.stencil.StencilProgram``: the whole
    program shares ONE tuned plan. Planning then runs against the
    program's ``plan_proxy()`` (worst per-dispatch fused halo, summed
    work, union of resident operands), the cache key head is
    ``program.cache_token()`` (v6 schema), a multi-group program keeps
    only ``bt == 1`` plans (its groups must alternate every step), and
    measurement times ``ops.stencil_program_run``.
    """
    from repro.core.stencil import StencilProgram
    from repro.kernels import ops
    program = spec if isinstance(spec, StencilProgram) else None
    if program is not None:
        spec = program.plan_proxy()
    shape = tuple(int(s) for s in shape)
    if len(shape) not in (spec.dims, spec.dims + 1):
        raise ValueError(
            f"shape {shape} matches neither spec.dims {spec.dims} nor "
            f"{spec.dims + 1} (a [B, *grid] batch)")
    batch = shape[0] if len(shape) == spec.dims + 1 else None
    grid = shape[1:] if batch is not None else shape
    dtype = str(jnp.dtype(dtype).name)
    backend = ops.resolve_backend(backend)
    if tpu is None:
        # Per-backend device model (perf_model.DEVICE_SPECS): ranking
        # ratios — and the spec name inside the cache key — now match
        # the device the backend actually runs on. An explicit tpu=
        # still overrides, for what-if planning.
        tpu = device_spec_for(backend)
    budget = vmem_budget if vmem_budget is not None else tpu.vmem_bytes
    itemsize = jnp.dtype(dtype).itemsize
    hbm = hbm_budget if hbm_budget is not None else tpu.hbm_bytes
    # Ghost-charged per-device shard residency — the same rule as
    # outofcore.route_decision (at bt=1; the routing decision must
    # pre-date the bt choice being planned here): only a per-shard
    # overflow goes out-of-core. With n_devices > 1 that plans the
    # COMPOSED path — per-device slab streaming with tile-granular
    # halo exchange — instead of raising.
    outofcore = shard_resident_bytes(
        spec, grid, itemsize, n_devices=max(n_devices, 1),
        batch=batch or 1, extra_streams=extra_streams) > hbm
    # Keyed on the *effective* budget: plan(hbm_budget=None) and
    # plan(hbm_budget=tpu.hbm_bytes) are the same problem and must hit
    # the same entry — and an entry's meaning must not silently shift
    # if a TpuSpec's default HBM is ever revised.
    if pipeline not in ("host", "kernel"):
        raise ValueError(f"pipeline must be 'host' or 'kernel', got "
                         f"{pipeline!r}")
    key = _key(spec, grid, dtype, backend, budget, tpu.name, n_devices,
               batch or 1, hbm, extra_streams,
               head=None if program is None else program.cache_token(),
               pipeline=pipeline)

    def _mk(bx, bt, variant, source, timings=None, tile=None):
        bp = BlockPlan(spec, grid, bx=bx, bt=bt, itemsize=itemsize)
        return TunedPlan(bx=bx, bt=bt, variant=variant, source=source,
                         block_plan=bp, timings=timings or {},
                         tile=tile)

    cache = _load_cache() if use_cache else {}
    hit = cache.get(key)
    # A hit only satisfies a forced-measurement request if the cached
    # winner was itself measured (only measured winners are persisted,
    # but stay defensive about hand-edited cache files).
    if hit is not None and not (measure is True
                                and hit.get("source") != "measured"):
        return _mk(hit["bx"], hit["bt"], hit["variant"], "cache",
                   tile=hit.get("tile"))

    # Batch-axis sharding (B % nd == 0): each device owns whole
    # problems, so plans are ranked per-device — no halo constraint,
    # no collective term, B/nd problems per dispatch.
    eff_nd, eff_batch = n_devices, batch or 1
    if batch is not None and n_devices > 1 and batch % n_devices == 0:
        eff_nd, eff_batch = 1, batch // n_devices
    # A multi-group program can't temporally block a dispatch: its
    # groups must alternate every program step, so only bt == 1 plans
    # are executable and anything else would be tuned garbage.
    multi_group = program is not None and not program.fully_fused
    tiles: dict = {}
    if outofcore:
        # Budget-aware planning: every VMEM-legal (bx, bt) — not the
        # in-core top-k, whose deep-bt favorites may have ghosts no
        # budget-legal tile can carry — is paired with the largest
        # tile its slabs can afford under the budget and re-ranked by
        # the out-of-core roofline. The HBM guard inside select_config
        # is bypassed (2**62) because the whole point here is that the
        # grid does NOT fit.
        ranked = []
        # n_devices=1 into select_config: the composed runner streams
        # per-device slab tiles from HOST buffers, so there is no
        # halo-fits-shard constraint to prune by (and no in-core mesh
        # whose collective term select_config's own ranking would
        # price — the re-rank below charges it properly).
        for p in select_config(spec, grid, n_steps, tpu=tpu,
                               top_k=1 << 30,
                               vmem_budget=vmem_budget,
                               n_devices=1, batch=batch or 1,
                               hbm_budget=2 ** 62, itemsize=itemsize):
            if multi_group and p.bt != 1:
                continue
            try:
                tp = plan_tiles(spec, grid, bx=p.bx, bt=p.bt,
                                hbm_budget=hbm, itemsize=itemsize,
                                batch=batch or 1,
                                extra_streams=extra_streams)
            except ValueError:
                continue          # this bt's ghosts can't fit: drop it
            # outofcore ⇒ the resident set exceeds hbm (a ghost-charged
            # shard is never bigger than the whole grid), so plan_tiles
            # (same expression, same budget) can never report an
            # in-core fit here.
            assert tp is not None
            terms = outofcore_roofline(tp, n_steps, tpu=tpu,
                                       n_devices=n_devices)
            ranked.append((terms.t_outofcore + terms.t_dispatch, p, tp))
        if not ranked:
            raise ValueError(
                f"no (bx, bt, tile) fits hbm_budget={hbm} for grid "
                f"{grid} (spec {spec.name!r}); raise the budget")
        ranked.sort(key=lambda t: t[0])
        shortlist = [p for _, p, _ in ranked[:top_k]]
        tiles = {(tp.bx, tp.bt): tp.tile for _, _, tp in ranked}
    elif multi_group:
        shortlist = [p for p in select_config(
            spec, grid, n_steps, tpu=tpu, top_k=1 << 30,
            vmem_budget=vmem_budget, n_devices=eff_nd, batch=eff_batch,
            hbm_budget=hbm, itemsize=itemsize) if p.bt == 1][:top_k]
    else:
        shortlist = select_config(
            spec, grid, n_steps, tpu=tpu, top_k=top_k,
            vmem_budget=vmem_budget, n_devices=eff_nd, batch=eff_batch,
            hbm_budget=hbm, itemsize=itemsize)
    variants = _variants_for(spec, backend)

    cells = 1
    for s in shape:
        cells *= s
    do_measure = (backend != "interpret" and cells <= _MEASURE_CELL_LIMIT
                  if measure is None else measure)

    def _tile_of(p):
        return tiles.get((p.bx, p.bt)) if outofcore else None

    if do_measure:
        x = jnp.zeros(shape, jnp.dtype(dtype))
        # The *effective* budget (tpu default applied), not the raw
        # argument: measurement must route the same in-core/out-of-core
        # path the ranking priced, even for a non-default TpuSpec.
        winner, w_variant, timings, failures = _measure(
            x, spec, shortlist, variants, backend, timer,
            n_devices=n_devices, hbm_budget=hbm,
            extra_streams=extra_streams, program=program,
            pipeline=pipeline)
        if winner is None:
            raise RuntimeError(
                f"autotune: every candidate for {spec.name!r} on grid "
                f"{shape} ({backend}) failed to run:\n" + "\n".join(
                    f"  bx={bx} bt={bt} {v}: {why}"
                    for (bx, bt, v), why in failures.items()))
        tuned = dataclasses.replace(
            _mk(winner.bx, winner.bt, w_variant, "measured", timings,
                tile=_tile_of(winner)), failures=failures)
    else:
        tuned = _mk(shortlist[0].bx, shortlist[0].bt, variants[0],
                    "model", tile=_tile_of(shortlist[0]))

    # Only measured winners are worth persisting: the model prior is
    # cheap to recompute and caching it would shadow later measurement.
    if use_cache and tuned.source == "measured":
        cache = _load_cache()
        cache[key] = {"bx": tuned.bx, "bt": tuned.bt,
                      "variant": tuned.variant, "source": tuned.source}
        if tuned.tile is not None:
            cache[key]["tile"] = tuned.tile
        _store_cache(cache)
    return tuned
