"""Public wrappers for the stencil engine + autotuner glue.

Backend dispatch:
  * ``"pallas"``     — compile the Pallas kernel for TPU (real hardware);
  * ``"interpret"``  — execute the Pallas kernel body in Python on CPU
                       (the validation mode used throughout this repo);
  * ``"reference"``  — the pure-jnp oracle (kernels/ref.py), i.e. the
                       thesis's "NDRange-like" data-parallel formulation;
  * ``"gpu"``        — compile the Pallas kernel through the Triton
                       lowering (GPU hosts only; 2D multioperand — see
                       docs/portability.md for the support matrix);
  * ``"auto"``       — pallas on TPU, gpu on a GPU host with the
                       Triton lowering, interpret elsewhere.

Blocking parameters: **one resolution rule for every entry point**
(``stencil_sweep``, ``stencil_run``, ``stencil_auto``): pass explicit
``bx``/``bt``/``variant``, or leave any of them ``None`` (the default)
to have ``kernels.autotune.plan`` resolve it (model prior -> measured
ground truth -> disk cache), device-count-aware. ``stencil_sweep`` used
to hard-default ``bx=256, bt=1`` and ignore ``n_devices``; it now
resolves and shards exactly like ``stencil_run``.

IR operands: ``aux`` maps every operand declared in ``spec.aux`` to a
same-shape grid; ``scalars`` carries per-step values for custom
updates (shape ``(bt, n_scalars)`` for one sweep, ``(n_steps,
n_scalars)`` for a run). The legacy ``source`` kwarg remains as an
undeclared source-role operand.

Multi-device: pass ``n_devices > 1`` to run through the deep-halo
sharded runner (``distributed/halo.py``) — the grid (and every aux
operand) is split along its leading axis and depth-``r*bt`` halos are
exchanged once per fused time block. The autotuner resolution becomes
device-count-aware. The ``reference`` backend ignores ``n_devices``
(the oracle is the single-device ground truth the sharded path is
tested against).

Batched execution: an ``x`` of rank ``spec.dims + 1`` is a ``[B,
*grid]`` batch of independent problems solved in one dispatch (the
batch is an outer Pallas grid dimension — see kernels/engine.py). All
aux/source operands must carry the same batch axis; ``scalars`` may be
shared ``(n_steps, k)`` or per-problem ``(B, n_steps, k)``. Mismatched
batch dims are rejected here, before anything reaches a kernel. With
``n_devices > 1`` the sharded runner splits the *batch* axis when it
divides the device count evenly (whole problems per device, no halo
traffic) and falls back to grid sharding otherwise.

Out-of-core execution: ``stencil_run``/``stencil_auto`` compare the
in-core working set against an HBM budget (``hbm_budget=``, default
the modeled device HBM) and auto-route over-budget problems through
the host-streaming tiled runner (``repro.outofcore`` —
docs/outofcore.md): host memory holds the grid, leading-axis tiles
with deep ghosts stream through the device, and the result comes back
as a host numpy array, bitwise-equal to the in-core engine.

Spans (``repro.spans``, recorded only under a profiler session):
``ops.stencil_run`` covers a whole run; inside it ``ops.plan`` covers
the blocking resolution and the out-of-core routing decision, and one
``ops.sweep`` per blocked sweep covers the engine call that enqueues it.
Each ``ops.sweep`` carries ``bt``, the spec's ``boundary`` and
``streams`` (the grids the kernel streams: the state, one pre-summed
source grid if any, one per coeff operand). A single-device sweep of the
2D revolving kernel also carries the kernel's register-strip geometry:
``strip`` (output rows per strip) and ``edge_strips`` (strips per tile
inside the grid's columns that take the boundary path; the rest skip
it).
"""
from __future__ import annotations

import jax

from repro import spans
from repro.core import blocking
from repro.core.blocking import BlockPlan
from repro.core.stencil import StencilSpec
from repro.kernels import ref as _ref
from repro.kernels.stencil2d import stencil2d as _stencil2d
from repro.kernels.stencil3d import stencil3d as _stencil3d


# ---------------------------------------------------------------------------
# Engine-dispatch accounting. Counts live here (host side), not inside
# jitted code — a counter in a kernel body would tick at trace time
# only. One tick per blocked engine dispatch issued by this module:
# a fused sweep, one fused program group, or one sharded/out-of-core
# blocked sweep (the out-of-core runner's per-tile fan-out is not
# counted; fused-vs-looped program comparisons stay apples-to-apples).
# ---------------------------------------------------------------------------

_DISPATCHES = 0


def reset_dispatch_count() -> None:
    global _DISPATCHES
    _DISPATCHES = 0


def dispatch_count() -> int:
    return _DISPATCHES


def _count_dispatch(n: int = 1) -> None:
    global _DISPATCHES
    _DISPATCHES += n


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _resolve(backend: str) -> str:
    """Resolve "auto" to the best compiled backend this host offers:
    ``pallas`` on TPU, ``gpu`` on a GPU host whose jax ships the
    Pallas/Triton lowering, ``interpret`` (the oracle) elsewhere."""
    if backend == "auto":
        if _on_tpu():
            return "pallas"
        from repro import compat
        if compat.platform() == "gpu" and compat.has_gpu_pallas():
            return "gpu"
        return "interpret"
    return backend


resolve_backend = _resolve


def backend_pairs() -> tuple[tuple[str, str], ...]:
    """(oracle, other) backend pairs differentially testable HERE.

    ``interpret`` — the Pallas kernel body executed in Python — is the
    ground-truth backend every other one is measured against
    (docs/portability.md):  the jit-compiled jnp ``reference`` is
    always runnable, ``pallas`` joins on a TPU host, ``gpu`` on a GPU
    host. ``tests/test_backends.py`` parametrizes its acceptance
    matrix over exactly this list, so the differential pass widens by
    itself on bigger hosts.
    """
    from repro import compat
    return tuple(("interpret", b) for b in compat.available_backends()
                 if b != "interpret")


def batch_of(x, spec: StencilSpec):
    """Batch size of ``x`` under ``spec``: ``None`` for a plain grid,
    ``B`` for a ``[B, *grid]`` batch, loud error for any other rank."""
    if x.ndim == spec.dims:
        return None
    if x.ndim == spec.dims + 1:
        return x.shape[0]
    raise ValueError(
        f"grid rank {x.ndim} matches neither spec.dims {spec.dims} nor "
        f"{spec.dims + 1} (a [B, *grid] batch) for spec {spec.name!r}")


def _validate_batch(x, spec: StencilSpec, aux, scalars, source):
    """Reject batch-dim mismatches on operands *before* the kernel.

    Without this, a forgotten batch axis on an aux operand surfaces as
    an opaque shape error from inside the engine (or worse, a rank
    error from ``jnp.pad``); every mismatch gets its own message here.
    """
    B = batch_of(x, spec)
    grid = x.shape[1:] if B is not None else x.shape
    operands = dict(aux) if aux else {}
    if source is not None:
        operands["source"] = source
    for name, a in operands.items():
        if B is not None:
            if a.ndim == spec.dims:
                raise ValueError(
                    f"operand {name!r} has shape {a.shape} but the grid "
                    f"is a batch of {B}: it is missing the batch axis "
                    f"(expected {(B,) + grid})")
            if a.ndim == spec.dims + 1 and a.shape[0] != B:
                raise ValueError(
                    f"operand {name!r} batch dim {a.shape[0]} != grid "
                    f"batch dim {B}")
        elif a.ndim == spec.dims + 1:
            raise ValueError(
                f"operand {name!r} has shape {a.shape} with a batch "
                f"axis, but the grid {x.shape} is unbatched")
    if scalars is not None and spec.n_scalars:
        sdim = jax.numpy.ndim(scalars)
        sshape = jax.numpy.shape(scalars)
        if B is not None and sdim == 3 and sshape[0] != B:
            raise ValueError(
                f"scalars batch dim {sshape[0]} != grid batch dim {B}")
        if B is None and sdim == 3:
            raise ValueError(
                f"scalars shape {sshape} is per-problem (rank 3), but "
                f"the grid {x.shape} is unbatched")
    return B


def _tslice(scalars, a: int, b: int):
    """Per-sweep time slice of shared ``(T, k)`` or per-problem
    ``(B, T, k)`` scalars."""
    return scalars[:, a:b] if scalars.ndim == 3 else scalars[a:b]


def resolve_blocking(x, spec, bx=None, bt=None, variant=None,
                     backend="interpret", n_steps=None, n_devices=1,
                     hbm_budget=None, extra_streams=0,
                     pipeline="host"):
    """Fill any None among (bx, bt, variant) from the autotuner.

    The **public resolve-once entry point**: apps and benchmarks that
    drive many ``stencil_run`` calls over one problem (srad_blocked's
    per-iteration sweeps, the rodinia suite's timed loops) call this
    once up front and pass the result explicitly, instead of paying a
    tuner resolution (and risking a mid-loop measurement race) per
    call. With ``bx`` and ``bt`` both explicit, no tuner runs and a
    None variant just takes the engine default — the tuner's variant
    choice is only meaningful alongside the (bx, bt) it was measured
    with. This is the single resolution path shared by
    ``stencil_sweep``, ``stencil_run`` and (via ``autotune.plan``)
    ``stencil_auto``. ``hbm_budget`` makes the resolution
    budget-aware: an over-budget problem ranks (bx, bt) by the
    out-of-core roofline (see ``kernels/autotune.py``);
    ``extra_streams`` counts caller-side operand grids (the legacy
    ``source=``) so the tuner sizes the problem the run will route.
    """
    if bx is not None and bt is not None:
        return bx, bt, variant if variant is not None else "revolving"
    from repro.kernels import autotune
    tuned = autotune.plan(x.shape, spec, dtype=x.dtype, backend=backend,
                          n_devices=n_devices, hbm_budget=hbm_budget,
                          extra_streams=extra_streams, pipeline=pipeline,
                          **({} if n_steps is None
                             else {"n_steps": n_steps}))
    return (bx if bx is not None else tuned.bx,
            bt if bt is not None else tuned.bt,
            variant if variant is not None else tuned.variant)


# Pre-PR-5 private name, kept for existing call sites.
_resolve_blocking = resolve_blocking


def _sweep_stats(spec: StencilSpec, bt: int, source) -> dict:
    """``ops.sweep`` stats of any sweep; ``streams`` is computed only if
    the span records."""
    return {"bt": bt, "boundary": spec.boundary,
            "streams": lambda: 1 + blocking.aux_streams(
                spec, source is not None)}


def _strip_stats(x, spec: StencilSpec, bx: int, bt: int,
                 variant: str) -> dict:
    """``ops.sweep`` stats of a sweep that runs the 2D revolving kernel
    (``core.blocking.strip_rows`` / ``edge_strips``), computed only if
    the span records."""
    if spec.dims != 2 or variant != "revolving":
        return {}
    h, item, halo = x.shape[-2], x.dtype.itemsize, spec.halo(bt)
    rows = blocking.round_up(h, blocking._SUBLANE[item])

    def strip():
        return blocking.strip_rows(bx, halo, rows, item)

    return {"strip": strip,
            "edge_strips": lambda: blocking.edge_strips(
                rows, strip(), halo, 0, h, item)}


def stencil_sweep(x: jax.Array, spec: StencilSpec, bx: int | None = None,
                  bt: int | None = None, backend: str = "auto",
                  variant: str | None = None,
                  source: jax.Array | None = None, aux=None,
                  scalars: jax.Array | None = None,
                  n_devices: int | None = None, devices=None,
                  overlap: bool = True) -> jax.Array:
    """One blocked pass = ``bt`` fused time steps over the whole grid.

    ``bx``/``bt``/``variant`` default to the autotuner's (device-count-
    aware) choice, exactly like ``stencil_run``. ``scalars``: ``(bt,
    n_scalars)`` per-step values for custom updates (``(B, bt,
    n_scalars)`` for per-problem values over a batched grid).
    ``n_devices > 1`` runs the sweep through the deep-halo sharded
    runner (one halo exchange for this block).
    """
    backend = _resolve(backend)
    nd = 1 if n_devices is None else n_devices
    _validate_batch(x, spec, aux, scalars, source)
    bx, bt, variant = resolve_blocking(
        x, spec, bx, bt, variant, backend, n_devices=nd,
        extra_streams=int(source is not None))
    if backend == "reference":
        return _ref.stencil_multistep(x, spec, bt, source, aux=aux,
                                      scalars=scalars)
    if nd > 1:
        if backend == "gpu":
            raise NotImplementedError(
                "the deep-halo sharded runner is not wired to the 'gpu' "
                "backend yet: shard_map + Triton-lowered pallas_call is "
                "untested here. Run the sharded path on 'pallas' or "
                "'interpret', or the gpu backend on one device.")
        from repro.distributed import halo
        _count_dispatch()
        with spans.span("ops.sweep", **_sweep_stats(spec, bt, source)):
            return halo.stencil_run_sharded(
                x, spec, bt, n_devices=nd, bx=bx, bt=bt, variant=variant,
                backend=backend, source=source, aux=aux, scalars=scalars,
                devices=devices, overlap=overlap)
    fn = _stencil2d if spec.dims == 2 else _stencil3d
    _count_dispatch()
    with spans.span("ops.sweep", **_sweep_stats(spec, bt, source),
                    **_strip_stats(x, spec, bx, bt, variant)):
        return fn(x, spec, bx=bx, bt=bt, variant=variant, backend=backend,
                  source=source, aux=aux, scalars=scalars)


def stencil_run(x: jax.Array, spec: StencilSpec, n_steps: int,
                bx: int | None = None, bt: int | None = None,
                backend: str = "auto", variant: str | None = None,
                source: jax.Array | None = None, aux=None,
                scalars: jax.Array | None = None,
                n_devices: int | None = None, devices=None,
                overlap: bool = True,
                hbm_budget: int | None = None,
                pipeline: str = "host",
                metrics: dict | None = None) -> jax.Array:
    """``n_steps`` total time steps as ceil(n/bt) blocked sweeps.

    The trailing partial sweep runs with the remainder temporal degree so
    the result is exactly ``n_steps`` applications of the stencil.
    ``bx``/``bt``/``variant`` resolve through the autotuner when None
    (the same rule as ``stencil_sweep``). ``scalars``: ``(n_steps,
    n_scalars)`` per-step values, sliced per sweep.

    ``n_devices > 1`` routes the whole run through the deep-halo
    sharded runner (one halo exchange per ``bt``-step block; see
    ``distributed/halo.py``); ``overlap`` selects its interior/edge
    schedule that hides the exchange under interior compute.

    **Out-of-core**: when the in-core working set (grid + output +
    every aux stream) exceeds ``hbm_budget`` — default: the modeled
    device HBM, ``perf_model.V5E.hbm_bytes`` — the run auto-routes
    through the host-streaming tiled runner (``repro.outofcore``):
    the grid stays in host memory and leading-axis tiles with
    ``r*bt``-deep ghosts stream through the device, bitwise-equal to
    the in-core path for any tile size. The result is then a *host*
    (numpy) array — it may not fit on the device either. Pass a small
    explicit ``hbm_budget`` to force the route for testing. With
    ``n_devices > 1`` the routing predicate is per *ghost-charged
    shard*; when even a shard overflows, each device streams its own
    slab's tiles with tile-granular halo exchange (grid size bounded
    only by host RAM — see docs/outofcore.md). The ``reference``
    backend ignores the budget (the oracle already runs on the
    host). ``pipeline`` selects the out-of-core streaming mode
    (``"host"`` Python-loop double buffering, or ``"kernel"`` for the
    persistent in-kernel DMA pipeline with automatic host fallback —
    see docs/pipelining.md); it is ignored on in-core runs.
    ``metrics``, when a dict is passed, is filled by the out-of-core
    runner (pipeline used, fallback reason, tiles — see
    ``outofcore.stencil_run_outofcore``) if the run routes there.
    """
    with spans.span("ops.stencil_run", shape=x.shape, n_steps=n_steps):
        backend = _resolve(backend)
        nd = 1 if n_devices is None else n_devices
        B = _validate_batch(x, spec, aux, scalars, source)
        with spans.span("ops.plan"):
            bx, bt, variant = resolve_blocking(
                x, spec, bx, bt, variant, backend, n_steps=n_steps,
                n_devices=nd, hbm_budget=hbm_budget,
                extra_streams=int(source is not None), pipeline=pipeline)
            bt = min(bt, n_steps) if n_steps else bt
            routed = False
            if backend != "reference":
                from repro.outofcore import route_decision
                grid = x.shape[1:] if B is not None else x.shape
                # Per-device comparison: a sharded run holds ~1/nd of the
                # working set per device, so a grid that overflows one
                # device but fits nd shards keeps its in-core deep-halo path.
                routed, budget = route_decision(
                    spec, grid, x.dtype.itemsize, hbm_budget, batch=B or 1,
                    extra_streams=int(source is not None), n_devices=nd,
                    bt=bt)
        if routed:
            # nd > 1 composes: each device streams its own slab's
            # tiles, halos exchanged at tile granularity
            # (outofcore._stream_sharded) — no in-core mesh is built,
            # so the gpu shard_map gate below does not apply.
            from repro.outofcore import stencil_run_outofcore
            _count_dispatch(-(-n_steps // bt))
            return stencil_run_outofcore(
                x, spec, n_steps, bx=bx, bt=bt, variant=variant,
                backend=backend, hbm_budget=budget,
                source=source, aux=aux, scalars=scalars,
                pipeline=pipeline, n_devices=nd, devices=devices,
                metrics=metrics)
        if scalars is not None:
            import jax.numpy as jnp
            scalars = jnp.asarray(scalars, jnp.float32)
            if B is not None and scalars.ndim == 3:
                scalars = scalars.reshape(B, n_steps, -1)
            else:
                scalars = scalars.reshape(n_steps, -1)
        if nd > 1 and backend != "reference":
            if backend == "gpu":
                raise NotImplementedError(
                    "the deep-halo sharded runner is not wired to the 'gpu' "
                    "backend yet: shard_map + Triton-lowered pallas_call is "
                    "untested here. Run the sharded path on 'pallas' or "
                    "'interpret', or the gpu backend on one device.")
            from repro.distributed import halo
            full, rem = divmod(n_steps, bt)
            _count_dispatch(full + (1 if rem else 0))
            with spans.span("ops.sweep",
                            **_sweep_stats(spec, bt, source)):
                return halo.stencil_run_sharded(
                    x, spec, n_steps, n_devices=nd, bx=bx, bt=bt,
                    variant=variant, backend=backend, source=source,
                    aux=aux, scalars=scalars, devices=devices,
                    overlap=overlap)
        full, rem = divmod(n_steps, bt)
        done = 0
        for _ in range(full):
            x = stencil_sweep(x, spec, bx=bx, bt=bt, backend=backend,
                              variant=variant, source=source, aux=aux,
                              scalars=(_tslice(scalars, done, done + bt)
                                       if scalars is not None else None))
            done += bt
        if rem:
            x = stencil_sweep(x, spec, bx=bx, bt=rem, backend=backend,
                              variant=variant, source=source, aux=aux,
                              scalars=(_tslice(scalars, done, done + rem)
                                       if scalars is not None else None))
        return x


def stencil_program_run(x_or_fields, program, n_steps: int, *,
                        inputs=None, scalars=None,
                        bx: int | None = None, bt: int | None = None,
                        backend: str = "auto", variant: str | None = None,
                        n_devices: int | None = None, devices=None,
                        overlap: bool = True,
                        hbm_budget: int | None = None,
                        fuse: bool = True):
    """``n_steps`` program steps of a ``StencilProgram``.

    The program analog of ``stencil_run``, with the same backend /
    batch / ``n_devices`` / ``hbm_budget`` routing. Each program step
    applies every sweep once, in declaration order; maximal legal fuse
    groups (``program.fuse_groups()``) run as ONE engine dispatch each,
    and a program that fuses into a single group additionally uses
    temporal blocking (``bt`` program steps per dispatch). Multi-group
    programs dispatch with ``bt=1`` — their groups must alternate every
    step. ``fuse=False`` forces one dispatch per sweep per step (the
    benchmark baseline and the bitwise parity gate: both paths are
    exactly equal).

    ``x_or_fields``: a dict mapping every evolving field name to its
    grid (missing fields are zero-initialized), or a bare array for
    single-field programs. The result has the same form. ``inputs``:
    dict of step-constant program inputs. ``scalars``: dict mapping a
    sweep name to its ``(n_steps, n_scalars)`` per-step values (or
    per-problem ``(B, n_steps, n_scalars)`` over a batch).

    One shared autotuned plan covers the whole program: ``bx``/``bt``/
    ``variant`` resolve through ``autotune.plan`` with the program's
    cache token as the key head (cache schema v7).
    """
    import numpy as np
    import jax.numpy as jnp
    from repro.core.stencil import StencilProgram

    if not isinstance(program, StencilProgram):
        raise TypeError(f"stencil_program_run needs a StencilProgram, "
                        f"got {type(program).__name__}")
    bare = not isinstance(x_or_fields, dict)
    if bare:
        if program.n_fields != 1:
            raise ValueError(
                f"program {program.name!r} evolves fields "
                f"{list(program.fields)}; pass a dict of grids")
        fields = {program.fields[0]: x_or_fields}
    else:
        fields = dict(x_or_fields)
    unknown = [f for f in fields if f not in program.fields]
    if unknown:
        raise ValueError(f"unknown fields {unknown} for program "
                         f"{program.name!r} (evolves: "
                         f"{list(program.fields)})")
    if not fields:
        raise ValueError("at least one evolving field must be provided")
    primary = next(iter(fields.values()))
    dims = program.dims
    if primary.ndim not in (dims, dims + 1):
        raise ValueError(
            f"field rank {primary.ndim} matches neither program dims "
            f"{dims} nor {dims + 1} (a [B, *grid] batch)")
    B = primary.shape[0] if primary.ndim == dims + 1 else None
    for f in program.fields:
        if f not in fields:
            fields[f] = jnp.zeros_like(primary)
    for n, a in fields.items():
        if a.shape != primary.shape:
            raise ValueError(f"field {n!r} shape {a.shape} != "
                             f"{primary.shape}")
    inputs = dict(inputs) if inputs else {}
    missing = [n for n in program.input_names if n not in inputs]
    if missing:
        raise ValueError(f"program {program.name!r} requires inputs "
                         f"{missing}")
    extra = [n for n in inputs if n not in program.input_names]
    if extra:
        raise ValueError(f"unknown inputs {extra} for program "
                         f"{program.name!r} (declared: "
                         f"{list(program.input_names)})")
    for n, a in inputs.items():
        if a.shape != primary.shape:
            raise ValueError(f"input {n!r} shape {a.shape} != "
                             f"{primary.shape}")
    scalars = dict(scalars) if scalars else {}
    by_name = {s.name: s for s in program.sweeps}
    for n in scalars:
        if n not in by_name:
            raise ValueError(f"scalars for unknown sweep {n!r} "
                             f"(sweeps: {list(by_name)})")
        if not by_name[n].spec.n_scalars:
            raise ValueError(f"sweep {n!r} takes no scalars")
    need = [s.name for s in program.sweeps
            if s.spec.n_scalars and s.name not in scalars]
    if need:
        raise ValueError(f"program {program.name!r} requires scalars "
                         f"for sweeps {need}")
    norm = {}
    for n, v in scalars.items():
        v = jnp.asarray(v, jnp.float32)
        k = by_name[n].spec.n_scalars
        if B is not None and v.ndim == 3:
            v = v.reshape(B, n_steps, k)
        else:
            v = v.reshape(n_steps, k)
        norm[n] = v
    scalars = norm

    backend = _resolve(backend)
    if backend == "reference":
        out = _ref.stencil_program_multistep(
            fields, program, n_steps, inputs=inputs or None,
            scalars=scalars or None)
        return out[program.fields[0]] if bare else out

    nd = 1 if n_devices is None else n_devices
    if bx is None or bt is None or variant is None:
        from repro.kernels import autotune
        tuned = autotune.plan(primary.shape, program, dtype=primary.dtype,
                              backend=backend, n_steps=n_steps,
                              n_devices=nd, hbm_budget=hbm_budget)
        bx = bx if bx is not None else tuned.bx
        bt = bt if bt is not None else tuned.bt
        variant = variant if variant is not None else tuned.variant
    groups = (program.fuse_groups() if fuse
              else tuple((s,) for s in program.sweeps))
    if len(groups) > 1:
        bt = 1       # groups must alternate every program step
    bt = max(1, min(bt, n_steps) if n_steps else bt)

    from repro.outofcore import route_decision
    grid = primary.shape[1:] if B is not None else primary.shape
    routed, budget = route_decision(
        program.plan_proxy(), grid, primary.dtype.itemsize, hbm_budget,
        batch=B or 1, n_devices=nd)
    if routed:
        # Host-streaming fallback: one out-of-core blocked sweep per
        # sweep per program step; evolving fields ride as aux operands
        # and live as host numpy arrays between sweeps. nd > 1
        # composes per sweep: every sweep streams each device's slab
        # tiles with tile-granular halo exchange.
        from repro.outofcore import stencil_run_outofcore
        fields = {n: np.asarray(a) for n, a in fields.items()}
        for t in range(n_steps):
            for s in program.sweeps:
                aux = {op.name: (fields[op.name] if op.name in fields
                                 else inputs[op.name])
                       for op in s.spec.aux}
                scal = None
                if s.spec.n_scalars:
                    scal = _tslice(scalars[s.name], t, t + 1)
                _count_dispatch()
                fields[s.field] = stencil_run_outofcore(
                    fields[s.field], s.spec, 1, bx=bx, bt=1,
                    variant=variant, backend=backend,
                    hbm_budget=budget, aux=aux or None, scalars=scal,
                    n_devices=nd, devices=devices)
        return fields[program.fields[0]] if bare else fields

    if nd > 1:
        if backend == "gpu":
            raise NotImplementedError(
                "the deep-halo sharded runner is not wired to the 'gpu' "
                "backend yet: shard_map + Triton-lowered pallas_call is "
                "untested here. Run the sharded path on 'pallas' or "
                "'interpret', or the gpu backend on one device.")
        from repro.distributed import halo
        _count_dispatch(sum(-(-n_steps // bt) for _ in groups))
        out = halo.stencil_program_run_sharded(
            fields, program, n_steps, n_devices=nd, bx=bx, bt=bt,
            variant=variant, backend=backend, inputs=inputs or None,
            scalars=scalars or None, devices=devices, overlap=overlap,
            fuse=fuse)
        return out[program.fields[0]] if bare else out

    from repro.kernels import engine
    full, rem = divmod(n_steps, bt)
    schedule = [bt] * full + ([rem] if rem else [])
    done = 0
    for bts in schedule:
        for group in groups:
            specs = tuple(s.spec for s in group)
            fname = group[0].field
            aux = {}
            for s in group:
                for op in s.spec.aux:
                    aux[op.name] = (fields[op.name]
                                    if op.name in fields
                                    else inputs[op.name])
            scal = tuple(
                (_tslice(scalars[s.name], done, done + bts)
                 if s.spec.n_scalars else None)
                for s in group)
            _count_dispatch()
            fields[fname] = engine.stencil_call_program(
                fields[fname], specs, bx=bx, bt=bts, variant=variant,
                backend=backend, aux=aux or None,
                scalars=(scal if any(c is not None for c in scal)
                         else None))
        done += bts
    return fields[program.fields[0]] if bare else fields


def stencil_auto(x: jax.Array, spec: StencilSpec, n_steps: int,
                 backend: str = "auto", source: jax.Array | None = None,
                 aux=None, scalars: jax.Array | None = None,
                 n_devices: int | None = None,
                 hbm_budget: int | None = None, **tune_kw):
    """Autotuned end-to-end run; returns (result, TunedPlan).

    ``hbm_budget`` flows into both the tuner (budget-aware ranking,
    ``TunedPlan.tile``) and the run itself (out-of-core auto-routing,
    same rule as ``stencil_run``).
    """
    from repro.kernels import autotune
    backend = _resolve(backend)
    nd = 1 if n_devices is None else n_devices
    tuned = autotune.plan(x.shape, spec, dtype=x.dtype, backend=backend,
                          n_steps=n_steps, n_devices=nd,
                          hbm_budget=hbm_budget,
                          extra_streams=int(source is not None),
                          **tune_kw)
    # The run must route against the same *effective* budget the tuner
    # sized with: a custom tpu= in tune_kw changes the default, and
    # handing the raw None to stencil_run would compare against
    # V5E.hbm_bytes instead — dropping the tile the tuner just ranked.
    if hbm_budget is None and "tpu" in tune_kw:
        hbm_budget = tune_kw["tpu"].hbm_bytes
    out = stencil_run(x, spec, n_steps, bx=tuned.bx, bt=tuned.bt,
                      backend=backend, variant=tuned.variant,
                      source=source, aux=aux, scalars=scalars,
                      n_devices=nd, hbm_budget=hbm_budget)
    return out, tuned


def plan_for(x: jax.Array, spec: StencilSpec, bx: int, bt: int) -> BlockPlan:
    return BlockPlan(spec, x.shape, bx=bx, bt=bt,
                     itemsize=x.dtype.itemsize)
