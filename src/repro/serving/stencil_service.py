"""Stencil serving front-end: bucketed, batched, asynchronous dispatch.

The paper's accelerator wins by *keeping the pipeline full* — and a
device that solves one small grid per launch is mostly idle between
launches. This service is the stencil-side instance of the
slot/continuous-batching pattern of ``serving/engine.py``: requests
are the in-flight items, a bucket is the lockstep batch, and the
batched engine dispatch (``kernels/engine.py``'s leading batch axis)
is the II=1 steady state the service works to keep saturated.

Lifecycle (``docs/serving.md`` has the full walk-through):

  1. **submit** — clients enqueue ``StencilRequest``s (a grid, a
     ``StencilSpec``, ``n_steps``, optional aux operands / per-step
     scalars). Nothing runs yet.
  2. **group** — at ``flush()`` the queue is grouped by *compilation
     key*: (spec, grid shape, dtype, n_steps, aux signature, scalars
     signature). Problems in one group are bit-identical work modulo
     data, so they can share one compiled batched program.
  3. **bucket** — each group is cut into batches and padded up to a
     power-of-two ``<= max_batch``. Bucketing bounds recompilation:
     any request volume compiles at most ``log2(max_batch) + 1``
     distinct batch sizes per group, instead of one program per
     distinct B ever seen.
  4. **dispatch** — every bucket becomes one batched
     ``ops.stencil_run`` call through a per-(key, bucket) jitted
     dispatcher. Dispatches are launched back-to-back *without
     blocking* (JAX's async dispatch): all buckets are in flight
     before the first result is read back. On TPU the batch buffer is
     donated (``donate_argnums``) so the device can reuse it for the
     output; on CPU/interpret donation is a no-op and is skipped to
     avoid the XLA warning.
  5. **complete** — results are unstacked and returned per request
     (padding rows are dropped). **Exactness guarantee**: the batched
     engine is bitwise-identical per problem to a solo run (the batch
     axis is an outer grid dimension; tests assert equality), so a
     served result never differs from the unbatched one. ``check=True``
     re-verifies that per request, for smoke tests.

``metrics`` counts dispatches, served/padding problem counts, failed
requests, and buckets that failed and were re-served solo
(``bucket_failures`` — even when every solo retry succeeds, so a
refused batched kernel is visible).

**Spans** (``repro.spans``, recorded only under a profiler session, on
the profiler's clock): ``service.flush`` covers a flush (stats
``requests``, ``buckets``); inside it ``service.group`` the grouping,
and per bucket ``service.stack`` the host stacking with padding,
``service.dispatch`` the dispatcher call (upload, enqueue, a compile on
a miss; stats ``grid``, ``bucket``, ``pad``, ``uids``),
``service.device_wait`` the wait for the bucket's result and
``service.to_host`` its copy to the host and unstacking. The
per-request fallback runs under ``service.solo``. How long the device
is idle, and what the host did meanwhile, comes from the trace.

**Error isolation**: a request whose dispatch raises — a mis-shaped
aux grid that joined a bucket (the key hashes aux *names*), a value
that trips an engine assert — fails ALONE. Its bucket re-dispatches
per request, the poisoned request's completion carries the exception
(``StencilCompletion.error``), every other request still gets its
result, and ``metrics["failed"]`` counts the casualties.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import spans
from repro.core.stencil import StencilProgram, StencilSpec
from repro.kernels import ops


def bucket_size(n: int, max_batch: int = 8) -> int:
    """Smallest power-of-two >= n, capped at ``max_batch``."""
    b = 1
    while b < min(n, max_batch):
        b *= 2
    return min(b, max_batch)


@dataclasses.dataclass
class StencilRequest:
    """One client problem: ``n_steps`` of ``spec`` over grid ``x``.

    Exactly one of ``spec`` / ``program`` must be set. A ``program``
    request runs a whole ``StencilProgram`` (single evolving field,
    no per-sweep scalars); its ``aux`` dict supplies the program's
    step-constant inputs.
    """

    uid: int
    x: jax.Array
    spec: Optional[StencilSpec] = None
    n_steps: int = 1
    aux: Optional[Dict[str, jax.Array]] = None
    scalars: Optional[jax.Array] = None      # (n_steps, spec.n_scalars)
    program: Optional[StencilProgram] = None


@dataclasses.dataclass
class StencilCompletion:
    uid: int
    result: Optional[np.ndarray]  # host-side: each bucket materializes
    # once. None iff this request failed (then ``error`` says why).
    bucket: int          # batch rows in the dispatch that served it
    padded: int          # how many of those rows were padding
    # The exception this request's dispatch raised, or None on success.
    # A failed request fails ALONE: its bucket-mates re-dispatch solo
    # and still complete (see flush()).
    error: Optional[Exception] = None


class StencilService:
    """Bucketed batched stencil execution with solo-run exactness.

    ``max_batch`` caps the bucket (and therefore compiled batch) size;
    ``backend`` follows ``kernels.ops`` dispatch ("auto" = pallas on
    TPU, interpret elsewhere); explicit ``bx``/``bt``/``variant``
    bypass the autotuner, otherwise each compilation key resolves its
    blocking once through ``autotune.plan`` (batch-aware cache).
    ``check=True`` re-runs every request solo and asserts equality —
    the smoke suite's parity gate, not a production mode.

    Buckets whose in-core working set exceeds ``hbm_budget`` (default:
    the modeled device HBM) are **served out-of-core** instead of
    being rejected: the dispatch routes through the host-streaming
    tiled runner (``repro.outofcore``), which is bitwise-equal to the
    in-core engine — so ``check=True`` passes unchanged and clients
    cannot tell the difference beyond latency.
    ``metrics["outofcore_dispatches"]`` counts such buckets.

    With ``n_devices > 1`` an oversized bucket additionally **shards**:
    each device streams its slab of the leading axis through the same
    out-of-core runner (tile-granular halo exchange between slabs), so
    the serveable grid is bounded by aggregate host RAM rather than a
    single device's HBM — still bitwise-equal to the solo in-core run.
    """

    def __init__(self, *, max_batch: int = 8, backend: str = "auto",
                 bx: Optional[int] = None, bt: Optional[int] = None,
                 variant: Optional[str] = None, check: bool = False,
                 hbm_budget: Optional[int] = None,
                 n_devices: Optional[int] = None):
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        self.max_batch = max_batch
        self.backend = ops.resolve_backend(backend)
        self._blocking = (bx, bt, variant)
        self.check = check
        # Device HBM available to one bucket (None: the modeled device
        # HBM, perf_model.V5E.hbm_bytes). Buckets whose in-core working
        # set exceeds it are served through the out-of-core tiled
        # runner instead of being rejected — huge simulation requests
        # succeed, just at host-streaming bandwidth (docs/outofcore.md).
        self.hbm_budget = hbm_budget
        # Devices available to one bucket (None/1: solo). Oversized
        # buckets shard: each device owns a slab of the leading axis
        # and streams its tiles through the out-of-core runner, so the
        # serveable grid is bounded by host RAM, not one device's HBM.
        self.n_devices = n_devices
        self._queue: List[StencilRequest] = []
        # (key, bucket) -> jitted dispatcher; the bucket is part of the
        # cache key because B is a static shape (see docs/serving.md).
        self._dispatchers: dict = {}
        # (key, bucket) -> the (bx, bt, variant) the dispatcher runs
        # with — the check path must reuse it exactly, or the solo run
        # could legally differ in float association (different bt).
        self._resolved: dict = {}
        # (key, bucket) pairs that route out-of-core (for metrics).
        self._outofcore: set = set()
        self.metrics = {"dispatches": 0, "problems": 0, "pad_rows": 0,
                        "outofcore_dispatches": 0, "failed": 0,
                        "bucket_failures": 0}

    # ------------------------------------------------------------------
    def submit(self, req: StencilRequest) -> None:
        if (req.spec is None) == (req.program is None):
            raise ValueError(
                f"request {req.uid}: set exactly one of spec / program")
        if req.program is not None:
            if req.program.n_fields != 1:
                raise ValueError(
                    f"request {req.uid}: program {req.program.name!r} "
                    f"evolves {req.program.n_fields} fields; the service "
                    f"batches single-field programs only")
            if req.program.n_scalars:
                raise ValueError(
                    f"request {req.uid}: program {req.program.name!r} "
                    f"takes per-sweep scalars, which the service does "
                    f"not batch yet")
            if req.scalars is not None:
                raise ValueError(
                    f"request {req.uid}: program requests pass no "
                    f"request-level scalars")
        dims = (req.program or req.spec).dims
        if req.x.ndim != dims:
            raise ValueError(
                f"request {req.uid}: grid rank {req.x.ndim} != dims "
                f"{dims} (submit single problems; the service "
                f"does the batching)")
        self._queue.append(req)

    def run(self, requests: Optional[List[StencilRequest]] = None
            ) -> List[StencilCompletion]:
        """Submit ``requests`` (if given) and flush the whole queue."""
        for r in requests or ():
            self.submit(r)
        return self.flush()

    # ------------------------------------------------------------------
    def _key(self, r: StencilRequest):
        aux_sig = tuple(sorted(r.aux)) if r.aux else ()
        scal_sig = (None if r.scalars is None
                    else tuple(np.shape(r.scalars)))
        # r.x.dtype avoids materializing device arrays just for a key.
        dtype = getattr(r.x, "dtype", None)
        if dtype is None:
            dtype = np.asarray(r.x).dtype
        # The leading element is the whole program (or spec): two
        # programs that differ in ANY sweep hash differently, so they
        # can never share a bucket even on identical grids/dtypes.
        work = r.program if r.program is not None else r.spec
        return (work, tuple(np.shape(r.x)), str(dtype), int(r.n_steps),
                aux_sig, scal_sig)

    def _dispatcher(self, key, bucket: int):
        """The batched runner for one (compilation key, bucket): a
        jitted in-core dispatch, or — when the bucket's working set
        exceeds the HBM budget — the out-of-core host-streaming call
        (not jitted: it is a host loop that jits per slab inside)."""
        fn = self._dispatchers.get((key, bucket))
        if fn is not None:
            return fn
        work, shape, dtype, n_steps, aux_names, scal_sig = key
        program = work if isinstance(work, StencilProgram) else None
        bx, bt, variant = self._blocking
        if bx is None or bt is None:
            from repro.kernels import autotune
            tuned = autotune.plan((bucket,) + shape, work, dtype=dtype,
                                  backend=self.backend, n_steps=n_steps,
                                  hbm_budget=self.hbm_budget,
                                  n_devices=self.n_devices or 1)
            bx = bx if bx is not None else tuned.bx
            bt = bt if bt is not None else tuned.bt
            variant = variant if variant is not None else tuned.variant

        def call(xb, aux_b, scal_b):
            if program is not None:
                return ops.stencil_program_run(
                    xb, program, n_steps, bx=bx, bt=bt,
                    backend=self.backend, variant=variant,
                    inputs=aux_b or None, hbm_budget=self.hbm_budget,
                    n_devices=self.n_devices or 1)
            return ops.stencil_run(xb, work, n_steps, bx=bx, bt=bt,
                                   backend=self.backend, variant=variant,
                                   aux=aux_b or None, scalars=scal_b,
                                   hbm_budget=self.hbm_budget,
                                   n_devices=self.n_devices or 1)

        # The SAME predicate ops.stencil_run consults (a divergent copy
        # here could jit an "in-core" dispatcher whose traced run then
        # decides out-of-core and crashes converting a tracer to numpy).
        from repro.outofcore import route_decision
        routed, _ = route_decision(
            work if program is None else program.plan_proxy(), shape,
            np.dtype(dtype).itemsize, self.hbm_budget, batch=bucket,
            n_devices=self.n_devices or 1)
        if self.backend != "reference" and routed:
            # Oversized bucket: ops.stencil_run auto-routes it through
            # the out-of-core runner. The call stays un-jitted (its
            # tile loop runs on the host and returns a host array) and
            # undonated (the runner manages slab buffers itself).
            self._outofcore.add((key, bucket))
            fn = call
        else:
            # Donate the batch buffer so the device reuses it for the
            # output — meaningful on real hardware only; CPU donation
            # just warns and copies.
            donate = (0,) if self.backend == "pallas" else ()
            fn = jax.jit(call, donate_argnums=donate)
        self._dispatchers[(key, bucket)] = fn
        self._resolved[(key, bucket)] = (bx, bt, variant)
        return fn

    # ------------------------------------------------------------------
    def _solo_run(self, r: StencilRequest, bx, bt, variant):
        """One request, un-batched, through the same ops entry points
        the bucket dispatch uses (same blocking when known, so the
        result is bitwise-identical to the batched row it replaces)."""
        if r.program is not None:
            return ops.stencil_program_run(
                jnp.asarray(r.x), r.program, r.n_steps, bx=bx, bt=bt,
                variant=variant, backend=self.backend, inputs=r.aux,
                hbm_budget=self.hbm_budget,
                n_devices=self.n_devices or 1)
        return ops.stencil_run(
            jnp.asarray(r.x), r.spec, r.n_steps, bx=bx, bt=bt,
            variant=variant, backend=self.backend, aux=r.aux,
            scalars=r.scalars, hbm_budget=self.hbm_budget,
            n_devices=self.n_devices or 1)

    def _serve_solo(self, key, chunk, bucket: int
                    ) -> List[StencilCompletion]:
        """Per-request fallback after a bucket-level failure.

        The compilation key hashes aux *names*, not shapes — so one
        request with a mis-shaped aux grid (or a value that trips an
        engine assert) lands in a bucket of perfectly good work and
        fails the whole batched dispatch. Re-dispatching each request
        alone isolates the blast radius: the poisoned request completes
        with its ``error`` attached, every innocent bucket-mate still
        gets its result, and the accounting stays honest —
        ``metrics["failed"]`` counts casualties, ``problems`` only
        successes, ``dispatches`` the solo retries that actually ran.
        """
        out: List[StencilCompletion] = []
        bx, bt, variant = self._resolved.get((key, bucket),
                                             self._blocking)
        for r in chunk:
            try:
                with spans.span("service.solo", uid=r.uid):
                    res = np.asarray(jax.block_until_ready(
                        self._solo_run(r, bx, bt, variant)))
            except Exception as e:   # noqa: BLE001 — client data is
                # arbitrary; any per-request failure must stay local.
                self.metrics["failed"] += 1
                out.append(StencilCompletion(
                    uid=r.uid, result=None, bucket=1, padded=0,
                    error=e))
                continue
            self.metrics["dispatches"] += 1
            self.metrics["problems"] += 1
            out.append(StencilCompletion(uid=r.uid, result=res,
                                         bucket=1, padded=0))
        return out

    # ------------------------------------------------------------------
    def flush(self) -> List[StencilCompletion]:
        with spans.span("service.flush",
                        requests=len(self._queue)) as flush_span:
            # Group by compilation key, preserving arrival order within
            # a group (continuous admission: a group keeps filling its
            # current bucket until the queue runs dry or the bucket is
            # full, exactly like slots absorbing queued requests).
            with spans.span("service.group"):
                groups: dict = {}
                for r in self._queue:
                    groups.setdefault(self._key(r), []).append(r)
                self._queue.clear()

            done: List[StencilCompletion] = []
            in_flight = []       # (key, reqs, bucket, pad, result_future)
            n_buckets = 0
            for key, reqs in groups.items():
                for i in range(0, len(reqs), self.max_batch):
                    chunk = reqs[i: i + self.max_batch]
                    n_buckets += 1
                    out = self._dispatch(key, chunk, done)
                    if out is not None:
                        in_flight.append(out)
            flush_span.set(buckets=n_buckets)

            for key, chunk, bucket, pad, out in in_flight:
                self._complete(key, chunk, bucket, pad, out, done)
            return done

    def _dispatch(self, key, chunk, done: List[StencilCompletion]):
        """Stack one bucket on the host and dispatch it: the in-flight
        ``(key, chunk, bucket, pad, result)``, or None when it failed
        and its requests were served solo into ``done``."""
        bucket = bucket_size(len(chunk), self.max_batch)
        pad = bucket - len(chunk)
        try:
            with spans.span("service.stack"):
                # Stack on the *host* (one memcpy + one device upload):
                # jnp.stack over many small device buffers costs more
                # than the batched dispatch it feeds.
                xb = np.stack(
                    [np.asarray(r.x, np.dtype(key[2])) for r in chunk]
                    + [np.zeros(key[1], np.dtype(key[2]))] * pad)
                aux_b = None
                if chunk[0].aux:
                    aux_b = {
                        nm: np.stack(
                            [np.asarray(r.aux[nm], xb.dtype)
                             for r in chunk]
                            + [np.zeros(key[1], xb.dtype)] * pad)
                        for nm in chunk[0].aux}
                scal_b = None
                if chunk[0].scalars is not None:
                    scal_b = np.stack(
                        [np.asarray(r.scalars, np.float32).reshape(
                            r.n_steps, -1) for r in chunk]
                        + [np.zeros(
                            (chunk[0].n_steps, chunk[0].spec.n_scalars),
                            np.float32)] * pad)
            with spans.span("service.dispatch", grid=key[1],
                            bucket=bucket, pad=pad,
                            uids=lambda: [r.uid for r in chunk]):
                out = self._dispatcher(key, bucket)(xb, aux_b, scal_b)
        except Exception:   # noqa: BLE001 — one bad request (mis-shaped
            # aux, poisonous value) must not sink its bucket-mates:
            # re-dispatch each one alone.
            self.metrics["bucket_failures"] += 1
            done.extend(self._serve_solo(key, chunk, bucket))
            return None
        self.metrics["dispatches"] += 1
        if (key, bucket) in self._outofcore:
            self.metrics["outofcore_dispatches"] += 1
        self.metrics["pad_rows"] += pad
        return key, chunk, bucket, pad, out

    def _complete(self, key, chunk, bucket: int, pad: int, out,
                  done: List[StencilCompletion]) -> None:
        """Read one dispatched bucket back and unstack it into
        ``done``."""
        # One device->host materialization per bucket; slicing the
        # device array per request would instead dispatch one lazy
        # gather per request — quietly re-creating the per-problem
        # dispatch storm the batching removed.
        try:
            with spans.span("service.device_wait", bucket=bucket):
                out = jax.block_until_ready(out)
            with spans.span("service.to_host", bucket=bucket):
                out = np.asarray(out)
                served = [StencilCompletion(uid=r.uid, result=out[j],
                                            bucket=bucket, padded=pad)
                          for j, r in enumerate(chunk)]
        except Exception:   # noqa: BLE001 — async dispatch: a compiled
            # bucket's failure surfaces here, at readback.
            self.metrics["bucket_failures"] += 1
            done.extend(self._serve_solo(key, chunk, bucket))
            return
        if self.check:
            for r, c in zip(chunk, served):
                self._check_solo(key, bucket, r, c.result)
        done.extend(served)
        self.metrics["problems"] += len(chunk)

    def _check_solo(self, key, bucket: int, r: StencilRequest,
                    res) -> None:
        """``check=True``: the served row equals the request's solo run
        with the bucket's blocking, bit for bit."""
        bx, bt, variant = self._resolved[(key, bucket)]
        if r.program is not None:
            solo = ops.stencil_program_run(
                jnp.asarray(r.x), r.program, r.n_steps, bx=bx, bt=bt,
                variant=variant, backend=self.backend, inputs=r.aux)
        else:
            solo = ops.stencil_run(
                jnp.asarray(r.x), r.spec, r.n_steps, bx=bx, bt=bt,
                variant=variant, backend=self.backend, aux=r.aux,
                scalars=r.scalars)
        np.testing.assert_array_equal(
            np.asarray(res), np.asarray(solo),
            err_msg=f"served result for request {r.uid} diverged from "
                    f"its solo run")
