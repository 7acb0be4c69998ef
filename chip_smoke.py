"""Smoke-run the stencil system on a TPU at the thesis's problem sizes.

Drives the main path once through the entry points a user calls
(``ops.stencil_run`` with the autotuner resolving the plan,
``apps.hotspot``, the out-of-core runner, ``StencilService``), checks
every result against the jnp reference (``kernels/ref.py``) on the chip
under the float32 policy of docs/portability.md (|got - want| <=
3e-5 + 3e-5 |want| per cell), and prints one JSON line per phase:

  python chip_smoke.py             # one chip, phases a-e
  python chip_smoke.py --chips 4   # four chips: the deep-halo sharded runner

One chip:
  a  2D diffusion, star r1 and r4, f32 8192^2 (256 MiB), 64 steps
  b  3D diffusion, star r1 and r4, f32 512^3 (512 MiB), 64 steps
  c  Rodinia Hotspot 8192^2 (clamp boundary, power as a source operand)
  d  phase a's r1 problem out-of-core under a small HBM budget, once per
     pipeline ("host" loop, in-kernel DMA "kernel")
  e  StencilService(check=True) serving Hotspot requests at 1024^2, 512^2
Four chips: ``ops.stencil_run(n_devices=4)`` on phase a's r1 and phase
b's r1 problems, each against the one-chip run on ``devices[0]``.

Each phase line gives the device kind, the plan (bx, bt, variant) and
where it came from (measured / cache / model), the compile seconds (the
first call less a warm one), one warm run timed to
``block_until_ready``, and the max abs error. These are one smoke run,
not benchmark numbers. The last line is ``{"ok": true, "device":
{...}}``. Without a TPU, or when a phase fails, the script exits
non-zero and prints no such line. Inputs are made from fixed seeds.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
TOL = 3e-5                # rtol = atol, docs/portability.md (float32)
N_STEPS = 64
GRID_2D = (8192, 8192)
GRID_3D = (512, 512, 512)
OOC_BUDGET = 192 << 20    # HBM budget that streams GRID_2D in >= 4 tiles
SERVE_SIZES = (1024, 1024, 1024, 1024, 512, 512, 512, 512)
SERVE_STEPS = 16


class PhaseFailed(SystemExit):
    def __init__(self, msg: str):
        super().__init__(f"chip_smoke: FAILED: {msg}")


def require(cond, msg: str) -> None:
    if not cond:
        raise PhaseFailed(msg)


def report(phase: str, **fields) -> None:
    import jax
    print(json.dumps({"phase": phase,
                      "device_kind": jax.devices()[0].device_kind,
                      **fields}), flush=True)


def timed(fn):
    """(result, compile seconds, warm seconds): the first call less a
    warm one, then the warm call, both ending in block_until_ready."""
    import jax
    t0 = time.perf_counter()
    jax.block_until_ready(fn())
    first = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    warm = time.perf_counter() - t0
    return out, first - warm, warm


def max_error(got, want, device=None):
    """Max abs error and whether every cell is within tolerance,
    computed on ``device`` (default: where ``want`` lives)."""
    import jax
    import jax.numpy as jnp
    if device is not None:
        got, want = jax.device_put(got, device), jax.device_put(want, device)
    d = jnp.abs(jnp.asarray(got) - want)
    return float(jnp.max(d)), bool(jnp.all(d <= TOL + TOL * jnp.abs(want)))


def plan_fields(tuned) -> dict:
    return {"bx": tuned.bx, "bt": tuned.bt, "variant": tuned.variant,
            "plan_source": tuned.source}


def phase_incore(name, spec, shape, n_steps, seed):
    """``n_steps`` of ``spec`` on a seeded grid through
    ``ops.stencil_run(backend="auto")``; returns the plan used."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import autotune, ops, ref
    x = jax.random.uniform(jax.random.PRNGKey(seed), shape, jnp.float32)
    tuned = autotune.plan(shape, spec, dtype=x.dtype, backend="auto",
                          n_steps=n_steps)
    out, compile_s, run_s = timed(
        lambda: ops.stencil_run(x, spec, n_steps, backend="auto"))
    err, ok = max_error(out, ref.stencil_multistep(x, spec, n_steps))
    report(name, grid=list(shape), n_steps=n_steps, **plan_fields(tuned),
           compile_s=compile_s, run_s=run_s, max_abs_err=err)
    require(ok, f"{name}: max abs error {err} outside tolerance {TOL}")
    return tuned


def phase_hotspot(shape, n_steps, seed):
    import jax
    from repro.apps import hotspot, problems
    from repro.kernels import autotune
    params = hotspot.HotspotParams()
    temp, power = problems.hotspot(jax.random.PRNGKey(seed), *shape)
    tuned = autotune.plan(shape, hotspot.spec_of(params), backend="auto",
                          n_steps=n_steps)
    out, compile_s, run_s = timed(
        lambda: hotspot.hotspot_blocked(temp, power, n_steps, p=params))
    err, ok = max_error(out, hotspot.hotspot_reference(temp, power,
                                                       n_steps, params))
    report("c.hotspot", grid=list(shape), n_steps=n_steps,
           **plan_fields(tuned), compile_s=compile_s, run_s=run_s,
           max_abs_err=err)
    require(ok, f"c.hotspot: max abs error {err} outside tolerance {TOL}")


def phase_outofcore(spec, shape, n_steps, budget, seed, min_tiles=4):
    """The same problem streamed from host memory under ``budget``,
    once per pipeline; the kernel pipeline must not fall back."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import autotune, ops, ref
    x = jax.random.uniform(jax.random.PRNGKey(seed), shape, jnp.float32)
    want = ref.stencil_multistep(x, spec, n_steps)
    for pipeline in ("host", "kernel"):
        tuned = autotune.plan(shape, spec, dtype=x.dtype, backend="auto",
                              n_steps=n_steps, hbm_budget=budget,
                              pipeline=pipeline)
        m: dict = {}
        out, compile_s, run_s = timed(lambda: ops.stencil_run(
            x, spec, n_steps, backend="auto", hbm_budget=budget,
            pipeline=pipeline, metrics=m))
        err, ok = max_error(out, want)
        report(f"d.outofcore.{pipeline}", grid=list(shape),
               n_steps=n_steps, hbm_budget=budget, **plan_fields(tuned),
               tile=m.get("tile"), n_tiles=m.get("n_tiles"),
               pipeline=m.get("pipeline"),
               fallback_reason=m.get("fallback_reason"),
               compile_s=compile_s, run_s=run_s, max_abs_err=err)
        require(m.get("pipeline") == pipeline,
                f"d.outofcore.{pipeline}: ran pipeline "
                f"{m.get('pipeline')!r} ({m.get('fallback_reason')})")
        require(not m.get("fallback_reason"),
                f"d.outofcore.{pipeline}: fallback "
                f"{m.get('fallback_reason')!r}")
        require(m["n_tiles"] >= min_tiles,
                f"d.outofcore.{pipeline}: {m['n_tiles']} tiles < "
                f"{min_tiles}")
        require(ok, f"d.outofcore.{pipeline}: max abs error {err} "
                    f"outside tolerance {TOL}")


def phase_serving(sizes, n_steps, seed):
    import jax
    from repro.apps import hotspot, problems
    from repro.kernels import autotune, ops
    from repro.serving import StencilRequest, StencilService
    params = hotspot.HotspotParams()
    spec = hotspot.spec_of(params)
    problems_ = [problems.hotspot(jax.random.PRNGKey(seed + i), n, n)
                 for i, n in enumerate(sizes)]
    reqs = [StencilRequest(uid=i, x=t, spec=spec, n_steps=n_steps,
                           aux={"power": hotspot.source_of(p, params)})
            for i, (t, p) in enumerate(problems_)]
    svc = StencilService(check=True)
    done, compile_s, run_s = timed(lambda: svc.run(reqs))
    require(len(done) == len(reqs), f"e.serving: {len(done)} of "
                                    f"{len(reqs)} requests completed")
    errors = [c for c in done if c.error is not None]
    require(not errors, f"e.serving: requests failed: "
                        f"{[(c.uid, repr(c.error)) for c in errors]}")
    require(svc.metrics["bucket_failures"] == 0,
            f"e.serving: {svc.metrics['bucket_failures']} buckets failed")
    worst = 0.0
    for c in done:
        t, p = problems_[c.uid]
        err, ok = max_error(c.result, hotspot.hotspot_reference(
            t, p, n_steps, params))
        worst = max(worst, err)
        require(ok, f"e.serving: request {c.uid} max abs error {err}")
    plans = {}
    for n in sorted(set(sizes)):
        batch = sizes.count(n)
        tuned = autotune.plan((batch, n, n), spec, backend="auto",
                              n_steps=n_steps)
        plans[f"{batch}x{n}x{n}"] = plan_fields(tuned)
    report("e.serving", requests=len(reqs), n_steps=n_steps, plans=plans,
           dispatches=svc.metrics["dispatches"], compile_s=compile_s,
           run_s=run_s, max_abs_err=worst,
           backend=ops.resolve_backend("auto"))


def phase_sharded(name, spec, shape, n_steps, seed, devices):
    """The deep-halo sharded runner on ``devices`` against the one-chip
    run on ``devices[0]``."""
    import jax
    import jax.numpy as jnp
    from repro.kernels import autotune, ops
    x = jax.device_put(jax.random.uniform(jax.random.PRNGKey(seed), shape,
                                          jnp.float32), devices[0])
    n = len(devices)
    one, c1, r1 = timed(lambda: ops.stencil_run(x, spec, n_steps,
                                                backend="auto"))
    tuned = autotune.plan(shape, spec, dtype=x.dtype, backend="auto",
                          n_steps=n_steps, n_devices=n)
    got, compile_s, run_s = timed(lambda: ops.stencil_run(
        x, spec, n_steps, backend="auto", n_devices=n, devices=devices))
    err, ok = max_error(got, one, device=devices[0])
    report(name, grid=list(shape), n_steps=n_steps, n_devices=n,
           **plan_fields(tuned), compile_s=compile_s, run_s=run_s,
           one_chip_run_s=r1, max_abs_err_vs_one_chip=err)
    require(ok, f"{name}: sharded vs one-chip max abs error {err} "
                f"outside tolerance {TOL}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded path and its one-chip "
                         "comparison")
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no src/repro next to {__file__}; run it from "
              f"a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    # What the tuner reads is built from this checkout and this run.
    os.environ["REPRO_AUTOTUNE_CACHE"] = str(ROOT / ".cache"
                                             / "chip_smoke_autotune.json")
    from repro.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{devices[0].platform!r}); nothing was run",
              file=sys.stderr)
        return 1
    from repro.core.stencil import diffusion
    from repro.kernels import ops
    require(ops.resolve_backend("auto") == "pallas",
            "backend 'auto' did not resolve to the compiled TPU kernels")
    print(json.dumps({"compile_cache": cache_dir,
                      "devices": [{"id": d.id, "kind": d.device_kind,
                                   "coords": list(d.coords),
                                   "core_on_chip": d.core_on_chip}
                                  for d in devices]}), flush=True)
    if args.chips == 4:
        require(len(devices) >= 4, f"--chips 4 needs 4 devices, JAX "
                                   f"sees {len(devices)}")
        devices = devices[:4]
        phase_sharded("4chip.2d_r1", diffusion(2, 1), GRID_2D, N_STEPS,
                      seed=1, devices=devices)
        phase_sharded("4chip.3d_r1", diffusion(3, 1), GRID_3D, N_STEPS,
                      seed=3, devices=devices)
    else:
        t2 = [phase_incore(f"a.2d_r{r}", diffusion(2, r), GRID_2D, N_STEPS,
                           seed=r) for r in (1, 4)]
        t3 = [phase_incore(f"b.3d_r{r}", diffusion(3, r), GRID_3D, N_STEPS,
                           seed=2 + r) for r in (1, 4)]
        require(max(t.bt for t in t2) >= 2 and max(t.bt for t in t3) >= 2,
                "no 2D or no 3D phase ran temporal blocking (bt >= 2)")
        phase_hotspot(GRID_2D, N_STEPS, seed=11)
        phase_outofcore(diffusion(2, 1), GRID_2D, N_STEPS, OOC_BUDGET,
                        seed=1)
        phase_serving(SERVE_SIZES, SERVE_STEPS, seed=100)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
