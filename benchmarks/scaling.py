"""Weak/strong scaling of the deep-halo multi-device stencil runner.

For each device count the §5.4 model (device-aware: halo-fits-shard
pruning, collective term, slab-recompute factor — see
``core.perf_model.select_config``) picks the best (bx, bt) and reports:

  * **strong scaling** — fixed global grid split n ways: modeled
    speedup over n=1 plus the modeled *exposed-communication fraction*
    (how much of the halo ppermute the interior/edge overlap schedule
    cannot hide, ``RooflineTerms.exposed_collective_fraction``);
  * **weak scaling** — the per-device grid held constant while the
    global grid grows with n: modeled parallel efficiency;
  * **measured parity sweep** — when this host exposes more than one
    device (e.g. ``XLA_FLAGS=--xla_force_host_platform_device_count``),
    one small sharded sweep is actually executed and timed through
    ``ops.stencil_run(..., n_devices=...)`` and checked against the
    oracle, so the scaling table is anchored by at least one ground-
    truth cell;
  * **measured overlap accounting** — the same sharded problem runs
    overlapped and forced-serial (``overlap=False``), with the
    exchange-only collective cost timed separately; differencing
    yields the *measured* exposed-collective fraction
    (``measured_exposed_collective_fraction``, gated by
    ``tools/perf_gate.py`` — see ``docs/pipelining.md``). Skipped on
    single-device hosts.

``--smoke``/``--json`` mirror the other suites: smoke shrinks the
executed cells to CI size and the record lands in
``BENCH_scaling.json`` (the ``scaling`` suite of ``benchmarks/run.py``
keeps emitting the same rows).

Note how the tuner's chosen ``bt`` can *grow* with the device count:
deeper halos are the price of exchanging less often once the collective
term competes with HBM traffic — the central tradeoff of the deep-halo
design (arXiv:2002.05983's multi-FPGA spatial blocking, here with
temporal blocking preserved across the distribution boundary).
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import perf_model as pm
from repro.core.stencil import diffusion
from repro.kernels import ops, ref

_REPEATS = 3     # best-of-N, same convention as the other suites

GRID_2D = (8192, 8192)
GRID_3D = (512, 512, 512)
BASE_2D = (2048, 8192)      # weak scaling: per-device share at n=1
BASE_3D = (128, 512, 512)
N_STEPS = 64
DEVICE_COUNTS = (1, 2, 4, 8, 16)


def _modeled(spec, grid, n: int):
    plan = pm.select_config(spec, grid, N_STEPS, top_k=1, n_devices=n)[0]
    terms = pm.stencil_roofline(plan, N_STEPS, chips=n,
                                halo_exchange=n > 1)
    return plan, terms


def _strong_rows() -> list[dict]:
    rows = []
    for dims, grid in ((2, GRID_2D), (3, GRID_3D)):
        spec = diffusion(dims, 2)
        base = None
        for n in DEVICE_COUNTS:
            plan, terms = _modeled(spec, grid, n)
            t = terms.t_predicted
            base = t if base is None else base
            rows.append({
                "name": f"strong{dims}d_n{n}",
                "us": t * 1e6,
                "derived": (f"bx={plan.bx} bt={plan.bt} "
                            f"speedup={base / t:.2f}x "
                            f"eff={base / t / n:.2f} "
                            f"exposed_comm="
                            f"{terms.exposed_collective_fraction:.3f} "
                            f"bound={terms.dominant}"),
            })
    return rows


def _weak_rows() -> list[dict]:
    rows = []
    for dims, base_grid in ((2, BASE_2D), (3, BASE_3D)):
        spec = diffusion(dims, 2)
        base = None
        for n in DEVICE_COUNTS:
            grid = (base_grid[0] * n,) + base_grid[1:]
            plan, terms = _modeled(spec, grid, n)
            t = terms.t_predicted
            base = t if base is None else base
            rows.append({
                "name": f"weak{dims}d_n{n}",
                "us": t * 1e6,
                "derived": (f"bx={plan.bx} bt={plan.bt} "
                            f"eff={base / t:.2f} "
                            f"exposed_comm="
                            f"{terms.exposed_collective_fraction:.3f} "
                            f"bound={terms.dominant}"),
            })
    return rows


def _measured_rows() -> list[dict]:
    """One executed sharded cell when this host has > 1 device."""
    n = len(jax.devices())
    if n < 2:
        return [{"name": "measured_sharded", "us": 0.0,
                 "derived": "skipped: single-device host (set XLA_FLAGS="
                            "--xla_force_host_platform_device_count=N)"}]
    n = min(n, 4)
    spec = diffusion(2, 2)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((64 * n + 3, 512)), jnp.float32)
    run = lambda: ops.stencil_run(x, spec, 4, bx=256, bt=2,  # noqa: E731
                                  backend="interpret",
                                  n_devices=n).block_until_ready()
    got = run()   # warm-up; also the parity check below
    t0 = time.perf_counter()
    run()
    dt = time.perf_counter() - t0
    err = float(jnp.max(jnp.abs(got - ref.stencil_multistep(x, spec, 4))))
    return [{"name": f"measured_sharded_n{n}", "us": dt * 1e6,
             "derived": f"grid={tuple(x.shape)} bt=2 maxerr={err:.1e}"}]


def _best(fn):
    fn()                       # warm-up / compile
    best = float("inf")
    for _ in range(_REPEATS):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def _collective_seconds(x, hs, n, axis_name="shard"):
    """Best-of-N wall time of *just* the halo ppermutes the schedule
    issues — one ``exchange_halos`` per sweep depth, with a scalar
    tap per exchange so none of them can be dead-code-eliminated."""
    from repro import compat
    from repro.distributed.halo import _device_mesh, exchange_halos
    from jax.sharding import PartitionSpec as P

    mesh = _device_mesh(n, None)

    def body(xs):
        acc = jnp.zeros((1,), xs.dtype)
        for h in hs:
            fa, fb = exchange_halos(xs, h, n, axis_name)
            acc = acc + fa.ravel()[0] + fb.ravel()[0]
        return acc

    fn = jax.jit(compat.shard_map(body, mesh=mesh,
                                  in_specs=(P(axis_name),),
                                  out_specs=P(axis_name),
                                  check_vma=False))
    return _best(lambda: fn(x))


def _overlap_rows(smoke: bool) -> list[dict]:
    """Measured exposed-collective fraction: overlapped vs forced-
    serial sharded runs, with the exchange-only cost timed apart.

    ``hidden = clip(t_serial - t_ovl, 0, collective_s)`` is the
    collective time the interior/edge overlap actually removed from
    the wall; what remains of ``collective_s`` is exposed in the
    overlapped schedule. The overlapped fraction can never exceed the
    serial one by construction, so the gated metric tracks a
    deterministic inequality rather than a noise race.
    """
    from repro.distributed import halo

    n = len(jax.devices())
    if n < 2:
        return [{"name": "scaling_overlap", "us": 0.0,
                 "derived": "skipped: single-device host (set XLA_FLAGS="
                            "--xla_force_host_platform_device_count=N)"}]
    n = min(n, 4)
    spec = diffusion(2, 1)
    bt = 2
    n_steps = 4 if smoke else 8
    rows_per = 64 if smoke else 256
    width = 512 if smoke else 1024
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((rows_per * n, width)),
                    jnp.float32)

    shard = lambda ov: halo.stencil_run_sharded(  # noqa: E731
        x, spec, n_steps, n_devices=n, bx=128, bt=bt,
        backend="interpret", overlap=ov)
    a, b = shard(True), shard(False)
    np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b),
        err_msg="overlap=True diverged from overlap=False")
    t_ovl = _best(lambda: shard(True))
    t_serial = _best(lambda: shard(False))

    # One exchange per sweep at that sweep's depth (ops' schedule:
    # full-bt sweeps then the remainder), matching what both runs pay.
    hs = [bt * spec.radius] * (n_steps // bt)
    if n_steps % bt:
        hs.append((n_steps % bt) * spec.radius)
    collective_s = min(_collective_seconds(x, hs, n), t_serial)

    f_serial = collective_s / t_serial if t_serial > 0 else 0.0
    hidden = min(max(t_serial - t_ovl, 0.0), collective_s)
    f_ovl = (max(0.0, collective_s - hidden) / t_ovl
             if t_ovl > 0 else 0.0)
    return [{
        "name": f"scaling_overlap_n{n}",
        "us": t_ovl * 1e6,
        "derived": (f"grid={tuple(x.shape)} bt={bt} "
                    f"serial={t_serial * 1e6:.0f}us "
                    f"collective={collective_s * 1e6:.0f}us "
                    f"measured_exposed_comm={f_ovl:.2f} "
                    f"(serial {f_serial:.2f}) bitwise ovl==serial"),
        "measured_exposed_collective_fraction": f_ovl,
        "measured_exposed_collective_fraction_serial": f_serial,
        "config": {"n_devices": n, "bx": 128, "bt": bt,
                   "n_steps": n_steps,
                   "collective_s": collective_s,
                   "t_serial_s": t_serial},
    }]


def run(smoke: bool = False) -> list[dict]:
    return (_strong_rows() + _weak_rows() + _measured_rows()
            + _overlap_rows(smoke))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized executed cells (the modeled tables "
                         "are cheap either way)")
    ap.add_argument("--json", default="BENCH_scaling.json",
                    help="machine-readable record path "
                         "(default: %(default)s; empty disables)")
    args = ap.parse_args(argv)

    rows = run(smoke=args.smoke)
    print("name,us_per_run,derived")
    for r in rows:
        print(f"{r['name']},{r['us']:.1f},{r['derived']}")

    if args.json:
        payload = {"generated_by": "benchmarks.scaling",
                   "smoke": args.smoke, "rows": rows}
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=1, sort_keys=True)
        print(f"# wrote {args.json} ({len(rows)} rows)", file=sys.stderr)


if __name__ == "__main__":
    main()
