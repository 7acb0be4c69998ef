"""End-to-end driver: a few hundred steps of thermal simulation
(Rodinia Hotspot, the thesis's ch.4/ch.5 flagship app) through the
blocked stencil accelerator, with the autotuner (model prior ->
measured ground truth -> disk cache) choosing the configuration.

  PYTHONPATH=src python examples/hotspot_sim.py [--steps 200]

The main run takes ``backend="auto"``: the compiled kernels on a TPU,
the Pallas interpreter elsewhere (keep the grid small there).
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.apps import hotspot
from repro.compile_cache import enable_compile_cache
from repro.core.perf_model import V5E, stencil_roofline
from repro.kernels import autotune, ops

enable_compile_cache()

ap = argparse.ArgumentParser()
ap.add_argument("--steps", type=int, default=200)
ap.add_argument("--h", type=int, default=512)
ap.add_argument("--w", type=int, default=2048)
args = ap.parse_args()

params = hotspot.HotspotParams()
spec = hotspot.spec_of(params)
temp, power = hotspot.random_problem(jax.random.PRNGKey(0), args.h, args.w)

# autotuned blocking choice (the thesis's §5.4 tuning flow)
backend = ops.resolve_backend("auto")
tuned = autotune.plan((args.h, args.w), spec, backend=backend,
                      n_steps=args.steps)
plan = tuned.block_plan
terms = stencil_roofline(plan, args.steps, tpu=V5E)
print(f"grid {args.h}x{args.w}, {args.steps} steps; autotuner chose "
      f"bx={plan.bx} bt={plan.bt} [{tuned.source}] "
      f"(v5e-bound: {terms.dominant}, "
      f"predicted {terms.t_predicted*1e3:.2f} ms/run)")

t0 = time.perf_counter()
out = hotspot.hotspot_blocked(temp, power, args.steps, bt=plan.bt,
                              bx=plan.bx, backend=backend)
out.block_until_ready()
dt = time.perf_counter() - t0
cells = args.h * args.w * args.steps
print(f"{backend} run on {jax.devices()[0].device_kind}: {dt:.2f}s "
      f"({cells/dt/1e6:.1f} MCell-updates/s, compile included)")

# physical sanity + agreement with the per-step reference on a window
ref_small = hotspot.hotspot_reference(temp[:64, :256], power[:64, :256], 8)
blk_small = hotspot.hotspot_blocked(temp[:64, :256], power[:64, :256], 8,
                                    bt=4, bx=128, backend="interpret")
err = float(jnp.max(jnp.abs(ref_small - blk_small)))
print(f"temperatures in [{float(out.min()):.1f}, {float(out.max()):.1f}] C;"
      f" blocked-vs-reference max err {err:.2e}")
assert np.isfinite(np.asarray(out)).all() and err < 1e-2
print("OK")
