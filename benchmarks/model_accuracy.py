"""Thesis §5.7.2 analog: performance-model accuracy.

The thesis validates its §5.4 model by comparing predicted vs measured
run time per configuration. Without TPU hardware we validate the same
property the thesis actually relies on: the model's *ranking* of
configurations matches measurement, so the pruned shortlist contains
the true optimum. We measure the CPU reference backend across a (bx,
bt) sweep (on CPU the arithmetic-per-byte trade-off of temporal
blocking is real), compare against the model evaluated with
CPU-calibrated constants, and report rank correlation + the shortlist
hit rate.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import perf_model as pm
from repro.core.blocking import BlockPlan
from repro.core.stencil import diffusion
from repro.kernels import ops

# CPU-calibrated "device" (1 core): ~50 GFLOP/s, ~20 GB/s effective.
CPU_DEV = pm.TpuSpec(name="host-cpu", peak_flops_bf16=5e10,
                     peak_flops_f32=5e10, vpu_flops_f32=5e10,
                     hbm_bw=2e10, ici_bw=1e12,
                     vmem_capacity=24 * 2 ** 20,
                     hbm_bytes=2 ** 34, tdp_watts=65.0)

GRID = (512, 2048)
N_STEPS = 16


def _measure(spec, bx, bt) -> float:
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal(GRID), jnp.float32)

    def go():
        return ops.stencil_run(x, spec, N_STEPS, bx=bx, bt=bt,
                               backend="reference").block_until_ready()

    go()
    t0 = time.perf_counter()
    go()
    return time.perf_counter() - t0


def _spearman(a, b):
    ra = np.argsort(np.argsort(a)).astype(float)
    rb = np.argsort(np.argsort(b)).astype(float)
    ra -= ra.mean()
    rb -= rb.mean()
    return float((ra * rb).sum() / np.sqrt((ra ** 2).sum()
                                           * (rb ** 2).sum()))


def run() -> list[dict]:
    spec = diffusion(2, 1)
    configs = [(256, 1), (256, 2), (256, 4), (512, 2), (512, 4),
               (1024, 1), (1024, 4), (2048, 2), (2048, 8)]
    preds, meas = [], []
    for bx, bt in configs:
        plan = BlockPlan(spec, GRID, bx=bx, bt=bt)
        preds.append(pm.stencil_roofline(plan, N_STEPS,
                                         tpu=CPU_DEV).t_predicted)
        meas.append(_measure(spec, bx, bt))
    rho = _spearman(np.asarray(preds), np.asarray(meas))
    # shortlist hit rate: is the measured best inside the model's top-3?
    order_pred = np.argsort(preds)[:3]
    hit = int(np.argmin(meas) in order_pred)
    rows = [{
        "name": "model_accuracy_rank_corr",
        "us": float(np.min(meas)) * 1e6,
        "derived": (f"spearman_rho={rho:.2f} best_in_top3={bool(hit)} "
                    f"configs={len(configs)} (§5.7.2 analog)"),
        "rho": rho, "hit": hit,
    }]
    for (bx, bt), p, m in zip(configs, preds, meas):
        rows.append({"name": f"model_acc_bx{bx}_bt{bt}", "us": m * 1e6,
                     "derived": f"predicted_us={p*1e6:.0f}"})
    return rows


if __name__ == "__main__":
    for r in run():
        print(f"{r['name']},{r['us']:.1f},{r['derived']}")
