"""Find the highest rate an open-loop cell sustains, by a sweep on the chip.

    python3 bench/sweep.py --workload hotspot2d.ensemble --seeds 7,8,9 \
        --seconds 10 --rates 40,60,80,100,120,140

runs the cell's open loop once per offered rate and seed, in one
process, and prints one JSON line per run: requests completed per
second, latency percentiles over all requests and over the first and
the last quarter of them, and the generator's diagnostics. A rate is
sustained on a seed when

* nearly all of it completes (``completed/offered >= 0.97``),
* the tail does not grow across the window: the 95th percentile of the
  last quarter of the requests is at most twice that of the first
  quarter (or of the base, if that is higher), and
* the tail stays near its low-load value: the 95th percentile over all
  requests is at most ``TAIL_MULTIPLE`` times the base, the median over
  the seeds of the 95th percentile at the lowest rate swept.

The knee is the highest rate sustained on every seed, with every lower
rate sustained too; the last line names it. A cell's ``rate_per_s`` is
then set, by hand, to four fifths of it.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
TAIL_MULTIPLE = 3.0
QUARTER_GROWTH = 2.0


def sweep_point(cell, seed: int, seconds: float, rate: float) -> dict:
    import numpy as np
    from bench import generator
    traffic = dict(cell.traffic, rate_per_s=rate)
    win = generator.drive(cell.config, traffic, seed, seconds,
                          annotate=False, hooks=generator.NoHooks())
    lat = 1e3 * np.asarray(win.latencies_s)     # in arrival order, ms
    q = max(1, len(lat) // 4)
    return {"offered_per_s": rate, "seed": seed,
            "completed_per_s": win.completed / win.seconds,
            "completed_share": win.completed / win.attempted,
            "p50_ms": float(np.percentile(lat, 50)),
            "p95_ms": float(np.percentile(lat, 95)),
            "first_quarter_p95_ms": float(np.percentile(lat[:q], 95)),
            "last_quarter_p95_ms": float(np.percentile(lat[-q:], 95)),
            **win.diagnostics, "counters": win.counters}


def sustained(point: dict, base_p95_ms: float) -> bool:
    """Whether one run at one rate kept up (module docstring)."""
    return bool(
        point["completed_share"] >= 0.97
        and point["completed_per_s"] >= 0.97 * point["offered_per_s"]
        and point["last_quarter_p95_ms"] <= QUARTER_GROWTH * max(
            point["first_quarter_p95_ms"], base_p95_ms)
        and point["p95_ms"] <= TAIL_MULTIPLE * base_p95_ms)


def knee(points: list) -> dict:
    """The base tail, each rate's verdict, and the knee, from the runs."""
    import numpy as np
    rates = sorted({p["offered_per_s"] for p in points})
    base = float(np.median([p["p95_ms"] for p in points
                            if p["offered_per_s"] == rates[0]]))
    verdict = {r: all(sustained(p, base) for p in points
                      if p["offered_per_s"] == r) for r in rates}
    best = None
    for r in rates:
        if not verdict[r]:
            break
        best = r
    return {"base_p95_ms": base, "tail_multiple": TAIL_MULTIPLE,
            "sustained": {str(r): v for r, v in verdict.items()},
            "knee_per_s": best}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="7,8,9")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    here = pathlib.Path(__file__).resolve().parent
    sys.path[:] = [str(ROOT), str(ROOT / "src")] + [
        p for p in sys.path if pathlib.Path(p or ".").resolve() != here]
    os.environ["REPRO_AUTOTUNE_CACHE"] = str(ROOT / ".cache"
                                             / "bench_autotune.json")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    from bench import harness
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    if jax.devices()[0].platform != "tpu":
        print("sweep: JAX found no TPU", file=sys.stderr)
        return 1
    cell = harness.find_cell(ROOT, args.workload)
    points = []
    for rate in sorted(float(r) for r in args.rates.split(",")):
        for seed in (int(s) for s in args.seeds.split(",")):
            point = sweep_point(cell, seed, args.seconds, rate)
            print(json.dumps(point), flush=True)
            points.append(point)
    print(json.dumps(knee(points)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
