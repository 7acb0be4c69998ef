"""Read the two numbers a cell's correctness limits are set from.

    python3 bench/limits.py --workload hotspot2d.solve --seeds 12 --control 3

runs, in one process on the chip, the cell's timed path at its own size
on ``--seeds`` seeds, with a short window (one solve of a closed loop;
``--seconds`` at the cell's own rate for an open loop), and compares
each with the reference as a benchmark run does: the largest of these
readings is the lower one. On the first ``--control`` seeds it also puts
the control in the program's place, the reference computed one precision
lower (bfloat16 for float32), and compares that: the smallest of those
readings is the upper one. Each line is JSON; the last gives both. The
limits in ``bench/limits/<cell>.json`` are set by hand between them.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=4_100_000_000)
    ap.add_argument("--seconds", type=float, default=2.0)
    args = ap.parse_args(argv)
    here = pathlib.Path(__file__).resolve().parent
    sys.path[:] = [str(ROOT), str(ROOT / "src")] + [
        p for p in sys.path if pathlib.Path(p or ".").resolve() != here]
    os.environ["REPRO_AUTOTUNE_CACHE"] = str(ROOT / ".cache"
                                             / "bench_autotune.json")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    from bench import check, generator, harness
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    if jax.devices()[0].platform != "tpu":
        print("limits: JAX found no TPU", file=sys.stderr)
        return 1
    cell = harness.find_cell(ROOT, args.workload)
    if jax.device_count() < cell.chips:
        print(f"limits: the cell asks for {cell.chips} chips, JAX sees "
              f"{jax.device_count()}", file=sys.stderr)
        return 1
    seconds = 0.0 if cell.traffic["loop"] == "closed" else args.seconds
    program, control = [], []
    for i in range(args.seeds):
        seed = args.first_seed + i
        win = generator.drive(cell.config, cell.traffic, seed, seconds,
                              annotate=False, hooks=generator.NoHooks(),
                              chips=cell.chips)
        line = {"seed": seed, "attempted": win.attempted,
                "compared": len(win.outputs),
                "program": check.compare(win, cell.config)}
        program.append(line["program"])
        if i < args.control:
            line["control"] = check.control(win, cell.config)
            control.append(line["control"])
        print(json.dumps(line), flush=True)
        del win
    names = sorted(program[0])
    print(json.dumps({
        "workload": args.workload,
        "lower": {k: max(p[k] for p in program) for k in names},
        "upper": {k: min(c[k] for c in control) for k in names}
        if control else None}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
