"""Benchmark driver: one module per thesis table/figure.

  PYTHONPATH=src python -m benchmarks.run [--only rodinia,stencil,...]
                                          [--json BENCH_stencil.json]

Prints ``name,us_per_call,derived`` CSV per benchmark, plus (when the
dry-run cache exists) the LM roofline summary that EXPERIMENTS.md
§Roofline reads — and always writes a machine-readable JSON record
(``BENCH_stencil.json`` by default) with, per row: the suite, the
resolved blocking config, the best measured time and the modeled
roofline (where the suite computes one). CI's smoke job parses that
file, so benchmark code cannot silently rot.
"""
from __future__ import annotations

import argparse
import json
import sys
import traceback

SUITES = ("smoke", "rodinia", "stencil", "scaling", "serving",
          "outofcore", "solvers", "model_accuracy", "projection")


def _json_row(suite: str, r: dict) -> dict:
    """The machine-readable form of one benchmark row: suite, config,
    best time, modeled roofline. Suites attach ``config``/``roofline``
    when they resolve one (stencil_tables does); rows without them are
    recorded with nulls so the schema stays uniform."""
    return {
        "suite": suite,
        "name": r["name"],
        "us_per_call": r["us"],
        "config": r.get("config"),
        "roofline": r.get("roofline"),
        "derived": r["derived"],
    }


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of: " + ",".join(SUITES))
    ap.add_argument("--retune", action="store_true",
                    help="drop the stencil autotuner's on-disk cache so "
                         "every (bx, bt, variant) choice is re-searched")
    ap.add_argument("--json", default="BENCH_stencil.json",
                    help="path for the machine-readable record "
                         "(default: %(default)s; empty string disables)")
    args = ap.parse_args(argv)
    picked = args.only.split(",") if args.only else list(SUITES)

    from repro.compile_cache import enable_compile_cache
    from repro.kernels import autotune
    enable_compile_cache()
    if args.retune:
        autotune.clear_cache()
    print(f"# autotune cache: {autotune.cache_path()}", file=sys.stderr)

    failures = []
    records = []
    print("name,us_per_call,derived")
    for suite in picked:
        try:
            if suite == "smoke":
                from benchmarks import smoke as mod
            elif suite == "rodinia":
                from benchmarks import rodinia as mod
            elif suite == "stencil":
                from benchmarks import stencil_tables as mod
            elif suite == "scaling":
                from benchmarks import scaling as mod
            elif suite == "serving":
                from benchmarks import serving as mod
            elif suite == "outofcore":
                from benchmarks import outofcore as mod
            elif suite == "solvers":
                from benchmarks import solvers as mod
            elif suite == "model_accuracy":
                from benchmarks import model_accuracy as mod
            elif suite == "projection":
                from benchmarks import projection as mod
            else:
                raise ValueError(f"unknown suite {suite}")
            for r in mod.run():
                print(f"{r['name']},{r['us']:.1f},{r['derived']}")
                records.append(_json_row(suite, r))
        except Exception:  # noqa: BLE001
            traceback.print_exc()
            failures.append(suite)

    # LM roofline table (from cached dry-run cells, if present)
    try:
        from repro.launch import roofline
        rows = [a for c in roofline.load_cells("single")
                if (a := roofline.analyze(c))]
        for r in rows:
            print(f"roofline_{r['arch']}_{r['shape']},"
                  f"{r['t_predicted']*1e6:.1f},"
                  f"dominant={r['dominant']} useful/HLO="
                  f"{r['useful_ratio']:.2f} MFU@roof="
                  f"{r['mfu_at_roofline']:.3f}")
    except Exception:  # noqa: BLE001
        print("roofline_cells,0,no dry-run cache yet", file=sys.stderr)

    if args.json:
        payload = {"generated_by": "benchmarks.run",
                   "suites": picked, "failures": failures,
                   "rows": records}
        with open(args.json, "w") as f:
            json.dump(payload, f, indent=1, sort_keys=True)
        print(f"# wrote {args.json} ({len(records)} rows)",
              file=sys.stderr)

    if failures:
        print(f"FAILED suites: {failures}", file=sys.stderr)
        raise SystemExit(1)


if __name__ == "__main__":
    main()
