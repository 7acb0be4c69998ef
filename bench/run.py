"""Run one benchmark cell once on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout. The cell, its configuration, traffic mix,
limits and metrics are found by name (``bench/harness.py``). The run
warms up every shape the cell uses (set-up), measures for ``--seconds``,
compares what the window produced with the plain reference, and prints
as the last line of standard output one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and with
``--trace 1`` the trace's ``breakdown``), and last ``checks``, each
number compared beside its limit. The same numbers are the last lines
of standard error. An earlier line gives the plan the program ran.

It exits 1 and prints no result when JAX finds no TPU or fewer chips
than the cell asks for, and 2 outside a checkout of the repository.
JAX's compilation cache is ``<checkout>/.jax_cache`` (set in
``JAX_COMPILATION_CACHE_DIR``, whatever the environment held), and the
tuner's plans are kept in ``<checkout>/.cache/bench_autotune.json``:
fixed paths inside the checkout, so that only a cell's first run there
compiles and tunes, and two checkouts share nothing.

The first run of a cell in a checkout first warms the cell up in a
process of its own (``--warm-only``: set-up and one request or solve,
no result), which runs the tuner and fills both caches, and leaves a
stamp in ``<checkout>/.cache/bench_warm/``. The run itself then traces
and compiles its programs as every later run does, with no tuning in
its process, so that a second run finds every program in the cache.
That child counts in the first run's set-up.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--warm-only", action="store_true",
                    help="set-up alone, in a checkout's first run")
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: no src/repro in {ROOT}; run it from a checkout "
              f"of the repository", file=sys.stderr)
        return 2
    # Import the benchmark as the package ``bench``, never its modules
    # by their bare names from the script's own directory.
    here = pathlib.Path(__file__).resolve().parent
    sys.path[:] = [str(ROOT), str(ROOT / "src")] + [
        p for p in sys.path if pathlib.Path(p or ".").resolve() != here]
    os.environ["REPRO_AUTOTUNE_CACHE"] = str(ROOT / ".cache"
                                             / "bench_autotune.json")
    # Before JAX is imported, which reads it once.
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    from bench import harness
    cell = harness.find_cell(ROOT, args.workload)
    stamp = ROOT / ".cache" / "bench_warm" / args.workload
    if not args.warm_only and not stamp.is_file():
        # Before this process touches the chip: the child holds it.
        child = subprocess.run(
            [sys.executable, str(pathlib.Path(__file__).resolve()),
             "--workload", args.workload, "--seed", str(args.seed),
             "--seconds", "0", "--trace", "0", "--warm-only"],
            stdin=subprocess.DEVNULL, stdout=sys.stderr)
        if child.returncode != 0:
            return child.returncode

    from repro.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    import jax
    # Every program goes to the cache, however short its compile, so
    # that a second run of a cell compiles nothing.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()
    print(f"bench: platform {devices[0].platform} kind "
          f"{devices[0].device_kind!r} count {len(devices)} compile cache "
          f"{cache_dir}", file=sys.stderr, flush=True)
    if devices[0].platform != "tpu":
        print("bench: JAX found no TPU; nothing was run", file=sys.stderr)
        return 1
    if len(devices) < cell.chips:
        print(f"bench: the cell asks for {cell.chips} chips, JAX sees "
              f"{len(devices)}; nothing was run", file=sys.stderr)
        return 1

    if args.warm_only:
        harness.warm(ROOT, args.workload, args.seed)
        stamp.parent.mkdir(parents=True, exist_ok=True)
        stamp.write_text(f"{time.perf_counter() - T_START:.3f}\n")
        print(f"bench: warmed {args.workload} in "
              f"{time.perf_counter() - T_START:.1f} s", file=sys.stderr)
        return 0

    result, plan = harness.run_cell(ROOT, args.workload, args.seed,
                                    args.seconds, bool(args.trace),
                                    T_START)
    print(json.dumps(plan), flush=True)
    print(json.dumps(result), flush=True)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
