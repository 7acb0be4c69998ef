"""Measure the float32 rate a pure-VALU Pallas kernel sustains on one chip.

No VPU float32 rate is published for the TPU v5e, so the benchmark's
roofline takes this measured rate as its compute peak. The kernel holds
a block in VMEM and iterates ``x = x * a + b`` on it, one multiply and
one add per element per iteration (2 operations, counted as the stencil
count counts them), with no HBM traffic worth naming: 4096 iterations
per element loaded. Block heights from 8 rows up to 2048 are tried
over the same 2**19 rows in all (more rows give the scheduler more
independent vector registers, until the block no longer fits in them);
the rate of each is the best of ``--repeats`` timed calls, each ending
in ``block_until_ready``. The rate rises with the height, levels off,
and falls once a block spills out of the registers; the plateau is what
a pure-VALU kernel sustains, and it goes into
``bench/peaks.json`` by hand, with the date and this method, so that
the roofline does not drift with the noise of a run.

    python bench/calibrate_vpu.py            # on the chip; prints JSON
"""
from __future__ import annotations

import argparse
import functools
import json
import sys
import time

LANES = 128
ITERS = 4096
ROWS = (8, 32, 64, 128, 256, 512, 1024, 2048)
TOTAL_ROWS = 1 << 19
UNROLL = 16              # Mosaic lowers fori_loop with unroll=1 only


def _valu_kernel(x_ref, o_ref, *, iters):
    import jax
    import jax.numpy as jnp
    a = jnp.float32(0.999)
    b = jnp.float32(0.001)

    def body(_, v):
        for _ in range(UNROLL):
            v = v * a + b
        return v
    o_ref[...] = jax.lax.fori_loop(0, iters // UNROLL, body, x_ref[...])


def valu_call(rows: int, n_blocks: int, iters: int, interpret=False):
    """A jitted call over ``n_blocks`` blocks of ``(rows, 128)`` f32."""
    import jax
    from jax.experimental import pallas as pl
    kern = functools.partial(_valu_kernel, iters=iters)
    shape = (rows * n_blocks, LANES)

    @jax.jit
    def call(x):
        return pl.pallas_call(
            kern, grid=(n_blocks,),
            in_specs=[pl.BlockSpec((rows, LANES), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct(shape, x.dtype),
            interpret=interpret, name="valu_calibration")(x)
    return call, shape


def measure(rows: int, repeats: int, iters=ITERS) -> dict:
    import jax
    import jax.numpy as jnp
    call, shape = valu_call(rows, TOTAL_ROWS // rows, iters)
    x = jnp.full(shape, 0.5, jnp.float32)
    jax.block_until_ready(call(x))
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(call(x))
        best = min(best, time.perf_counter() - t0)
    ops = 2 * shape[0] * shape[1] * iters
    return {"rows": rows, "ops": ops, "best_s": best,
            "ops_per_s": ops / best}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--repeats", type=int, default=10)
    args = ap.parse_args(argv)
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"calibrate_vpu: no TPU (platform {dev.platform!r})",
              file=sys.stderr)
        return 1
    rows = [measure(r, args.repeats) for r in ROWS]
    for r in rows:
        print(json.dumps(r), flush=True)
    print(json.dumps({"device_kind": dev.device_kind,
                      "vpu_f32_ops_per_s": max(r["ops_per_s"] for r in rows),
                      "method": f"x = x*a + b, {ITERS} iterations per "
                                f"element, (rows,{LANES}) f32 blocks, "
                                f"rows in {list(ROWS)}, best of "
                                f"{args.repeats} calls each"}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
