"""Compile the engine's kernels for a described TPU v5e — no chip needed.

The TPU compiler is installed on every host, and it refuses what
interpret mode accepts: a kernel whose VMEM exceeds its scoped limit, a
slice the tiling cannot take, an op Mosaic cannot lower. These tests
compile, at the thesis's sizes, the plans the planner picks for the
smoke run's phases (chip_smoke.py), one persistent-kernel chunk, and
check the VMEM model against the compiler. Nothing runs; a compile
that passes is not a chip run.

The topology is described inside a module fixture, never at import:
only one process may hold the TPU library, and every test worker
imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.apps import hotspot
from repro.core import blocking
from repro.core.perf_model import V5E
from repro.core.stencil import diffusion
from repro.kernels import autotune, engine

GRID_2D = (8192, 8192)
GRID_3D = (512, 512, 512)
N_STEPS = 64


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # Compiles for a described chip cannot be read back from JAX's
    # persistent cache without one; keep them out of it.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # noqa: BLE001 - no TPU compiler here
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    assert topo.devices[0].device_kind == "TPU v5 lite"
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=sharding)
            for s in shapes]
    return jax.jit(fn).lower(*args).compile()


def _planned(shape, spec, **kw):
    return autotune.plan(shape, spec, backend="pallas", tpu=V5E,
                         n_steps=N_STEPS, measure=False, use_cache=False,
                         **kw)


CASES = {
    "2d_r1": (GRID_2D, diffusion(2, 1)),
    "2d_r4": (GRID_2D, diffusion(2, 4)),
    "3d_r1": (GRID_3D, diffusion(3, 1)),
    "hotspot": (GRID_2D, hotspot.spec_of(hotspot.HotspotParams())),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_planned_kernel_compiles(case, one_chip):
    shape, spec = CASES[case]
    tuned = _planned(shape, spec)
    assert tuned.block_plan.vmem_bytes(tuned.variant) <= V5E.vmem_bytes
    n_aux = len(spec.aux)

    def run(x, *aux):
        return engine.stencil_call(
            x, spec, bx=tuned.bx, bt=tuned.bt, variant=tuned.variant,
            backend="pallas",
            aux=dict(zip((op.name for op in spec.aux), aux)) or None)

    compiled = _compile(run, [shape] * (1 + n_aux), one_chip)
    assert "tpu_custom_call" in compiled.as_text()


def test_persistent_chunk_compiles(one_chip):
    """One interior chunk of the smoke run's out-of-core phase, with
    the in-kernel tile the runner sizes against the VMEM budget."""
    spec = diffusion(2, 1)
    budget = 192 << 20
    tuned = _planned(GRID_2D, spec, hbm_budget=budget, pipeline="kernel")
    tp = blocking.plan_tiles(spec, GRID_2D, bx=tuned.bx, bt=tuned.bt,
                             hbm_budget=budget)
    assert tp.n_tiles >= 4
    g = tp.ghost
    ktile = blocking.persistent_tile(
        spec, GRID_2D[1:], bx=tuned.bx, bt=tuned.bt,
        vmem_budget=V5E.vmem_bytes, limit=tp.tile)

    def run(chunk):
        return engine.stencil_call_persistent(
            chunk, spec, bx=tuned.bx, bt=tuned.bt, tile=ktile, lead=g,
            owned=tp.tile, backend="pallas")

    _compile(run, [(tp.tile + 2 * g, GRID_2D[1])], one_chip)


@pytest.mark.parametrize("case", [
    (2, 1, 128, 4, "revolving", "dirichlet0"),
    (2, 4, 256, 2, "multioperand", "dirichlet0"),
    (2, 2, 128, 1, "revolving", "clamp"),
    (3, 4, 128, 2, "revolving", "dirichlet0"),
])
def test_vmem_model_brackets_the_compiler(case, one_chip, monkeypatch):
    """``kernel_vmem_bytes`` never undercounts what the compiler needs by
    more than ``VMEM_MODEL_MARGIN``, and never overcounts it twice:
    the kernel compiles at (1 + margin) x the model and is refused at
    half of it."""
    dims, r, bx, bt, variant, boundary = case
    spec = diffusion(dims, r, boundary=boundary)
    shape = (1024, 1024) if dims == 2 else (8, 512, 512)
    model = blocking.BlockPlan(spec, shape, bx=bx, bt=bt).vmem_bytes(
        variant)

    def compile_at(limit):
        monkeypatch.setattr(engine, "vmem_limit", lambda _: int(limit))
        jax.clear_caches()
        return _compile(lambda x: engine.stencil_call(
            x, spec, bx=bx, bt=bt, variant=variant, backend="pallas"),
            [shape], one_chip)

    compile_at(model * (1 + blocking.VMEM_MODEL_MARGIN))
    with pytest.raises(Exception, match="vmem"):
        compile_at(model / 2)
