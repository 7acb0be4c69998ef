"""In-kernel DMA pipelining (kernels/engine.py persistent path +
outofcore/runner.py ``pipeline="kernel"`` mode).

The persistent kernel streams leading-axis tiles HBM->VMEM with
double-buffered async copies *inside* one pallas_call; everything here
pins it **bitwise** against the in-core engine (the same contract the
host-loop out-of-core runner carries), plus the capability gate, the
graceful fallback, and the runner's timing-metrics contract.
"""
import os

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core.stencil import diffusion
from repro.kernels import engine
from repro.outofcore import stencil_run_outofcore

BX = 128


def _grid(dims, rng):
    shape = (67, 140) if dims == 2 else (41, 9, 133)
    return jnp.asarray(rng.standard_normal(shape), jnp.float32)


# ---------------------------------------------------------------------------
# Engine level: stencil_call_persistent vs stencil_call, full slab
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dims", [2, 3])
@pytest.mark.parametrize("radius,bt", [(1, 1), (1, 4), (2, 2), (4, 1)])
@pytest.mark.parametrize("boundary", ["dirichlet0", "clamp"])
def test_persistent_bitwise_vs_incore(dims, radius, bt, boundary):
    avail, why = engine.kernel_pipeline_available("interpret")
    if not avail:
        pytest.skip(f"kernel pipeline unavailable: {why}")
    rng = np.random.default_rng(0)
    x = _grid(dims, rng)
    spec = diffusion(dims, radius, boundary=boundary)
    want = engine.stencil_call(x, spec, bx=BX, bt=bt, backend="interpret")
    got = engine.stencil_call_persistent(
        x, spec, bx=BX, bt=bt, tile=9, lead=0, owned=x.shape[0],
        backend="interpret")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_persistent_chunk_with_lead_ghost():
    """A chunk that is an interior slab of a larger grid: the leading
    ghost rows are inputs only, ``owned`` rows come back."""
    avail, why = engine.kernel_pipeline_available("interpret")
    if not avail:
        pytest.skip(f"kernel pipeline unavailable: {why}")
    rng = np.random.default_rng(1)
    x = _grid(2, rng)
    spec = diffusion(2, 1)
    bt, g = 2, 2                       # ghost depth bt*r
    want = engine.stencil_call(x, spec, bx=BX, bt=bt, backend="interpret",
                               valid_lo=None, valid_hi=None)
    # Chunk covering grid rows [20, 50) with g ghosts each side.
    c0, c1 = 20, 50
    chunk = x[c0 - g:c1 + g]
    got = engine.stencil_call_persistent(
        chunk, spec, bx=BX, bt=bt, tile=7, lead=g, owned=c1 - c0,
        backend="interpret")
    # Interior rows are ghost-covered, so they match the full-grid run.
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(want)[c0:c1])


# ---------------------------------------------------------------------------
# Capability gate
# ---------------------------------------------------------------------------

def test_capability_gate():
    ok, _ = engine.kernel_pipeline_supported(
        diffusion(2, 1), backend="interpret")
    avail, why = engine.kernel_pipeline_available("interpret")
    assert ok == avail
    # gpu never qualifies; unsupported operands are named in the reason
    ok, why = engine.kernel_pipeline_available("gpu")
    assert not ok and "Triton" in why
    for kw in ("batched", "has_source", "has_aux", "has_scalars"):
        ok, why = engine.kernel_pipeline_supported(
            diffusion(2, 1), backend="interpret", **{kw: True})
        assert not ok, kw


def test_kill_switch_env(monkeypatch):
    monkeypatch.setenv("REPRO_DISABLE_KERNEL_PIPELINE", "1")
    ok, why = engine.kernel_pipeline_available("interpret")
    assert not ok and "REPRO_DISABLE_KERNEL_PIPELINE" in why


# ---------------------------------------------------------------------------
# Runner level: pipeline="kernel" vs "host" vs in-core, incl. chunking
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dims", [2, 3])
@pytest.mark.parametrize("budget", [None, 1 << 20, 128 << 10])
def test_runner_kernel_mode_bitwise(dims, budget):
    rng = np.random.default_rng(2)
    x = _grid(dims, rng)
    spec = diffusion(dims, 1)
    kw = dict(bx=BX, bt=2, backend="interpret")
    if budget is None:
        kw["tile"] = 9
    else:
        kw["hbm_budget"] = budget
    want = engine.stencil_call(x, spec, bx=BX, bt=2, backend="interpret")
    want = np.asarray(engine.stencil_call(
        np.asarray(want), spec, bx=BX, bt=1, backend="interpret"))  # 3 steps
    host = stencil_run_outofcore(x, spec, 3, pipeline="host", **kw)
    np.testing.assert_array_equal(np.asarray(host), want)
    m: dict = {}
    got = stencil_run_outofcore(x, spec, 3, pipeline="kernel",
                                metrics=m, **kw)
    np.testing.assert_array_equal(np.asarray(got), want)
    if engine.kernel_pipeline_available("interpret")[0]:
        assert m["pipeline"] == "kernel"
        assert m["n_chunks"] >= 1
    else:
        assert m["pipeline"] == "host" and m["fallback_reason"]


def test_runner_kernel_fallback_paths():
    """Unsupported operands and the env kill-switch fall back to the
    host loop — same answer, reason recorded."""
    rng = np.random.default_rng(3)
    x = _grid(2, rng)
    spec = diffusion(2, 1)
    src = jnp.asarray(rng.standard_normal(x.shape), jnp.float32) * 0.1
    m: dict = {}
    got = stencil_run_outofcore(x, spec, 2, bx=BX, bt=1, tile=16,
                                backend="interpret", source=src,
                                pipeline="kernel", metrics=m)
    assert m["pipeline_requested"] == "kernel"
    assert m["pipeline"] == "host" and m["fallback_reason"]
    want = stencil_run_outofcore(x, spec, 2, bx=BX, bt=1, tile=16,
                                 backend="interpret", source=src)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    old = os.environ.get("REPRO_DISABLE_KERNEL_PIPELINE")
    os.environ["REPRO_DISABLE_KERNEL_PIPELINE"] = "1"
    try:
        m2: dict = {}
        got2 = stencil_run_outofcore(x, spec, 2, bx=BX, bt=1, tile=16,
                                     backend="interpret", pipeline="kernel",
                                     metrics=m2)
        assert m2["pipeline"] == "host" and m2["fallback_reason"]
        want2 = stencil_run_outofcore(x, spec, 2, bx=BX, bt=1, tile=16,
                                      backend="interpret")
        np.testing.assert_array_equal(np.asarray(got2), np.asarray(want2))
    finally:
        if old is None:
            del os.environ["REPRO_DISABLE_KERNEL_PIPELINE"]
        else:
            os.environ["REPRO_DISABLE_KERNEL_PIPELINE"] = old


def test_runner_rejects_unknown_pipeline():
    x = _grid(2, np.random.default_rng(4))
    with pytest.raises(ValueError, match="pipeline"):
        stencil_run_outofcore(x, diffusion(2, 1), 1, bx=BX, bt=1,
                              tile=16, backend="interpret", pipeline="dma")


# ---------------------------------------------------------------------------
# Metrics contract
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("pipeline", ["host", "kernel"])
def test_metrics_phased_at_depth_1(pipeline):
    rng = np.random.default_rng(5)
    x = _grid(2, rng)
    m: dict = {}
    stencil_run_outofcore(x, diffusion(2, 1), 2, bx=BX, bt=1, tile=16,
                          backend="interpret", depth=1, pipeline=pipeline,
                          metrics=m)
    for k in ("pipeline_requested", "pipeline", "fallback_reason",
              "tile", "depth", "n_tiles", "n_sweeps", "n_dispatches",
              "wall_s"):
        assert k in m, k
    assert m["wall_s"] > 0
    # depth<=1 serializes the phases, so their timings are real numbers
    assert m["upload_s"] is not None and m["upload_s"] >= 0
    assert m["compute_s"] is not None and m["compute_s"] >= 0
    assert m["readback_s"] is not None and m["readback_s"] >= 0
    if m["pipeline"] == "kernel":
        assert m["n_chunks"] >= 1 and m["tiles_per_chunk"] >= 1


def test_metrics_overlapped_depth_skips_phases():
    rng = np.random.default_rng(6)
    x = _grid(2, rng)
    m: dict = {}
    stencil_run_outofcore(x, diffusion(2, 1), 2, bx=BX, bt=1, tile=16,
                          backend="interpret", depth=2, metrics=m)
    # In-flight transfers make per-phase attribution meaningless.
    assert m["upload_s"] is None and m["readback_s"] is None
    assert m["wall_s"] > 0
