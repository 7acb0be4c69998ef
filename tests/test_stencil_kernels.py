"""Per-kernel validation: Pallas (interpret mode) vs the pure-jnp oracle,
swept over shapes, dtypes, radii, temporal degrees and variants; plus
hypothesis property tests on the blocking planner's invariants.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

pytest.importorskip(
    "hypothesis",
    reason="dev-only dependency — pip install -r requirements-dev.txt "
           "(the non-hypothesis engine coverage lives in test_engine.py)")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core.blocking import BlockPlan, candidate_plans
from repro.core.stencil import StencilSpec, diffusion, hotspot2d, hotspot3d
from repro.kernels import ops, ref

TOL = dict(rtol=3e-5, atol=3e-5)


def _rand(shape, dtype=jnp.float32, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal(shape), dtype)


# ---------------------------------------------------------------------------
# 2D kernel sweeps
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("radius", [1, 2, 3, 4])
@pytest.mark.parametrize("bt", [1, 2, 3])
def test_stencil2d_radius_bt(radius, bt):
    spec = diffusion(2, radius)
    x = _rand((40, 300))
    got = ops.stencil_sweep(x, spec, bx=128, bt=bt, backend="interpret")
    want = ref.stencil_multistep(x, spec, bt)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)


@pytest.mark.parametrize("variant", ["revolving", "multioperand"])
@pytest.mark.parametrize("shape", [(8, 128), (33, 130), (40, 384),
                                   (17, 511)])
def test_stencil2d_shapes_variants(variant, shape):
    spec = hotspot2d()
    x = _rand(shape, seed=shape[0])
    got = ops.stencil_sweep(x, spec, bx=128, bt=2, backend="interpret",
                            variant=variant)
    want = ref.stencil_multistep(x, spec, 2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_stencil2d_dtypes(dtype):
    spec = diffusion(2, 1)
    x = _rand((24, 256), dtype)
    got = ops.stencil_sweep(x, spec, bx=128, bt=2, backend="interpret")
    want = ref.stencil_multistep(x, spec, 2)
    tol = dict(rtol=3e-2, atol=3e-2) if dtype == jnp.bfloat16 else TOL
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **tol)


def test_stencil2d_source_term():
    spec = diffusion(2, 2)
    x = _rand((30, 300))
    src = _rand((30, 300), seed=7) * 0.1
    for variant in ("revolving", "multioperand"):
        got = ops.stencil_sweep(x, spec, bx=128, bt=2, backend="interpret",
                                variant=variant, source=src)
        want = ref.stencil_multistep(x, spec, 2, src)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)


def test_stencil2d_multi_sweep_equals_steps():
    spec = diffusion(2, 1)
    x = _rand((20, 256))
    got = ops.stencil_run(x, spec, n_steps=5, bx=128, bt=2,
                          backend="interpret")
    want = ref.stencil_multistep(x, spec, 5)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# 3D kernel sweeps
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("radius,bt", [(1, 1), (1, 2), (2, 1), (2, 2),
                                       (3, 1), (4, 1)])
def test_stencil3d_radius_bt(radius, bt):
    spec = diffusion(3, radius)
    x = _rand((10, 20, 260))
    got = ops.stencil_sweep(x, spec, bx=128, bt=bt, backend="interpret")
    want = ref.stencil_multistep(x, spec, bt)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)


@pytest.mark.parametrize("shape", [(4, 8, 128), (7, 17, 300)])
def test_stencil3d_shapes(shape):
    spec = hotspot3d()
    x = _rand(shape, seed=shape[-1])
    got = ops.stencil_sweep(x, spec, bx=128, bt=2, backend="interpret")
    want = ref.stencil_multistep(x, spec, 2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)


def test_stencil3d_source_term():
    spec = diffusion(3, 1)
    x = _rand((8, 16, 260))
    src = _rand((8, 16, 260), seed=3) * 0.1
    got = ops.stencil_sweep(x, spec, bx=128, bt=3, backend="interpret",
                            source=src)
    want = ref.stencil_multistep(x, spec, 3, src)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)


# ---------------------------------------------------------------------------
# Property tests
# ---------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(h=st.integers(3, 40), w=st.integers(3, 300),
       radius=st.integers(1, 4), seed=st.integers(0, 2 ** 16))
def test_oracle_linearity(h, w, radius, seed):
    """The stencil operator is linear: S(a x + b y) = a S(x) + b S(y)."""
    spec = diffusion(2, radius)
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((h, w)), jnp.float32)
    y = jnp.asarray(rng.standard_normal((h, w)), jnp.float32)
    lhs = ref.stencil_step(2.0 * x + 3.0 * y, spec)
    rhs = 2.0 * ref.stencil_step(x, spec) + 3.0 * ref.stencil_step(y, spec)
    np.testing.assert_allclose(np.asarray(lhs), np.asarray(rhs),
                               rtol=1e-4, atol=1e-4)


@settings(max_examples=25, deadline=None)
@given(bt=st.integers(1, 8), radius=st.integers(1, 4),
       bx_exp=st.integers(7, 11))
def test_blockplan_invariants(bt, radius, bx_exp):
    spec = diffusion(2, radius)
    bx = 2 ** bx_exp
    if spec.halo(bt) > bx:
        with pytest.raises(ValueError):
            BlockPlan(spec, (1024, 4096), bx=bx, bt=bt)
        return
    plan = BlockPlan(spec, (1024, 4096), bx=bx, bt=bt)
    # redundancy >= 1, monotone in bt, -> 1 as bx -> inf
    assert plan.redundancy >= 1.0
    if spec.halo(bt + 1) <= bx:
        plan2 = BlockPlan(spec, (1024, 4096), bx=bx, bt=bt + 1)
        assert plan2.redundancy >= plan.redundancy
    big = BlockPlan(spec, (1024, 2 ** 16), bx=2 ** 16, bt=bt)
    assert big.redundancy < plan.redundancy or plan.redundancy == 1.0
    # flops accounting: redundant >= useful; sweeps math
    assert plan.flops_per_sweep() >= plan.useful_flops_per_sweep()
    assert plan.sweeps(bt * 7) == 7
    assert plan.sweeps(bt * 7 + 1) == 8


def test_candidate_plans_respect_vmem():
    spec = diffusion(2, 1)
    plans = candidate_plans(spec, (4096, 16384), vmem_budget=64 * 2 ** 20)
    assert plans, "no plans found"
    assert all(p.vmem_bytes() <= 64 * 2 ** 20 for p in plans)
    # full-height 4096-row panels: wide tiles no longer fit
    assert max(p.bx for p in plans) < 1024


def test_spec_validation():
    with pytest.raises(ValueError):
        StencilSpec(dims=4, radius=1, center=1.0, axis_weights=((0.0,),))
    with pytest.raises(ValueError):
        StencilSpec(dims=2, radius=5, center=1.0,
                    axis_weights=tuple([tuple([0.0] * 11)] * 2))
    with pytest.raises(ValueError):  # nonzero center column
        StencilSpec(dims=2, radius=1, center=1.0,
                    axis_weights=((0.1, 0.2, 0.1), (0.1, 0.0, 0.1)))
    s = diffusion(2, 3)
    assert s.points == 13 and s.flops_per_cell == 25
    assert diffusion(3, 1).flops_per_cell == 13  # thesis's 7-point count
