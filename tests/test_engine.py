"""Engine + autotuner coverage that runs without dev-only deps.

Parity of the unified engine (kernels/engine.py) against the pure-jnp
oracle for radius 1-4, odd (non-tile-aligned) shapes and both kernel
variants, all in interpret mode; plus autotuner plan/cache behavior.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core.stencil import diffusion, hotspot2d
from repro.kernels import autotune, engine, ops, ref

TOL = dict(rtol=3e-5, atol=3e-5)


def _rand(shape, dtype=jnp.float32, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal(shape), dtype)


@pytest.fixture(autouse=True)
def _isolated_cache(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE",
                       str(tmp_path / "autotune.json"))


# ---------------------------------------------------------------------------
# Engine parity (shared machinery, both variants, odd shapes)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("radius", [1, 2, 3, 4])
@pytest.mark.parametrize("variant", ["revolving", "multioperand"])
def test_engine_2d_radius_variants(radius, variant):
    spec = diffusion(2, radius)
    x = _rand((23, 261), seed=radius)          # odd, non-tile-aligned
    got = engine.stencil_call(x, spec, bx=128, bt=2, variant=variant,
                              backend="interpret")
    want = ref.stencil_multistep(x, spec, 2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)


@pytest.mark.parametrize("radius", [1, 2, 3, 4])
def test_engine_3d_radius(radius):
    spec = diffusion(3, radius)
    x = _rand((6, 11, 263), seed=radius)       # odd in every dim
    got = engine.stencil_call(x, spec, bx=128, bt=1, backend="interpret")
    want = ref.stencil_multistep(x, spec, 1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)


def test_engine_3d_temporal_pipeline():
    spec = diffusion(3, 1)
    x = _rand((7, 10, 260))
    got = engine.stencil_call(x, spec, bx=128, bt=3, backend="interpret")
    want = ref.stencil_multistep(x, spec, 3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)


def test_engine_source_term_both_variants():
    spec = hotspot2d()
    x = _rand((19, 261))
    src = _rand((19, 261), seed=5) * 0.1
    want = ref.stencil_multistep(x, spec, 2, src)
    for variant in engine.VARIANTS_2D:
        got = engine.stencil_call(x, spec, bx=128, bt=2, variant=variant,
                                  backend="interpret", source=src)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   **TOL)


def test_engine_rejects_unknown_variant():
    spec = diffusion(2, 1)
    x = _rand((8, 128))
    with pytest.raises(ValueError, match="variant"):
        engine.stencil_call(x, spec, bx=128, bt=1, variant="bogus",
                            backend="interpret")
    x3 = _rand((4, 8, 128))
    with pytest.raises(ValueError, match="variant"):
        engine.stencil_call(x3, diffusion(3, 1), bx=128, bt=1,
                            variant="multioperand", backend="interpret")


# ---------------------------------------------------------------------------
# Batched execution: each problem in a [B, *grid] batch must be
# BITWISE-identical to its solo run (the batch axis is an outer grid
# dimension — same kernel, same arithmetic order), for every radius,
# both boundary modes, 2D and 3D, B in {1, 2, 5}. The jax.vmap fallback
# (an independent lowering of the same batch) must agree bitwise too.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dims", [2, 3])
@pytest.mark.parametrize("boundary", ["dirichlet0", "clamp"])
def test_engine_batched_bitwise_equals_solo_loop(dims, boundary):
    shape = (13, 140) if dims == 2 else (5, 9, 133)
    for radius in (1, 2, 3, 4):
        spec = diffusion(dims, radius, boundary=boundary)
        for B in (1, 2, 5):
            x = _rand((B,) + shape, seed=radius * 10 + B)
            got = engine.stencil_call(x, spec, bx=128, bt=2,
                                      backend="interpret")
            solo = jnp.stack([
                engine.stencil_call(x[b], spec, bx=128, bt=2,
                                    backend="interpret") for b in range(B)])
            np.testing.assert_array_equal(
                np.asarray(got), np.asarray(solo),
                err_msg=f"dims={dims} {boundary} r={radius} B={B}")
            want = ref.stencil_multistep(x, spec, 2)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       **TOL)


@pytest.mark.parametrize("variant", ["revolving", "multioperand"])
def test_engine_batched_matches_vmap_fallback(variant):
    spec = diffusion(2, 2)
    x = _rand((3, 13, 140), seed=7)
    got = engine.stencil_call(x, spec, bx=128, bt=2, variant=variant,
                              backend="interpret")
    vm = engine.stencil_call_vmap(x, spec, bx=128, bt=2, variant=variant,
                                  backend="interpret")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(vm))


def test_engine_batched_source_and_3d():
    spec = hotspot2d()
    x = _rand((4, 13, 140), seed=1)
    src = _rand((4, 13, 140), seed=2) * 0.1
    got = engine.stencil_call(x, spec, bx=128, bt=2, backend="interpret",
                              source=src)
    solo = jnp.stack([
        engine.stencil_call(x[b], spec, bx=128, bt=2, backend="interpret",
                            source=src[b]) for b in range(4)])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(solo))
    spec3 = diffusion(3, 1)
    x3 = _rand((2, 4, 8, 133), seed=3)
    s3 = _rand((2, 4, 8, 133), seed=4) * 0.1
    got3 = engine.stencil_call(x3, spec3, bx=128, bt=2, backend="interpret",
                               source=s3)
    vm3 = engine.stencil_call_vmap(x3, spec3, bx=128, bt=2, source=s3,
                                   backend="interpret")
    np.testing.assert_array_equal(np.asarray(got3), np.asarray(vm3))


def test_engine_batched_rejects_bad_ranks():
    spec = diffusion(2, 1)
    with pytest.raises(ValueError, match="batch"):
        engine.stencil_call(_rand((2, 2, 8, 128)), spec, bx=128, bt=1,
                            backend="interpret")
    with pytest.raises(ValueError, match="at least one"):
        engine.stencil_call(jnp.zeros((0, 8, 128)), spec, bx=128, bt=1,
                            backend="interpret")
    with pytest.raises(ValueError, match="rank"):
        engine.stencil_call_vmap(_rand((8, 128)), spec, bx=128, bt=1,
                                 backend="interpret")


def test_ops_batched_autotuned_run():
    """ops.stencil_run on a batch, blocking resolved by the (batch-
    aware) tuner, equals the batched oracle."""
    spec = diffusion(2, 1)
    x = _rand((3, 16, 300), seed=5)
    got = ops.stencil_run(x, spec, n_steps=3, backend="interpret")
    want = ref.stencil_multistep(x, spec, 3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    # reference backend takes the same batched path
    got_ref = ops.stencil_run(x, spec, 3, bx=128, bt=1,
                              backend="reference")
    np.testing.assert_allclose(np.asarray(got_ref), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# Autotuned end-to-end runs
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape,dims", [((21, 259), 2), ((5, 9, 261), 3)])
def test_autotuned_run_matches_oracle(shape, dims):
    spec = diffusion(dims, 2)
    x = _rand(shape, seed=dims)
    out, tuned = ops.stencil_auto(x, spec, n_steps=3, backend="interpret",
                                  measure=False, vmem_budget=2 ** 22)
    want = ref.stencil_multistep(x, spec, 3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
    assert tuned.bt >= 1 and tuned.bx % 128 == 0
    assert tuned.variant in engine.variants_for(dims)


def test_ops_none_blocking_autotunes():
    spec = diffusion(2, 1)
    x = _rand((16, 300))
    got = ops.stencil_run(x, spec, n_steps=2, bx=None, bt=None,
                          variant=None, backend="interpret")
    want = ref.stencil_multistep(x, spec, 2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# Autotuner cache + measurement
# ---------------------------------------------------------------------------

def test_autotune_cache_roundtrip():
    spec = diffusion(2, 1)
    p1 = autotune.plan((16, 256), spec, backend="reference", top_k=2,
                       measure=True)
    assert p1.source == "measured"
    assert len(p1.timings) == 2
    p2 = autotune.plan((16, 256), spec, backend="reference", top_k=2)
    assert p2.source == "cache"
    assert (p2.bx, p2.bt, p2.variant) == (p1.bx, p1.bt, p1.variant)
    autotune.clear_cache()
    p3 = autotune.plan((16, 256), spec, backend="reference",
                       measure=False)
    assert p3.source == "model"


def test_autotune_cache_keys_are_problem_specific():
    from repro.core.perf_model import V5E
    spec = diffusion(2, 1)
    vm = V5E.vmem_bytes
    k1 = autotune._key(spec, (16, 256), "float32", "reference", vm, "v5e")
    k2 = autotune._key(spec, (16, 512), "float32", "reference", vm, "v5e")
    k3 = autotune._key(spec, (16, 256), "bfloat16", "reference", vm, "v5e")
    k4 = autotune._key(diffusion(2, 2), (16, 256), "float32", "reference",
                       vm, "v5e")
    k5 = autotune._key(spec, (16, 256), "float32", "reference", 2 ** 22,
                       "v5e")
    assert len({k1, k2, k3, k4, k5}) == 5
    # measured winners persist under the full key...
    autotune.plan((16, 256), spec, backend="reference", measure=True)
    data = autotune._load_cache()
    assert any(k.startswith("diffusion2d_r1|") for k in data)
    # ...model-prior results do not (cheap to recompute; must never
    # shadow a later forced measurement)
    autotune.clear_cache()
    autotune.plan((16, 256), spec, backend="reference", measure=False)
    assert not any(k.startswith("diffusion2d_r1|")
                   for k in autotune._load_cache())


def test_autotune_vmem_budget_not_served_stale_from_cache():
    """A cached plan for the default budget must not satisfy a stricter
    vmem_budget request (the key includes the budget)."""
    spec = diffusion(2, 1)
    big = autotune.plan((32, 1024), spec, backend="reference",
                        measure=True)
    small = autotune.plan((32, 1024), spec, backend="reference",
                          measure=False, vmem_budget=2 ** 20)
    assert small.source != "cache"
    assert small.block_plan.vmem_bytes() <= 2 ** 20
    assert big.block_plan.vmem_bytes() > 0


def test_autotune_raises_with_reasons_when_every_candidate_fails(
        monkeypatch):
    def refused(*a, **k):
        raise RuntimeError("Ran out of memory in memory space vmem")
    monkeypatch.setattr(ops, "stencil_run", refused)
    with pytest.raises(RuntimeError) as e:
        autotune.plan((16, 256), diffusion(2, 1), backend="reference",
                      top_k=2, measure=True)
    msg = str(e.value)
    assert "every candidate" in msg
    assert msg.count("Ran out of memory in memory space vmem") == 2


def test_autotune_records_refused_candidates(monkeypatch):
    real = ops.stencil_run

    def refuse_bt1(x, spec, n, **kw):
        if kw["bt"] == 1:
            raise RuntimeError("refused")
        return real(x, spec, n, **kw)
    monkeypatch.setattr(ops, "stencil_run", refuse_bt1)
    tuned = autotune.plan((16, 256), diffusion(2, 1), backend="reference",
                          top_k=1 << 10, measure=True)
    assert tuned.source == "measured" and tuned.bt != 1
    assert tuned.failures
    assert all(bt == 1 and "refused" in why
               for (_, bt, _), why in tuned.failures.items())


def test_autotune_large_grids_skip_measurement():
    spec = diffusion(2, 1)
    calls = []

    def timer():
        calls.append(1)
        import time
        return time.perf_counter()

    tuned = autotune.plan((8192, 8192), spec, backend="reference",
                          timer=timer)
    assert tuned.source == "model"
    assert not calls
