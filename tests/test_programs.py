"""StencilProgram: DAG validation, fusion legality, engine parity.

Covers the program layer end to end: ``core.stencil`` construction and
fuse-group analysis, the multi-sweep engine dispatch
(``engine.stencil_call_program``), the scheduler
(``ops.stencil_program_run``) against the pure-jnp oracle and against
composed NumPy goldens, dispatch accounting, the program-aware
autotuner cache (v8 rejects older files), the serving bucket key, and the
forced-multi-device sharded runner.

Property tests (random 2-3 sweep programs) run under hypothesis when
it is installed; five pinned instances of the same property always run
so the no-dev-deps CI job keeps real coverage.
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core.stencil import (AuxOperand, ProgramPlanProxy,
                                StencilProgram, StencilSpec, Sweep,
                                diffusion, shift)
from repro.kernels import engine, ops, ref

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
TOL = dict(rtol=5e-5, atol=5e-5)


def _rand(shape, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal(shape), jnp.float32)


def _pair(name="pair"):
    """Fusable 2-sweep program: r1 dirichlet0 then r2 clamp, one field."""
    return StencilProgram(
        (Sweep("a", diffusion(2, 1)),
         Sweep("b", diffusion(2, 2, boundary="clamp"))), name=name)


def _two_field():
    """Unfusable program: second sweep reads the evolving field u."""
    def upd(fields, spec):
        return 0.5 * fields["x"] + 0.5 * shift(fields["u"], 0, 1,
                                               spec.boundary)
    mix = StencilSpec(dims=2, radius=1, update=upd,
                      aux=(AuxOperand("u", role="coeff"),), name="mix")
    return StencilProgram(
        (Sweep("a", diffusion(2, 1), field="u"),
         Sweep("m", mix, field="v")), name="two_field")


# --------------------------------------------------------------------------
# construction & validation
# --------------------------------------------------------------------------

def test_program_requires_sweeps():
    with pytest.raises(ValueError, match="at least one"):
        StencilProgram((), name="empty")


def test_program_rejects_duplicate_sweep_names():
    with pytest.raises(ValueError, match="duplicate sweep"):
        StencilProgram((Sweep("a", diffusion(2, 1)),
                        Sweep("a", diffusion(2, 2))))


def test_program_rejects_mixed_dims():
    with pytest.raises(ValueError, match="dims"):
        StencilProgram((Sweep("a", diffusion(2, 1)),
                        Sweep("b", diffusion(3, 1))))


def test_program_rejects_self_field_aux_read():
    spec = StencilSpec(dims=2, radius=1,
                       update=lambda f, s: f["x"] + f["u"],
                       aux=(AuxOperand("u", role="coeff"),), name="self")
    with pytest.raises(ValueError, match="own field"):
        StencilProgram((Sweep("a", spec, field="u"),))


def test_program_after_must_name_earlier_sweep():
    with pytest.raises(ValueError, match="after"):
        StencilProgram((Sweep("a", diffusion(2, 1), after=("b",)),
                        Sweep("b", diffusion(2, 1))))


def test_program_rejects_reserved_field_names():
    with pytest.raises(ValueError):
        Sweep("a", diffusion(2, 1), field="x")
    with pytest.raises(ValueError):
        Sweep("a", diffusion(2, 1), field="scalars")


def test_program_fields_and_inputs():
    p = _two_field()
    assert p.fields == ("u", "v")
    assert p.input_names == ()
    assert p.n_fields == 2
    w = StencilProgram((Sweep(
        "a", StencilSpec(dims=2, radius=1,
                         update=lambda f, s: f["x"] + f["g"],
                         aux=(AuxOperand("g", role="coeff"),),
                         name="withg")),), name="w")
    assert w.input_names == ("g",)


def test_program_hashable_value_semantics():
    assert _pair() == _pair()
    assert hash(_pair()) == hash(_pair())
    assert _pair() != _two_field()
    assert {_pair(): 1}[_pair()] == 1


def test_cache_token_distinguishes_programs():
    assert _pair().cache_token() != _two_field().cache_token()
    assert _pair().cache_token() == _pair("pair").cache_token()
    assert _pair("x").cache_token() != _pair("y").cache_token()


def test_single_factory_roundtrip():
    spec = diffusion(2, 2)
    p = StencilProgram.single(spec)
    assert p.n_fields == 1 and len(p.sweeps) == 1
    assert p.sweeps[0].spec == spec


# --------------------------------------------------------------------------
# fusion legality
# --------------------------------------------------------------------------

def test_fuse_same_field_no_reads():
    p = _pair()
    assert len(p.fuse_groups()) == 1 and p.fully_fused
    assert p.max_group_radius == 3


def test_barrier_splits_group():
    p = StencilProgram((Sweep("a", diffusion(2, 1)),
                        Sweep("b", diffusion(2, 1), barrier=True)))
    assert len(p.fuse_groups()) == 2 and not p.fully_fused


def test_different_fields_split_group():
    assert len(_two_field().fuse_groups()) == 2


def test_evolving_read_splits_group():
    def upd(fields, spec):
        return fields["x"] + shift(fields["v"], 0, 1, spec.boundary)
    s = StencilSpec(dims=2, radius=1, update=upd,
                    aux=(AuxOperand("v", role="coeff"),), name="readv")
    p = StencilProgram((Sweep("w", diffusion(2, 1), field="v"),
                        Sweep("a", diffusion(2, 1), field="u"),
                        Sweep("b", s, field="u")), name="rd")
    # a and b share field u, but b reads evolving v: no fusion.
    assert [len(g) for g in p.fuse_groups()] == [1, 1, 1]


def test_3d_fusion_requires_equal_radius_and_boundary():
    fuses = StencilProgram((Sweep("a", diffusion(3, 1)),
                            Sweep("b", diffusion(3, 1))))
    assert fuses.fully_fused
    r_mix = StencilProgram((Sweep("a", diffusion(3, 1)),
                            Sweep("b", diffusion(3, 2))))
    assert len(r_mix.fuse_groups()) == 2
    b_mix = StencilProgram((Sweep("a", diffusion(3, 1)),
                            Sweep("b", diffusion(3, 1,
                                                 boundary="clamp"))))
    assert len(b_mix.fuse_groups()) == 2


def test_plan_proxy_shape():
    p = _pair()
    proxy = p.plan_proxy()
    assert isinstance(proxy, ProgramPlanProxy)
    assert proxy.dims == 2
    assert proxy.radius == 3            # fused group: 1 + 2
    assert proxy.halo(2) == 6
    assert proxy.layout == "program"
    p2 = _two_field().plan_proxy()
    assert p2.radius == 1               # max over singleton groups
    # the non-primary field rides as a coeff-like stream
    assert any(a.name == "__field__v" for a in p2.aux)


# --------------------------------------------------------------------------
# oracle semantics
# --------------------------------------------------------------------------

def test_oracle_requires_fields_and_inputs():
    p = _two_field()
    with pytest.raises(ValueError, match="not provided"):
        ref.stencil_program_multistep({"u": _rand((8, 132))}, p, 1)
    w = StencilProgram((Sweep(
        "a", StencilSpec(dims=2, radius=1,
                         update=lambda f, s: f["x"] + f["g"],
                         aux=(AuxOperand("g", role="coeff"),),
                         name="withg")),), name="w")
    with pytest.raises(ValueError, match="requires inputs"):
        ref.stencil_program_multistep({"u": _rand((8, 132))}, w, 1)


def test_oracle_matches_manual_composition():
    p = _pair()
    x = _rand((10, 140), seed=3)
    got = ref.stencil_program_multistep({"u": x}, p, 2)["u"]
    want = x
    for _ in range(2):
        for s in p.sweeps:
            want = ref.stencil_step(want, s.spec)
    # jit of the whole program vs per-sweep graphs: fma contraction can
    # differ by ~1 ulp, so tight allclose rather than bitwise here.
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)


# --------------------------------------------------------------------------
# engine: fused dispatch parity
# --------------------------------------------------------------------------

def test_fused_program_call_equals_per_sweep_calls():
    """ONE fused dispatch == chaining single-spec dispatches, bitwise."""
    p = _pair()
    x = _rand((40, 200), seed=1)
    specs = tuple(s.spec for s in p.sweeps)
    fused = engine.stencil_call_program(x, specs, bx=128, bt=2, backend="interpret")
    loop = x
    for _ in range(2):
        for sp in specs:
            loop = engine.stencil_call(loop, sp, bx=128, bt=1, backend="interpret")
    np.testing.assert_array_equal(np.asarray(fused), np.asarray(loop))


def test_fused_halo_exceeding_tile_is_loud():
    specs = tuple(s.spec for s in _pair().sweeps)
    with pytest.raises(ValueError, match="exceeds the tile width"):
        engine.stencil_call_program(_rand((40, 200)), specs, bx=128,
                                    bt=64, backend="interpret")


def test_run_fuse_true_equals_fuse_false_bitwise():
    p = _pair()
    x = _rand((40, 200), seed=2)
    a = ops.stencil_program_run(x, p, 5, backend="interpret", bx=128,
                                bt=2)
    b = ops.stencil_program_run(x, p, 5, backend="interpret", bx=128,
                                bt=2, fuse=False)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_run_3d_fused_parity():
    p = StencilProgram((Sweep("a", diffusion(3, 1)),
                        Sweep("b", diffusion(3, 1))), name="p3")
    x = _rand((10, 12, 132), seed=4)
    got = ops.stencil_program_run(x, p, 3, backend="interpret", bx=128,
                                  bt=2)
    want = ref.stencil_program_multistep({"u": x}, p, 3)["u"]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)


def test_run_multi_field_vs_oracle():
    p = _two_field()
    f = {"u": _rand((24, 140), seed=5), "v": jnp.zeros((24, 140),
                                                       jnp.float32)}
    got = ops.stencil_program_run(f, p, 3, backend="interpret", bx=128)
    want = ref.stencil_program_multistep(f, p, 3)
    for k in f:
        np.testing.assert_allclose(np.asarray(got[k]),
                                   np.asarray(want[k]), **TOL)


def test_run_batched_equals_solo_bitwise():
    p = _pair()
    xb = _rand((3, 24, 140), seed=6)
    got = ops.stencil_program_run(xb, p, 4, backend="interpret", bx=128,
                                  bt=2)
    solo = jnp.stack([ops.stencil_program_run(xb[i], p, 4,
                                              backend="interpret",
                                              bx=128, bt=2)
                      for i in range(3)])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(solo))


def test_run_validates_fields_and_scalars():
    p = _two_field()
    with pytest.raises(TypeError, match="StencilProgram"):
        ops.stencil_program_run(_rand((8, 132)), diffusion(2, 1), 1)
    with pytest.raises(ValueError, match="dict of grids"):
        ops.stencil_program_run(_rand((8, 132)), p, 1)
    with pytest.raises(ValueError, match="unknown"):
        ops.stencil_program_run({"u": _rand((8, 132)),
                                 "bogus": _rand((8, 132))}, p, 1,
                                backend="interpret", bx=128, bt=1)


def test_dispatch_count_fused_below_loop():
    p = _pair()
    x = _rand((40, 200), seed=7)
    ops.reset_dispatch_count()
    ops.stencil_program_run(x, p, 6, backend="interpret", bx=128, bt=2)
    fused = ops.dispatch_count()
    ops.reset_dispatch_count()
    ops.stencil_program_run(x, p, 6, backend="interpret", bx=128, bt=2,
                            fuse=False)
    loop = ops.dispatch_count()
    assert fused == 3          # ceil(6/2) blocks, one dispatch each
    assert loop == 12          # 6 steps x 2 sweeps
    assert fused < loop


# --------------------------------------------------------------------------
# property: random linear programs vs composed NumPy goldens
# --------------------------------------------------------------------------

def _np_zshift(a, axis, off, boundary):
    if boundary == "clamp":
        pad = [(0, 0)] * a.ndim
        r = abs(off)
        pad[axis] = (r, r)
        padded = np.pad(a, pad, mode="edge")
        idx = [slice(None)] * a.ndim
        idx[axis] = slice(r + off, r + off + a.shape[axis])
        return padded[tuple(idx)]
    out = np.zeros_like(a)
    n = a.shape[axis]
    src = [slice(None)] * a.ndim
    dst = [slice(None)] * a.ndim
    if off >= 0:
        src[axis], dst[axis] = slice(off, None), slice(None, n - off)
    else:
        src[axis], dst[axis] = slice(None, off), slice(-off, None)
    out[tuple(dst)] = a[tuple(src)]
    return out


def _np_star_step(x, spec):
    """NumPy mirror of ref.stencil_step's star tap order (float32)."""
    acc = np.float32(spec.center) * x
    w = np.asarray(spec.axis_weights, np.float64)
    r = spec.radius
    for a in range(spec.dims):
        for o in range(-r, r + 1):
            coeff = float(w[a, r + o])
            if o == 0 or coeff == 0.0:
                continue
            acc = acc + np.float32(coeff) * _np_zshift(x, a, o,
                                                       spec.boundary)
    return acc


def _random_program(seed: int):
    """A random 2-3 sweep single-field star program (the property's
    instance space: radii 1-2, both boundaries, random weights)."""
    rng = np.random.default_rng(seed)
    n_sweeps = int(rng.integers(2, 4))
    sweeps = []
    for i in range(n_sweeps):
        r = int(rng.integers(1, 3))
        aw = rng.uniform(-0.2, 0.2, (2, 2 * r + 1))
        aw[:, r] = 0.0
        boundary = ["dirichlet0", "clamp"][int(rng.integers(0, 2))]
        spec = StencilSpec(dims=2, radius=r,
                           center=float(rng.uniform(0.3, 0.9)),
                           axis_weights=tuple(map(tuple, aw)),
                           boundary=boundary, name=f"rnd{seed}_{i}")
        sweeps.append(Sweep(f"s{i}", spec))
    return StencilProgram(tuple(sweeps), name=f"rnd{seed}")


def _check_program_against_golden(seed: int):
    p = _random_program(seed)
    rng = np.random.default_rng(seed + 1000)
    x0 = rng.standard_normal((20, 140)).astype(np.float32)
    n_steps = int(rng.integers(1, 4))
    want = x0
    for _ in range(n_steps):
        for s in p.sweeps:
            want = _np_star_step(want, s.spec)
    got = ops.stencil_program_run(jnp.asarray(x0), p, n_steps,
                                  backend="interpret", bx=128, bt=2)
    np.testing.assert_allclose(
        np.asarray(got), want, **TOL,
        err_msg=f"seed={seed} sweeps={len(p.sweeps)} n={n_steps}")
    # fuse=False must agree bitwise with the fused schedule
    loop = ops.stencil_program_run(jnp.asarray(x0), p, n_steps,
                                   backend="interpret", bx=128, bt=2,
                                   fuse=False)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(loop))


PINNED_SEEDS = [11, 23, 37, 58, 71]


@pytest.mark.parametrize("seed", PINNED_SEEDS)
def test_random_program_golden_pinned(seed):
    """Five pinned instances of the property — they run with no dev
    deps installed, so the no-dev-deps CI job keeps this coverage."""
    _check_program_against_golden(seed)


try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False


if HAVE_HYPOTHESIS:
    @given(seed=st.integers(min_value=0, max_value=10_000))
    @settings(max_examples=15, deadline=None)
    def test_random_program_golden_property(seed):
        _check_program_against_golden(seed)


# --------------------------------------------------------------------------
# autotune: program plans and the v8 cache version gate
# --------------------------------------------------------------------------

def test_autotune_plans_a_program(tmp_path, monkeypatch):
    from repro.kernels import autotune
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE",
                       str(tmp_path / "cache.json"))
    p = _pair()
    plan = autotune.plan((48, 260), p, backend="interpret", n_steps=4)
    assert plan.bx % 128 == 0 and plan.bt >= 1
    # multi-group programs must only ever get bt == 1
    plan2 = autotune.plan((48, 260), _two_field(), backend="interpret",
                          n_steps=4)
    assert plan2.bt == 1


def test_autotune_rejects_v6_cache(tmp_path, monkeypatch, caplog):
    from repro.kernels import autotune
    path = tmp_path / "cache.json"
    stale_key = "handmade|stale|winner"
    path.write_text(json.dumps({"version": 6,
                                stale_key: {"bx": 128, "bt": 8,
                                            "variant": "revolving",
                                            "source": "measured"}}))
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(path))
    import logging
    with caplog.at_level(logging.WARNING, logger="repro.autotune"):
        tuned = autotune.plan((48, 260), diffusion(2, 1),
                              backend="interpret", n_steps=4,
                              measure=True)
    assert "version 6" in caplog.text and "version 9" in caplog.text
    # every v6 winner is dropped from the live cache...
    assert stale_key not in autotune._load_cache()
    # ...and the re-measured winner persists under a v9 stamp
    assert tuned.source == "measured"
    data = json.loads(path.read_text())
    assert data["version"] == autotune._CACHE_VERSION == 9
    assert stale_key not in data


# --------------------------------------------------------------------------
# serving: program-aware buckets
# --------------------------------------------------------------------------

def test_serving_programs_never_share_buckets():
    """Two different programs on identical grids/dtypes must group into
    different compilation keys (and therefore different dispatches)."""
    from repro.serving.stencil_service import (StencilRequest,
                                               StencilService)
    svc = StencilService(max_batch=8, backend="interpret", bx=128, bt=1)
    pa, pb = _pair("pa"), _pair("pb")
    assert pa != pb
    reqs = []
    for i in range(3):
        reqs.append(StencilRequest(uid=i, x=_rand((10, 132), seed=i),
                                   program=pa, n_steps=2))
    for i in range(3, 6):
        reqs.append(StencilRequest(uid=i, x=_rand((10, 132), seed=i),
                                   program=pb, n_steps=2))
    keys = {svc._key(r) for r in reqs}
    assert len(keys) == 2
    done = svc.run(reqs)
    assert len(done) == 6
    assert svc.metrics["dispatches"] == 2


def test_serving_program_results_match_solo():
    from repro.serving.stencil_service import (StencilRequest,
                                               StencilService)
    svc = StencilService(max_batch=4, backend="interpret", bx=128, bt=1,
                         check=True)   # check asserts parity internally
    p = _pair()
    done = svc.run([StencilRequest(uid=i, x=_rand((10, 132), seed=i),
                                   program=p, n_steps=3)
                    for i in range(3)])
    assert len(done) == 3
    want = ref.stencil_program_multistep(
        {"u": _rand((10, 132), seed=0)}, p, 3)["u"]
    got = [c for c in done if c.uid == 0][0].result
    np.testing.assert_allclose(got, np.asarray(want), **TOL)


def test_serving_program_validation():
    from repro.serving.stencil_service import (StencilRequest,
                                               StencilService)
    svc = StencilService(backend="interpret")
    x = _rand((10, 132))
    with pytest.raises(ValueError, match="exactly one"):
        svc.submit(StencilRequest(uid=0, x=x, n_steps=1))
    with pytest.raises(ValueError, match="exactly one"):
        svc.submit(StencilRequest(uid=0, x=x, spec=diffusion(2, 1),
                                  program=_pair(), n_steps=1))
    with pytest.raises(ValueError, match="single-field"):
        svc.submit(StencilRequest(uid=0, x=x, program=_two_field(),
                                  n_steps=1))


# --------------------------------------------------------------------------
# multi-device: the sharded program runner (forced host devices)
# --------------------------------------------------------------------------

def _run(script: str, devices: int) -> str:
    env = dict(os.environ, PYTHONPATH=SRC,
               XLA_FLAGS=f"--xla_force_host_platform_device_count="
                         f"{devices}")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                         env=env, capture_output=True, text=True,
                         timeout=900)
    assert out.returncode == 0, f"stdout:{out.stdout}\nstderr:{out.stderr}"
    return out.stdout


def test_sharded_program_parity_4dev():
    """Fused AND unfusable programs on 4 forced devices vs the oracle,
    shard-unaligned grid, remainder schedule."""
    _run("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core.stencil import (AuxOperand, StencilProgram,
                                        StencilSpec, Sweep, diffusion,
                                        shift)
        from repro.kernels import ops, ref
        assert len(jax.devices()) == 4
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal((67, 200)), jnp.float32)

        fused = StencilProgram(
            (Sweep("a", diffusion(2, 1)),
             Sweep("b", diffusion(2, 2, boundary="clamp"))), name="f")
        got = ops.stencil_program_run(x, fused, 5, backend="interpret",
                                      bx=128, bt=2, n_devices=4)
        want = ref.stencil_program_multistep({"u": x}, fused, 5)["u"]
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=5e-5, atol=5e-5)

        def upd(fields, spec):
            return (0.5 * fields["x"]
                    + 0.5 * shift(fields["u"], 0, 1, spec.boundary))
        mix = StencilSpec(dims=2, radius=1, update=upd,
                          aux=(AuxOperand("u", role="coeff"),),
                          name="mix")
        unf = StencilProgram((Sweep("a", diffusion(2, 1), field="u"),
                              Sweep("m", mix, field="v")), name="u")
        f = {"u": x, "v": jnp.zeros_like(x)}
        got = ops.stencil_program_run(f, unf, 4, backend="interpret",
                                      bx=128, n_devices=4)
        want = ref.stencil_program_multistep(f, unf, 4)
        for k in f:
            np.testing.assert_allclose(np.asarray(got[k]),
                                       np.asarray(want[k]),
                                       rtol=5e-5, atol=5e-5)
        print("OK")
    """, devices=4)


def test_sharded_program_batch_strategy_4dev():
    _run("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core.stencil import StencilProgram, Sweep, diffusion
        from repro.distributed import halo
        from repro.kernels import ref
        assert len(jax.devices()) == 4
        rng = np.random.default_rng(1)
        xb = jnp.asarray(rng.standard_normal((4, 30, 140)), jnp.float32)
        p = StencilProgram((Sweep("a", diffusion(2, 1)),
                            Sweep("b", diffusion(2, 2))), name="p")
        out = halo.stencil_program_run_sharded({"u": xb}, p, 3,
                                               n_devices=4, bx=128,
                                               bt=2, backend="interpret")
        want = ref.stencil_program_multistep({"u": xb}, p, 3)["u"]
        np.testing.assert_allclose(np.asarray(out["u"]),
                                   np.asarray(want),
                                   rtol=5e-5, atol=5e-5)
        # B % n_devices != 0 no longer raises: it falls back to grid
        # sharding (axis 1) with a warning, same numerical contract.
        import warnings
        with warnings.catch_warnings(record=True) as rec:
            warnings.simplefilter("always")
            out3 = halo.stencil_program_run_sharded({"u": xb[:3]}, p, 3,
                                                    n_devices=4, bx=128, backend="interpret")
        assert any("falling back" in str(w.message) for w in rec), \
            [str(w.message) for w in rec]
        want3 = ref.stencil_program_multistep({"u": xb[:3]}, p, 3)["u"]
        np.testing.assert_allclose(np.asarray(out3["u"]),
                                   np.asarray(want3),
                                   rtol=5e-5, atol=5e-5)
        print("OK")
    """, devices=4)
