"""Stencil-IR coverage: boundary modes, box taps, aux operands,
per-step scalars, custom updates — against an *independent* NumPy
golden model, through the oracle (kernels/ref.py) and the engine
(kernels/engine.py), single-device and sharded.

The NumPy golden below shares no code with the jnp oracle (np.pad +
explicit tap loops), so a sign/offset convention bug in one cannot
cancel in the other. Multi-device cases run in subprocesses with
``--xla_force_host_platform_device_count`` (same pattern as
tests/test_halo.py).
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.stencil import (AuxOperand, StencilSpec, box_spec,
                                diffusion, shift, star_as_box)
from repro.kernels import engine, ops, ref

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
TOL = dict(rtol=3e-5, atol=3e-5)


def _rand(shape, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal(shape), jnp.float32)


@pytest.fixture(autouse=True)
def _isolated_cache(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE",
                       str(tmp_path / "autotune.json"))


# ---------------------------------------------------------------------------
# NumPy golden model
# ---------------------------------------------------------------------------

def np_stencil_step(x: np.ndarray, spec: StencilSpec) -> np.ndarray:
    """One step of a star/box spec in pure numpy (independent golden)."""
    r = spec.radius
    mode = "edge" if spec.boundary == "clamp" else "constant"
    p = np.pad(x, r, mode=mode)
    out = np.zeros_like(x)
    if spec.layout == "box":
        bw = np.asarray(spec.box_weights, dtype=np.float64)
        it = np.ndindex(*bw.shape)
    else:
        bw = None
        it = None
    if spec.layout == "star":
        out += np.float32(spec.center) * x
        aw = np.asarray(spec.axis_weights, dtype=np.float64)
        for a in range(spec.dims):
            for o in range(-r, r + 1):
                w = aw[a, r + o]
                if o == 0 or w == 0.0:
                    continue
                sl = [slice(r, r + n) for n in x.shape]
                sl[a] = slice(r + o, r + o + x.shape[a])
                out += np.float32(w) * p[tuple(sl)]
    else:
        for idx in it:
            w = bw[idx]
            if w == 0.0:
                continue
            sl = [slice(r + (i - r), r + (i - r) + n)
                  for i, n in zip(idx, x.shape)]
            out += np.float32(w) * p[tuple(sl)]
    return out


def np_multistep(x, spec, n_steps):
    for _ in range(n_steps):
        x = np_stencil_step(x, spec)
    return x


# ---------------------------------------------------------------------------
# Clamp vs Dirichlet golden tests, r in 1..4, 2D and 3D
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("radius", [1, 2, 3, 4])
@pytest.mark.parametrize("boundary", ["dirichlet0", "clamp"])
def test_golden_2d(radius, boundary):
    spec = diffusion(2, radius, boundary=boundary)
    x = _rand((23, 261), seed=radius)
    want = np_multistep(np.asarray(x, np.float32), spec, 2)
    got_ref = ref.stencil_multistep(x, spec, 2)
    np.testing.assert_allclose(np.asarray(got_ref), want, **TOL)
    for variant in engine.VARIANTS_2D:
        got = engine.stencil_call(x, spec, bx=128, bt=2, variant=variant,
                                  backend="interpret")
        np.testing.assert_allclose(np.asarray(got), want, **TOL,
                                   err_msg=f"{boundary} r={radius} {variant}")


@pytest.mark.parametrize("radius", [1, 2, 3, 4])
@pytest.mark.parametrize("boundary", ["dirichlet0", "clamp"])
def test_golden_3d(radius, boundary):
    spec = diffusion(3, radius, boundary=boundary)
    x = _rand((7, 11, 263), seed=radius)
    want = np_multistep(np.asarray(x, np.float32), spec, 2)
    got_ref = ref.stencil_multistep(x, spec, 2)
    np.testing.assert_allclose(np.asarray(got_ref), want, **TOL)
    got = engine.stencil_call(x, spec, bx=128, bt=2, backend="interpret")
    np.testing.assert_allclose(np.asarray(got), want, **TOL,
                               err_msg=f"{boundary} r={radius}")


def test_clamp_actually_differs_from_dirichlet():
    """Guard against a fill that silently degrades to zeroing."""
    x = _rand((16, 140), seed=9)
    a = ref.stencil_multistep(x, diffusion(2, 1), 3)
    b = ref.stencil_multistep(x, diffusion(2, 1, boundary="clamp"), 3)
    assert float(jnp.max(jnp.abs(a - b))) > 1e-3


# ---------------------------------------------------------------------------
# Box taps
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dims", [2, 3])
@pytest.mark.parametrize("boundary", ["dirichlet0", "clamp"])
def test_box_embeds_star(dims, boundary):
    """A star spec re-expressed as a box tensor is the same operator."""
    spec = diffusion(dims, 2, boundary=boundary)
    bspec = star_as_box(spec)
    shape = (23, 261) if dims == 2 else (6, 11, 133)
    x = _rand(shape, seed=dims)
    want = ref.stencil_multistep(x, spec, 2)
    np.testing.assert_allclose(
        np.asarray(ref.stencil_multistep(x, bspec, 2)),
        np.asarray(want), rtol=1e-5, atol=1e-5)
    got = ops.stencil_sweep(x, bspec, bx=128, bt=2, backend="interpret")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)


@pytest.mark.parametrize("dims", [2, 3])
def test_box_with_diagonal_taps_golden(dims):
    """A genuine box (nonzero diagonals — inexpressible as a star)
    against the numpy golden, oracle and engine."""
    rng = np.random.default_rng(7)
    bw = rng.standard_normal((3,) * dims) * 0.05
    spec = box_spec(bw, boundary="clamp", name=f"rbox{dims}")
    assert spec.layout == "box" and spec.radius == 1
    shape = (19, 150) if dims == 2 else (6, 9, 140)
    x = _rand(shape, seed=dims + 10)
    want = np_multistep(np.asarray(x, np.float32), spec, 2)
    np.testing.assert_allclose(
        np.asarray(ref.stencil_multistep(x, spec, 2)), want, **TOL)
    got = ops.stencil_sweep(x, spec, bx=128, bt=2, backend="interpret")
    np.testing.assert_allclose(np.asarray(got), want, **TOL)


# ---------------------------------------------------------------------------
# Variable coefficients (coeff aux + custom update) and per-step scalars
# ---------------------------------------------------------------------------

def _varcoef_update(fields, spec):
    """Heterogeneous-material diffusion: j += s_t * c * laplacian(j)."""
    j, c, s = fields["x"], fields["c"], fields["scalars"]
    lap = (shift(j, 0, -1, "clamp") + shift(j, 0, 1, "clamp")
           + shift(j, 1, -1, "clamp") + shift(j, 1, 1, "clamp") - 4.0 * j)
    return j + s[0] * c * lap


VARCOEF = StencilSpec(dims=2, radius=1, boundary="clamp",
                      update=_varcoef_update, n_scalars=1,
                      aux=(AuxOperand("c", role="coeff"),),
                      name="varcoef_test")


def test_variable_coefficient_parity():
    """Custom update + coeff operand + per-step scalars: the engine
    (both variants) matches a hand-written jnp evolution."""
    x = _rand((27, 197), seed=3)
    c = jnp.asarray(np.random.default_rng(4).uniform(0.05, 0.2, x.shape),
                    jnp.float32)
    scal = jnp.asarray([[0.3], [0.1], [0.2]], jnp.float32)

    def hand(j):
        for t in range(3):
            lap = (shift(j, 0, -1, "clamp") + shift(j, 0, 1, "clamp")
                   + shift(j, 1, -1, "clamp") + shift(j, 1, 1, "clamp")
                   - 4.0 * j)
            j = j + scal[t, 0] * c * lap
        return j

    want = hand(x)
    np.testing.assert_allclose(
        np.asarray(ref.stencil_multistep(x, VARCOEF, 3, aux={"c": c},
                                         scalars=scal)),
        np.asarray(want), rtol=1e-5, atol=1e-5)
    for variant in engine.VARIANTS_2D:
        got = ops.stencil_sweep(x, VARCOEF, bx=128, bt=3,
                                backend="interpret", variant=variant,
                                aux={"c": c}, scalars=scal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   **TOL, err_msg=variant)


def test_spec_validation_ir():
    with pytest.raises(ValueError, match="exactly one"):
        StencilSpec(dims=2, radius=1)                       # no layout
    with pytest.raises(ValueError, match="boundary"):
        diffusion(2, 1, boundary="reflect")
    with pytest.raises(ValueError, match="coeff"):
        StencilSpec(dims=2, radius=1, center=1.0,
                    axis_weights=((0.0, 0.0, 0.0), (0.0, 0.0, 0.0)),
                    aux=(AuxOperand("c", role="coeff"),))
    with pytest.raises(ValueError, match="2D-only"):
        StencilSpec(dims=3, radius=1, update=lambda f, s: f["x"])
    with pytest.raises(ValueError, match="reserved"):
        StencilSpec(dims=2, radius=1, update=lambda f, s: f["x"],
                    aux=(AuxOperand("x", role="coeff"),))
    # box center is derived from the tensor
    s = box_spec(np.full((3, 3), 0.1))
    assert s.center == pytest.approx(0.1)
    assert s.points == 9 and s.flops_per_cell == 17


def test_engine_requires_declared_operands():
    x = _rand((16, 140))
    with pytest.raises(ValueError, match="requires aux"):
        ops.stencil_sweep(x, VARCOEF, bx=128, bt=1, backend="interpret",
                          scalars=jnp.ones((1, 1)))
    spec = diffusion(2, 1)
    with pytest.raises(ValueError, match="unknown aux"):
        ops.stencil_sweep(x, spec, bx=128, bt=1, backend="interpret",
                          aux={"bogus": x})


def test_sharded_runner_rejects_unknown_operands():
    """The sharded path must fail as loudly as the single-device path —
    silently dropping a typo'd operand would compute without it."""
    from repro.distributed import halo
    x = _rand((16, 140))
    with pytest.raises(ValueError, match="unknown aux"):
        halo.stencil_run_sharded(x, diffusion(2, 1), 2, n_devices=1,
                                 bx=128, bt=1, backend="interpret",
                                 aux={"bogus": x})
    with pytest.raises(ValueError, match="shape"):
        halo.stencil_run_sharded(
            x, StencilSpec(dims=2, radius=1, center=1.0,
                           axis_weights=((0.0,) * 3,) * 2,
                           aux=(AuxOperand("s"),), name="s1"),
            2, n_devices=1, bx=128, bt=1, backend="interpret",
            aux={"s": _rand((8, 140))})


def test_srad_blocked_resolves_blocking_once(tmp_path, monkeypatch):
    """bx/bt left None must hit the autotuner once for the whole run,
    not once per iteration."""
    from repro.apps import problems, srad
    from repro.kernels import autotune
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "at.json"))
    calls = []
    real = autotune.plan

    def spy(*a, **kw):
        calls.append(1)
        return real(*a, **kw)

    monkeypatch.setattr(autotune, "plan", spy)
    img = problems.srad(jax.random.PRNGKey(3), 16, 128)
    srad.srad_blocked(img, 5, backend="interpret")
    assert len(calls) == 1


# ---------------------------------------------------------------------------
# ops.stencil_sweep unification (satellite): autotuner deferral +
# n_devices routing, same resolution path as stencil_run
# ---------------------------------------------------------------------------

def test_stencil_sweep_defers_to_autotuner(monkeypatch):
    from repro.kernels import autotune
    calls = []
    real = autotune.plan

    def spy(*a, **kw):
        calls.append(kw)
        return real(*a, **kw)

    monkeypatch.setattr(autotune, "plan", spy)
    x = _rand((16, 300))
    spec = diffusion(2, 1)
    got = ops.stencil_sweep(x, spec, backend="interpret")   # all defaults
    assert calls, "stencil_sweep must resolve (bx, bt) through the tuner"
    # one sweep of the tuned bt steps — compare against the oracle at
    # whatever bt the tuner picked (through the public resolve-once
    # entry point, the same one apps/benchmarks use)
    bx, bt, _ = ops.resolve_blocking(x, spec, backend="interpret")
    want = ref.stencil_multistep(x, spec, bt)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_stencil_sweep_routes_n_devices(monkeypatch):
    """stencil_sweep no longer silently ignores n_devices: it must hand
    the sweep to the sharded runner with n_steps == bt."""
    from repro.distributed import halo
    seen = {}

    def spy(x, spec, n_steps, **kw):
        seen.update(n_steps=n_steps, **kw)
        return x

    monkeypatch.setattr(halo, "stencil_run_sharded", spy)
    x = _rand((16, 300))
    ops.stencil_sweep(x, diffusion(2, 1), bx=128, bt=2,
                      backend="interpret", n_devices=2)
    assert seen["n_steps"] == 2 and seen["bt"] == 2
    assert seen["n_devices"] == 2


# ---------------------------------------------------------------------------
# Sharded: clamp applies at true grid edges only (ghost cells keep
# exchanging), aux operands shard, SRAD/Hotspot acceptance end-to-end.
# One subprocess per forced-device-count scenario (see module docstring).
# ---------------------------------------------------------------------------

def _run(script: str, devices: int) -> str:
    env = dict(os.environ,
               PYTHONPATH=SRC,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                         env=env, capture_output=True, text=True,
                         timeout=900)
    assert out.returncode == 0, f"stdout:{out.stdout}\nstderr:{out.stderr}"
    return out.stdout


def test_sharded_clamp_and_ir_operands():
    """4-way sharded, shard-unaligned grids: clamp parity vs the
    single-device oracle for 2D/3D (if shard-interior edges were
    clamped — instead of exchanging ghost cells — interior rows would
    see replicated values and the comparison would fail), plus aux
    sources, coeff operands and per-step scalars through the halo
    runner."""
    _run("""
        import numpy as np, jax, jax.numpy as jnp
        assert len(jax.devices()) == 4
        from repro.core.stencil import (AuxOperand, StencilSpec,
                                        diffusion, shift)
        from repro.kernels import ops, ref
        rng = np.random.default_rng(11)
        x = jnp.asarray(rng.standard_normal((67, 197)), jnp.float32)
        # clamp, radius sweep, remainder sweep (n_steps=5)
        for radius in (1, 2):
            spec = diffusion(2, radius, boundary="clamp")
            want = ref.stencil_multistep(x, spec, 5)
            for bt in (1, 2, 4):
                got = ops.stencil_run(x, spec, 5, bx=128, bt=bt,
                                      backend="interpret", n_devices=4)
                np.testing.assert_allclose(
                    np.asarray(got), np.asarray(want),
                    rtol=5e-5, atol=5e-5, err_msg=f"r={radius} bt={bt}")
        # 3D clamp (z is the sharded axis -> plane-replication edges)
        x3 = jnp.asarray(rng.standard_normal((23, 9, 133)), jnp.float32)
        spec3 = diffusion(3, 1, boundary="clamp")
        want3 = ref.stencil_multistep(x3, spec3, 4)
        got3 = ops.stencil_run(x3, spec3, 4, bx=128, bt=2,
                               backend="interpret", n_devices=4)
        np.testing.assert_allclose(np.asarray(got3), np.asarray(want3),
                                   rtol=5e-5, atol=5e-5)
        # coeff aux + scalars through the sharded runner
        def upd(fields, spec):
            j, c, s = fields["x"], fields["c"], fields["scalars"]
            lap = (shift(j, 0, -1, "clamp") + shift(j, 0, 1, "clamp")
                   + shift(j, 1, -1, "clamp") + shift(j, 1, 1, "clamp")
                   - 4.0 * j)
            return j + s[0] * c * lap
        vspec = StencilSpec(dims=2, radius=1, boundary="clamp",
                            update=upd, n_scalars=1,
                            aux=(AuxOperand("c", role="coeff"),),
                            name="varcoef")
        c = jnp.asarray(rng.uniform(0.05, 0.2, x.shape), jnp.float32)
        scal = jnp.asarray(rng.uniform(0.05, 0.25, (5, 1)), jnp.float32)
        want = ref.stencil_multistep(x, vspec, 5, aux={"c": c},
                                     scalars=scal)
        got = ops.stencil_run(x, vspec, 5, bx=128, bt=2,
                              backend="interpret", n_devices=4,
                              aux={"c": c}, scalars=scal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=5e-5, atol=5e-5)
        print("OK")
    """, devices=4)


def test_apps_on_engine_forced_4_device():
    """Acceptance: srad_blocked and hotspot run end-to-end through
    ops.stencil_run on 4 forced devices, matching their reference
    implementations for n_iter/n_steps = 8 and bt in {1, 2, 4}."""
    _run("""
        import numpy as np, jax, jax.numpy as jnp
        assert len(jax.devices()) == 4
        from repro.apps import hotspot, problems, srad
        KEY = jax.random.PRNGKey(0)
        img = problems.srad(KEY, 45, 150)      # shard-unaligned rows
        want = srad.srad_fused(img, 8)
        for bt in (1, 2, 4):
            got = srad.srad_blocked(img, 8, bt=bt, bx=128,
                                    backend="interpret", n_devices=4)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=1e-4, atol=1e-5,
                                       err_msg=f"srad bt={bt}")
        t, p = problems.hotspot(KEY, 45, 260)
        want = hotspot.hotspot_reference(t, p, 8)
        for bt in (1, 2, 4):
            got = hotspot.hotspot_blocked(t, p, 8, bt=bt, bx=128,
                                          backend="interpret", n_devices=4)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=1e-4, atol=1e-3,
                                       err_msg=f"hotspot bt={bt}")
        print("OK")
    """, devices=4)


# ---------------------------------------------------------------------------
# Autotuner / perf model IR-awareness
# ---------------------------------------------------------------------------

def test_cache_key_carries_ir_fields():
    from repro.core.perf_model import V5E
    from repro.kernels import autotune
    vm = V5E.vmem_bytes
    base = diffusion(2, 1)
    keys = {
        autotune._key(s, (16, 256), "float32", "reference", vm, "v5e")
        for s in (base, diffusion(2, 1, boundary="clamp"),
                  star_as_box(base), VARCOEF)
    }
    assert len(keys) == 4        # boundary / layout / aux+scalars split
    k = autotune._key(base, (16, 256), "float32", "reference", vm, "v5e")
    assert "|nd1|" in k          # device suffix still present
    assert "|hb-|" in k          # HBM-budget suffix present (v5)
    assert k.endswith("|plhost")  # pipeline-mode suffix terminal (v8)


def test_blockplan_counts_aux_traffic():
    from repro.core.blocking import BlockPlan
    from repro.apps import hotspot
    plain = BlockPlan(diffusion(2, 1), (256, 1024), bx=256, bt=1)
    with_aux = BlockPlan(hotspot.spec_of(hotspot.HotspotParams()),
                         (256, 1024), bx=256, bt=1)
    assert with_aux.n_aux == 1
    # one extra operand read per sweep
    extra = with_aux.hbm_bytes_per_sweep() - plain.hbm_bytes_per_sweep()
    assert extra == pytest.approx(256 * 1024 * 4)
    assert with_aux.vmem_bytes() > plain.vmem_bytes()
    # sources are pre-summed into ONE stream: two source operands cost
    # the same as one, while a coeff operand adds its own stream
    two_src = StencilSpec(
        dims=2, radius=1, center=1.0, axis_weights=((0.0,) * 3,) * 2,
        aux=(AuxOperand("a"), AuxOperand("b")), name="two_src")
    assert BlockPlan(two_src, (256, 1024), bx=256, bt=1).n_aux == 1
    src_and_coeff = StencilSpec(
        dims=2, radius=1, update=lambda f, s: f["x"],
        aux=(AuxOperand("a"), AuxOperand("c", role="coeff")), name="sc")
    assert BlockPlan(src_and_coeff, (256, 1024), bx=256, bt=1).n_aux == 2


def test_autotune_measures_specs_with_operands():
    """Declared operands must not break the measurement race — the
    tuner synthesizes zeros/ones of the declared shapes."""
    from repro.apps import hotspot
    from repro.kernels import autotune
    spec = hotspot.spec_of(hotspot.HotspotParams())
    tuned = autotune.plan((16, 256), spec, backend="reference",
                          measure=True, top_k=2)
    assert tuned.source == "measured"
    assert tuned.timings


# ---------------------------------------------------------------------------
# Batch-aware autotuner cache (satellite): B in the key, version-bump
# invalidation of PR-3 entries, --retune re-measurement under a
# batched plan.
# ---------------------------------------------------------------------------

def test_autotune_cache_key_distinguishes_batch_sizes():
    from repro.core.perf_model import V5E
    from repro.kernels import autotune
    spec = diffusion(2, 1)
    vm = V5E.vmem_bytes
    ks = {autotune._key(spec, (16, 256), "float32", "reference", vm,
                        "v5e", batch=b) for b in (1, 2, 8)}
    assert len(ks) == 3
    # the batched plan() call and the unbatched one hit different
    # entries even though the per-problem grid is identical
    autotune.plan((16, 256), spec, backend="reference", measure=True)
    autotune.plan((4, 16, 256), spec, backend="reference", measure=True)
    keys = [k for k in autotune._load_cache()
            if k.startswith("diffusion2d_r1|")]
    assert len(keys) == 2
    assert any("|B1|" in k for k in keys)
    assert any("|B4|" in k for k in keys)


def test_autotune_version_bump_invalidates_v3_entries(tmp_path,
                                                      monkeypatch):
    """A PR-3 (version 3) cache file must be dropped wholesale — its
    keys have no batch field, so reading one as a current entry would
    silently misapply an unbatched answer to a batched problem."""
    import json
    from repro.kernels import autotune
    path = tmp_path / "stale.json"
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(path))
    autotune._MEM.clear()
    stale_key = ("diffusion2d_r1|d2|r1|bdirichlet0|Lstar|ax-|sc0|"
                 "16x256|float32|reference|vm100663296|tpu-v5e|nd1")
    path.write_text(json.dumps(
        {"version": 3,
         stale_key: {"bx": 512, "bt": 16, "variant": "multioperand",
                     "source": "measured"}}))
    assert autotune._load_cache() == {}          # ignored, not misread
    tuned = autotune.plan((16, 256), diffusion(2, 1),
                          backend="reference", measure=False)
    assert tuned.source == "model"               # not "cache"
    assert (tuned.bx, tuned.bt) != (512, 16)


def test_retune_remeasures_under_batched_plan():
    """clear_cache (what benchmarks/run.py --retune does) must force a
    fresh measurement of a batched problem, not serve the old winner."""
    from repro.kernels import autotune
    spec = diffusion(2, 1)
    p1 = autotune.plan((3, 16, 256), spec, backend="reference",
                       measure=True, top_k=2)
    assert p1.source == "measured" and p1.timings
    assert autotune.plan((3, 16, 256), spec, backend="reference",
                         top_k=2).source == "cache"
    autotune.clear_cache()
    p2 = autotune.plan((3, 16, 256), spec, backend="reference",
                       measure=True, top_k=2)
    assert p2.source == "measured" and p2.timings
    # the block plan always covers ONE problem of the batch
    assert p2.block_plan.grid_shape == (16, 256)


def test_autotune_rejects_bad_rank():
    from repro.kernels import autotune
    with pytest.raises(ValueError, match="batch"):
        autotune.plan((2, 2, 16, 256), diffusion(2, 1),
                      backend="reference")


# ---------------------------------------------------------------------------
# Batch-dim validation (satellite): every mismatch gets its own clear
# error from ops, *before* anything reaches a kernel.
# ---------------------------------------------------------------------------

def test_ops_rejects_unbatched_aux_for_batched_grid():
    spec = StencilSpec(dims=2, radius=1, center=1.0,
                       axis_weights=((0.0,) * 3,) * 2,
                       aux=(AuxOperand("p"),), name="bsrc")
    xb = _rand((3, 16, 140))
    with pytest.raises(ValueError, match="missing the batch axis"):
        ops.stencil_run(xb, spec, 2, bx=128, bt=1, backend="interpret",
                        aux={"p": _rand((16, 140))})


def test_ops_rejects_wrong_batch_dim_on_aux():
    spec = StencilSpec(dims=2, radius=1, center=1.0,
                       axis_weights=((0.0,) * 3,) * 2,
                       aux=(AuxOperand("p"),), name="bsrc2")
    xb = _rand((3, 16, 140))
    with pytest.raises(ValueError,
                       match="batch dim 2 != grid batch dim 3"):
        ops.stencil_run(xb, spec, 2, bx=128, bt=1, backend="interpret",
                        aux={"p": _rand((2, 16, 140))})


def test_ops_rejects_batched_operand_for_unbatched_grid():
    x = _rand((16, 140))
    with pytest.raises(ValueError, match="grid .* is unbatched"):
        ops.stencil_sweep(x, diffusion(2, 1), bx=128, bt=1,
                          backend="interpret",
                          source=_rand((3, 16, 140)))


def test_ops_rejects_mismatched_scalar_batch():
    xb = _rand((3, 16, 140))
    with pytest.raises(ValueError,
                       match="scalars batch dim 2 != grid batch dim 3"):
        ops.stencil_run(xb, VARCOEF, 2, bx=128, bt=1,
                        backend="interpret",
                        aux={"c": _rand((3, 16, 140))},
                        scalars=jnp.ones((2, 2, 1)))
    x = _rand((16, 140))
    with pytest.raises(ValueError, match="per-problem"):
        ops.stencil_run(x, VARCOEF, 2, bx=128, bt=1,
                        backend="interpret", aux={"c": x},
                        scalars=jnp.ones((3, 2, 1)))


def test_ops_rejects_legacy_source_batch_mismatch():
    xb = _rand((3, 16, 140))
    with pytest.raises(ValueError, match="missing the batch axis"):
        ops.stencil_run(xb, diffusion(2, 1), 2, bx=128, bt=1,
                        backend="interpret", source=_rand((16, 140)))


# ---------------------------------------------------------------------------
# Property-based IR suite (satellite): random specs (dims, radius,
# star/box/custom, boundary, aux roles, scalars) x random batch sizes,
# engine == independent NumPy golden == jax.vmap fallback. Guarded so
# the no-dev-deps CI degrades to a skip, not a collection error (the
# module-level importorskip pattern of test_stencil_kernels.py would
# skip this whole file, which carries non-hypothesis tests too).
# ---------------------------------------------------------------------------

try:
    from hypothesis import HealthCheck, given, settings, strategies as st
    _HAS_HYPOTHESIS = True
except ImportError:          # no-dev-deps CI
    _HAS_HYPOTHESIS = False


def _np_custom_step(x, c, s):
    """NumPy golden for the fixed custom update below (clamp
    laplacian heterogeneous diffusion) — independent of jnp."""
    p = np.pad(x, 1, mode="edge")
    lap = (p[:-2, 1:-1] + p[2:, 1:-1] + p[1:-1, :-2] + p[1:-1, 2:]
           - 4.0 * x)
    return x + np.float32(s) * c * lap


def _check_ir_problem(dims, layout, radius, boundary, with_src, B, bt,
                      shape, seed):
    """One randomized IR problem: batched engine vs NumPy golden vs
    jax.vmap fallback (the property, shared by the hypothesis suite
    and the pinned no-dev-deps cases)."""
    rng = np.random.default_rng(seed)
    xs = rng.standard_normal((B,) + shape).astype(np.float32)
    x = jnp.asarray(xs)
    aux = scalars = src = None
    c = scal = None
    if layout == "star":
        spec = diffusion(dims, radius, boundary=boundary)
    elif layout == "box":
        bw = rng.standard_normal((2 * radius + 1,) * dims) * 0.05
        spec = box_spec(bw, boundary=boundary,
                        name=f"pbox{dims}r{radius}")
    else:
        spec = VARCOEF
        c = rng.uniform(0.05, 0.2, (B,) + shape).astype(np.float32)
        scal = rng.uniform(0.05, 0.3, (B, bt, 1)).astype(np.float32)
        aux = {"c": jnp.asarray(c)}
        scalars = jnp.asarray(scal)
    if with_src:
        src = rng.standard_normal((B,) + shape).astype(np.float32)

    # Independent NumPy golden, one problem at a time
    want = []
    for b in range(B):
        g = xs[b]
        for t in range(bt):
            if layout == "custom":
                g = _np_custom_step(g, c[b], scal[b, t, 0])
            else:
                g = np_stencil_step(g, spec)
                if src is not None:
                    g = g + src[b]
        want.append(g)
    want = np.stack(want)

    kw = dict(bx=128, bt=bt, backend="interpret", aux=aux, scalars=scalars,
              source=None if src is None else jnp.asarray(src))
    got = engine.stencil_call(x, spec, **kw)
    np.testing.assert_allclose(np.asarray(got), want,
                               rtol=1e-4, atol=1e-4)
    vm = engine.stencil_call_vmap(x, spec, **kw)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(vm))


# Pinned instances of the property — always run, with or without
# hypothesis, so the no-dev-deps CI keeps real (if narrower) coverage.
_PINNED = [
    (2, "star", 3, "dirichlet0", True, 2, 2, (13, 141), 11),
    (2, "box", 1, "clamp", False, 3, 2, (10, 133), 12),
    (2, "custom", 1, "clamp", False, 2, 2, (12, 131), 13),
    (3, "star", 2, "clamp", True, 2, 1, (4, 7, 134), 14),
    (3, "box", 1, "dirichlet0", False, 1, 2, (5, 6, 139), 15),
]


@pytest.mark.parametrize("case", _PINNED,
                         ids=[f"{c[0]}d-{c[1]}-{c[3]}-B{c[5]}"
                              for c in _PINNED])
def test_ir_pinned_batched_golden_vmap(case):
    _check_ir_problem(*case)


if _HAS_HYPOTHESIS:

    @st.composite
    def _ir_problems(draw):
        dims = draw(st.sampled_from([2, 3]))
        layout = draw(st.sampled_from(
            ["star", "box", "custom"] if dims == 2 else ["star", "box"]))
        if layout == "custom":
            radius, boundary = 1, "clamp"    # the fixed update's cone
        else:
            radius = draw(st.integers(1, 4 if dims == 2 else 2))
            boundary = draw(st.sampled_from(["dirichlet0", "clamp"]))
        with_src = draw(st.booleans()) and layout != "custom"
        B = draw(st.sampled_from([1, 2, 3]))
        bt = draw(st.sampled_from([1, 2]))
        if dims == 2:
            shape = (draw(st.integers(9, 21)),
                     draw(st.integers(129, 148)))
        else:
            shape = (draw(st.integers(3, 6)), draw(st.integers(5, 9)),
                     draw(st.integers(129, 140)))
        seed = draw(st.integers(0, 2 ** 20))
        return (dims, layout, radius, boundary, with_src, B, bt, shape,
                seed)

    @settings(max_examples=10, deadline=None, derandomize=True,
              suppress_health_check=[HealthCheck.too_slow])
    @given(_ir_problems())
    def test_property_batched_engine_golden_vmap(problem):
        _check_ir_problem(*problem)

else:

    @pytest.mark.skip(reason="hypothesis not installed (dev-only dep; "
                             "see requirements-dev.txt) — the pinned "
                             "cases above still run")
    def test_property_batched_engine_golden_vmap():
        pass
