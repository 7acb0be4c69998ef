"""Out-of-core tiled stencil execution (repro/outofcore + the budget
plumbing through blocking/perf_model/autotune/ops/serving).

The subsystem's contract is **bitwise equality with the in-core
engine**: the in-core path on the same (bx, bt, variant) is the
differential oracle, and a forced-small HBM budget is what makes the
public entry points actually tile. Every assertion against the engine
here is ``assert_array_equal`` — no tolerances.
"""
import json
import logging

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import perf_model as pm
from repro.core.blocking import (BlockPlan, TilePlan,
                                 incore_resident_bytes, plan_tiles)
from repro.core.stencil import (AuxOperand, StencilSpec, diffusion,
                                shift)
from repro.kernels import ops
from repro.outofcore import exceeds_budget, stencil_run_outofcore


@pytest.fixture(autouse=True)
def _isolated_cache(tmp_path, monkeypatch):
    from repro.kernels import autotune
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE",
                       str(tmp_path / "autotune.json"))
    autotune._MEM.clear()


def _rand(shape, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal(shape), jnp.float32)


def _forcing_budget(spec, grid, itemsize=4, batch=1, frac=0.7):
    """A budget strictly below the in-core working set (so the
    out-of-core route must engage) but big enough to tile under."""
    return int(incore_resident_bytes(spec, grid, itemsize, batch) * frac)


# ---------------------------------------------------------------------------
# Acceptance matrix: bitwise equality vs the in-core engine under a
# forced-small budget — radius 1-4 x {2D, 3D} x bt {1, 2, 4} x both
# boundary modes (n_steps=5 exercises the remainder sweep for bt 2/4).
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("radius", [1, 2, 3, 4])
def test_outofcore_parity_2d(radius):
    x = _rand((140, 140), seed=radius)
    for boundary in ("dirichlet0", "clamp"):
        spec = diffusion(2, radius, boundary=boundary)
        budget = _forcing_budget(spec, x.shape)
        for bt in (1, 2, 4):
            want = np.asarray(ops.stencil_run(
                x, spec, 5, bx=128, bt=bt, backend="interpret"))
            got = ops.stencil_run(x, spec, 5, bx=128, bt=bt,
                                  backend="interpret",
                                  hbm_budget=budget)
            assert isinstance(got, np.ndarray)   # host-resident result
            np.testing.assert_array_equal(
                got, want, err_msg=f"r={radius} bt={bt} {boundary}")


@pytest.mark.parametrize("radius", [1, 2, 3, 4])
def test_outofcore_parity_3d(radius):
    x = _rand((140, 8, 128), seed=radius)
    for boundary in ("dirichlet0", "clamp"):
        spec = diffusion(3, radius, boundary=boundary)
        budget = _forcing_budget(spec, x.shape)
        for bt in (1, 2, 4):
            want = np.asarray(ops.stencil_run(
                x, spec, 5, bx=128, bt=bt, backend="interpret"))
            got = ops.stencil_run(x, spec, 5, bx=128, bt=bt,
                                  backend="interpret",
                                  hbm_budget=budget)
            assert isinstance(got, np.ndarray)
            np.testing.assert_array_equal(
                got, want, err_msg=f"r={radius} bt={bt} {boundary}")


def test_ghost_deeper_than_tile_stays_exact():
    """No ghost <= tile constraint (unlike the sharded runner): a
    1-slice tile under a 16-deep ghost (r=4, bt=4) is exact."""
    spec = diffusion(2, 4, boundary="clamp")
    x = _rand((41, 140))
    want = np.asarray(ops.stencil_run(x, spec, 4, bx=128, bt=4,
                                      backend="interpret"))
    got = stencil_run_outofcore(x, spec, 4, bx=128, bt=4,
                                backend="interpret", tile=1)
    np.testing.assert_array_equal(got, want)


def test_tile_not_dividing_extent_and_single_tile():
    spec = diffusion(2, 2)
    x = _rand((37, 140))
    want = np.asarray(ops.stencil_run(x, spec, 3, bx=128, bt=2,
                                      backend="interpret"))
    for tile in (7, 36, 37):        # remainder tile / near-full / full
        got = stencil_run_outofcore(x, spec, 3, bx=128, bt=2,
                                    backend="interpret", tile=tile)
        np.testing.assert_array_equal(got, want, err_msg=f"tile={tile}")


# ---------------------------------------------------------------------------
# Aux operands, scalars, batches — streamed per tile exactly like the
# halo runner shards them.
# ---------------------------------------------------------------------------

def test_outofcore_source_operand_hotspot():
    """Hotspot: clamp boundary + power as a declared source operand."""
    from repro.apps import hotspot
    spec = hotspot.spec_of(hotspot.HotspotParams())
    x, p = _rand((96, 140), 1), _rand((96, 140), 2)
    budget = _forcing_budget(spec, x.shape)
    want = np.asarray(ops.stencil_run(x, spec, 4, bx=128, bt=2,
                                      backend="interpret",
                                      aux={"power": p}))
    got = ops.stencil_run(x, spec, 4, bx=128, bt=2, backend="interpret",
                          aux={"power": p}, hbm_budget=budget)
    assert isinstance(got, np.ndarray)
    np.testing.assert_array_equal(got, want)


def test_outofcore_source_operand_hotspot3d():
    from repro.apps import hotspot3d
    spec = hotspot3d.spec_of(hotspot3d.Hotspot3DParams())
    x, p = _rand((48, 8, 128), 1), _rand((48, 8, 128), 2)
    budget = _forcing_budget(spec, x.shape)
    want = np.asarray(ops.stencil_run(x, spec, 4, bx=128, bt=2,
                                      backend="interpret",
                                      aux={"power": p}))
    got = ops.stencil_run(x, spec, 4, bx=128, bt=2, backend="interpret",
                          aux={"power": p}, hbm_budget=budget)
    np.testing.assert_array_equal(got, want)


def _varcoef_spec():
    def upd(fields, spec):
        c, q, x = fields["k"], fields["scalars"][0], fields["x"]
        return x + q * 0.1 * (c * shift(x, 0, 1, spec.boundary) - c * x)

    return StencilSpec(dims=2, radius=1, boundary="clamp", update=upd,
                       aux=(AuxOperand("k", role="coeff"),), n_scalars=1,
                       name="ooc_varcoef")


def test_outofcore_coeff_and_scalars():
    spec = _varcoef_spec()
    x, k = _rand((96, 140), 1), _rand((96, 140), 2)
    scal = np.linspace(0.5, 1.5, 6).reshape(6, 1).astype(np.float32)
    budget = _forcing_budget(spec, x.shape)
    want = np.asarray(ops.stencil_run(x, spec, 6, bx=128, bt=3,
                                      backend="interpret", aux={"k": k},
                                      scalars=scal))
    got = ops.stencil_run(x, spec, 6, bx=128, bt=3, backend="interpret",
                          aux={"k": k}, scalars=scal, hbm_budget=budget)
    np.testing.assert_array_equal(got, want)


def test_outofcore_batched_with_per_problem_scalars():
    """[B, *grid] batches tile the grid's leading axis with the whole
    batch riding on every slab; per-problem scalars slice per sweep."""
    spec = _varcoef_spec()
    B = 3
    x, k = _rand((B, 60, 140), 1), _rand((B, 60, 140), 2)
    rng = np.random.default_rng(3)
    scal = rng.standard_normal((B, 6, 1)).astype(np.float32)
    budget = _forcing_budget(spec, (60, 140), batch=B)
    want = np.asarray(ops.stencil_run(x, spec, 6, bx=128, bt=2,
                                      backend="interpret", aux={"k": k},
                                      scalars=scal))
    got = ops.stencil_run(x, spec, 6, bx=128, bt=2, backend="interpret",
                          aux={"k": k}, scalars=scal, hbm_budget=budget)
    assert isinstance(got, np.ndarray)
    np.testing.assert_array_equal(got, want)


def test_outofcore_batched_3d_legacy_source():
    spec = diffusion(3, 1, boundary="clamp")
    x, s = _rand((2, 48, 8, 128), 1), _rand((2, 48, 8, 128), 2)
    budget = _forcing_budget(spec, (48, 8, 128), batch=2)
    want = np.asarray(ops.stencil_run(x, spec, 3, bx=128, bt=2,
                                      backend="interpret", source=s))
    got = ops.stencil_run(x, spec, 3, bx=128, bt=2, backend="interpret",
                          source=s, hbm_budget=budget)
    np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# Runner hygiene
# ---------------------------------------------------------------------------

def test_runner_does_not_mutate_host_input():
    spec = diffusion(2, 1)
    x = np.asarray(_rand((40, 140)))
    before = x.copy()
    stencil_run_outofcore(x, spec, 4, bx=128, bt=1, backend="interpret",
                          tile=10)      # 4 sweeps: both buffers written
    np.testing.assert_array_equal(x, before)


def test_runner_validates_like_the_engine():
    spec = _varcoef_spec()
    x = _rand((40, 140))
    with pytest.raises(ValueError, match="requires aux"):
        stencil_run_outofcore(x, spec, 2, bx=128, bt=1, backend="interpret",
                              tile=8)
    with pytest.raises(ValueError, match="unknown aux"):
        stencil_run_outofcore(x, diffusion(2, 1), 2, bx=128, bt=1,
                              backend="interpret", tile=8,
                              aux={"nope": x})
    with pytest.raises(ValueError, match="tile must be in"):
        stencil_run_outofcore(x, diffusion(2, 1), 2, bx=128, bt=1,
                              backend="interpret", tile=41)
    with pytest.raises(ValueError, match="tile= or hbm_budget="):
        stencil_run_outofcore(x, diffusion(2, 1), 2, bx=128, bt=1,
                              backend="interpret")


def test_outofcore_with_sharding_composes(monkeypatch):
    """Combined out-of-core + n_devices COMPOSES: when even a
    per-device shard overflows the budget, ops.stencil_run routes
    through the composed streaming runner (per-device slabs,
    tile-granular halo exchange) instead of raising. Single visible
    device here — the routing decision and the handoff are what is
    pinned (the forced-4-device bitwise matrix lives in
    tests/test_outofcore_sharded.py)."""
    import repro.outofcore as ooc
    from repro.kernels import autotune
    from repro.outofcore import runner
    spec = diffusion(2, 1)
    x = _rand((64, 140))
    ws = incore_resident_bytes(spec, x.shape)
    budget = ws // 8            # < ws/4: overflows even a 4-way shard
    seen = {}
    real = runner.stencil_run_outofcore

    def spy(xx, sp, n_steps, **kw):
        seen.update(n_steps=n_steps, **kw)
        kw["n_devices"] = 1     # run solo: only 1 device visible here
        return real(xx, sp, n_steps, **kw)

    # ops imports the runner lazily from the package at call time.
    monkeypatch.setattr(ooc, "stencil_run_outofcore", spy)
    want = np.asarray(ops.stencil_run(x, spec, 2, bx=128, bt=1,
                                      backend="interpret"))
    got = ops.stencil_run(x, spec, 2, bx=128, bt=1,
                          backend="interpret", n_devices=4,
                          hbm_budget=budget)
    assert seen["n_devices"] == 4       # composed path was asked for
    assert seen["hbm_budget"] == budget
    np.testing.assert_array_equal(np.asarray(got), want)
    # The tuner plans (instead of raising) for the same combination —
    # otherwise every measured candidate would die inside _measure's
    # blanket except and hand back an unusable "winner".
    tuned = autotune.plan(x.shape, spec, backend="interpret",
                          n_devices=4, hbm_budget=budget,
                          use_cache=False)
    assert tuned.bx >= 128 and tuned.bt >= 1


def test_route_decision_charges_ghost_bytes_per_shard():
    """Satellite bugfix: the per-shard residency must include the
    r*bt-deep ghost slices a slab actually holds. A budget between the
    ghost-free and ghost-charged per-device bytes used to stay in-core
    (understating true residency by up to 2*r*bt/S) — it must route
    out-of-core now."""
    from repro.core.blocking import shard_resident_bytes
    from repro.outofcore import route_decision
    spec = diffusion(2, 1)
    grid = (64, 140)
    ws = incore_resident_bytes(spec, grid)
    per_slice = ws // 64
    # n_devices=4: S=16 owned slices; ghost-charged slab is S + 2*r*bt.
    for bt, g in ((1, 1), (2, 2), (4, 4)):
        free_b = per_slice * 16                    # ghost-free shard
        charged = shard_resident_bytes(spec, grid, 4, n_devices=4,
                                       bt=bt)
        assert charged == per_slice * (16 + 2 * g)
        boundary = (free_b + charged) // 2         # strictly between
        routed_lo, _ = route_decision(spec, grid, 4, boundary,
                                      n_devices=4, bt=bt)
        assert routed_lo, (bt, boundary)           # the fixed predicate
        routed_hi, _ = route_decision(spec, grid, 4, charged,
                                      n_devices=4, bt=bt)
        assert not routed_hi                       # exact fit stays in-core


def test_sharded_run_keeps_incore_path_when_shards_fit(monkeypatch):
    """The routing predicate is per-DEVICE: a grid that overflows one
    device but fits its n_devices shards must keep the in-core
    deep-halo path (the PR-2 capability), not raise."""
    from repro.distributed import halo
    spec = diffusion(2, 1)
    x = _rand((64, 140))
    ws = incore_resident_bytes(spec, x.shape)
    seen = {}

    def spy(xx, sp, n_steps, **kw):
        seen.update(n_steps=n_steps, **kw)
        return xx

    monkeypatch.setattr(halo, "stencil_run_sharded", spy)
    # budget between ws/4 and ws: one device overflows, four don't
    ops.stencil_run(x, spec, 2, bx=128, bt=1, backend="interpret",
                    n_devices=4, hbm_budget=ws // 2)
    assert seen["n_devices"] == 4       # sharded in-core path taken


def test_reference_backend_ignores_budget():
    """The oracle already runs on the host; a budget must not reroute
    (or break) it."""
    from repro.kernels import ref
    spec = diffusion(2, 1)
    x = _rand((64, 140))
    got = ops.stencil_run(x, spec, 3, bx=128, bt=1,
                          backend="reference",
                          hbm_budget=_forcing_budget(spec, x.shape))
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(ref.stencil_multistep(x, spec, 3)))


# ---------------------------------------------------------------------------
# TilePlan / plan_tiles (core/blocking.py)
# ---------------------------------------------------------------------------

def test_tileplan_geometry_and_budget_fit():
    spec = diffusion(2, 2)
    grid = (1000, 512)
    tp = TilePlan(spec, grid, bx=128, bt=4, tile=100)
    assert tp.ghost == 8 and tp.n_tiles == 10
    assert tp.slab_extent == 116
    assert tp.transfer_amplification == pytest.approx(1.16)
    # host traffic: every slab up once + owned slices down once
    up = 10 * 116 * 512 * 4
    assert tp.host_bytes_per_sweep() == up + 1000 * 512 * 4
    # ghost deeper than tile is legal here (unlike the halo runner)
    assert TilePlan(spec, grid, bx=128, bt=4, tile=1).ghost == 8


def test_plan_tiles_none_when_in_core_fits():
    spec = diffusion(2, 1)
    assert plan_tiles(spec, (64, 128), bx=128, bt=2,
                      hbm_budget=1 << 30) is None


def test_plan_tiles_picks_largest_fitting_tile():
    spec = diffusion(2, 1)
    grid = (1000, 512)
    budget = _forcing_budget(spec, grid, frac=0.5)
    tp = plan_tiles(spec, grid, bx=128, bt=2, hbm_budget=budget)
    assert tp is not None
    assert tp.device_bytes(2) <= budget
    if tp.tile < grid[0]:
        bigger = TilePlan(spec, grid, bx=128, bt=2, tile=tp.tile + 1)
        assert bigger.device_bytes(2) > budget


def test_plan_tiles_raises_when_nothing_fits():
    spec = diffusion(2, 4)
    with pytest.raises(ValueError, match="hbm_budget"):
        plan_tiles(spec, (64, 512), bx=128, bt=4, hbm_budget=10_000)


def test_incore_resident_bytes_counts_every_operand():
    """Residency counts each *declared* operand as its own array (the
    engine's pre-summing of sources saves VMEM streams, not HBM
    residency) plus any caller-side legacy ``source=`` grid."""
    from repro.apps import hotspot
    grid_b = 64 * 128 * 4
    plain = incore_resident_bytes(diffusion(2, 1), (64, 128))
    with_aux = incore_resident_bytes(
        hotspot.spec_of(hotspot.HotspotParams()), (64, 128))
    assert plain == grid_b * 2
    assert with_aux == grid_b * 3             # + the power operand
    two_src = StencilSpec(
        dims=2, radius=1, center=1.0, axis_weights=((0.0,) * 3,) * 2,
        aux=(AuxOperand("a"), AuxOperand("b")), name="two_src_res")
    # BlockPlan.n_aux collapses these into ONE stream; residency must
    # still count both arrays.
    assert incore_resident_bytes(two_src, (64, 128)) == grid_b * 4
    assert incore_resident_bytes(diffusion(2, 1), (64, 128),
                                 extra_streams=1) == grid_b * 3
    assert incore_resident_bytes(diffusion(2, 1), (64, 128),
                                 batch=4) == 4 * plain
    assert exceeds_budget(diffusion(2, 1), (64, 128), 4, plain - 1)
    assert not exceeds_budget(diffusion(2, 1), (64, 128), 4, plain)


def test_legacy_source_counts_toward_routing():
    """A legacy ``source=`` grid is a third resident array: a budget
    between 2 and 3 grid-sizes must route the sourced run out-of-core
    (staying in-core would OOM on real hardware) while the unsourced
    run stays in-core."""
    spec = diffusion(2, 1)
    x, s = _rand((64, 140), 1), _rand((64, 140), 2)
    grid_b = 64 * 140 * 4
    budget = int(grid_b * 2.5)
    plain = ops.stencil_run(x, spec, 3, bx=128, bt=1,
                            backend="interpret", hbm_budget=budget)
    assert not isinstance(plain, np.ndarray)        # in-core: 2 grids
    sourced = ops.stencil_run(x, spec, 3, bx=128, bt=1,
                              backend="interpret", source=s,
                              hbm_budget=budget)
    assert isinstance(sourced, np.ndarray)          # routed: 3 grids
    want = np.asarray(ops.stencil_run(x, spec, 3, bx=128, bt=1,
                                      backend="interpret", source=s))
    np.testing.assert_array_equal(sourced, want)


# ---------------------------------------------------------------------------
# perf_model budget logic: the HBM guard, the host-transfer term, the
# exposed-transfer fraction.
# ---------------------------------------------------------------------------

def test_select_config_never_exceeds_device_hbm():
    """No (bx, bt) can shrink an in-core working set, so an over-HBM
    grid must raise (naming the out-of-core remedy) rather than return
    any plan — and a fitting grid's plans are all within budget."""
    spec = diffusion(2, 1)
    small_dev = pm.TpuSpec(name="tiny", hbm_bytes=1 << 20)
    with pytest.raises(ValueError, match="out-of-core"):
        pm.select_config(spec, (1024, 1024), 8, tpu=small_dev)
    with pytest.raises(ValueError, match="out-of-core"):
        pm.select_config(spec, (1024, 1024), 8, hbm_budget=1 << 20)
    # The exact guard boundary: one byte under the working set raises,
    # the working set itself is the largest budget that returns plans
    # (the set is plan-independent, so this IS the 'never exceeds'
    # guarantee — there exists no plan that could shrink it).
    ws = incore_resident_bytes(spec, (1024, 1024))
    with pytest.raises(ValueError, match="out-of-core"):
        pm.select_config(spec, (1024, 1024), 8, hbm_budget=ws - 1)
    assert pm.select_config(spec, (1024, 1024), 8, hbm_budget=ws)
    assert pm.select_config(spec, (1024, 1024), 8)    # v5e: fits


def test_outofcore_roofline_host_term():
    spec = diffusion(2, 1)
    grid = (4096, 4096)
    tp = TilePlan(spec, grid, bx=512, bt=2, tile=256)
    terms = pm.outofcore_roofline(tp, 16)
    assert terms.t_host > 0
    assert terms.host_bytes == pytest.approx(
        tp.host_bytes_per_sweep() * tp.sweeps(16))
    assert terms.t_outofcore >= terms.t_predicted
    assert 0.0 <= terms.exposed_transfer_fraction <= 1.0
    # host_bw is far below hbm_bw, so streaming dominates here
    assert terms.exposed_transfer_fraction > 0.5
    # ghost recompute: every slab computes its full tile+2g extent, so
    # the device terms carry the (tile+2g)/tile slab factor (the halo
    # model's analog) — without it deep-bt candidates rank too well
    base = pm.stencil_roofline(BlockPlan(spec, grid, bx=512, bt=2), 16)
    amp = tp.transfer_amplification
    assert terms.flops == pytest.approx(base.flops * amp)
    assert terms.t_compute == pytest.approx(base.t_compute * amp)
    assert terms.t_memory == pytest.approx(base.t_memory * amp)
    # in-core terms carry no host time at all
    assert base.t_host == 0.0
    assert base.exposed_transfer_fraction == 0.0


def test_outofcore_roofline_prefers_bigger_tiles_and_deeper_bt():
    """The two planner knobs: tile amortizes ghost re-upload, bt cuts
    host passes. Both must move the modeled streaming time the right
    way."""
    spec = diffusion(2, 1)
    grid = (8192, 4096)
    small = TilePlan(spec, grid, bx=512, bt=2, tile=32)
    large = TilePlan(spec, grid, bx=512, bt=2, tile=1024)
    assert (pm.outofcore_roofline(large, 16).t_host
            < pm.outofcore_roofline(small, 16).t_host)
    shallow = TilePlan(spec, grid, bx=512, bt=1, tile=256)
    deep = TilePlan(spec, grid, bx=512, bt=4, tile=256)
    assert (pm.outofcore_roofline(deep, 16).t_host
            < pm.outofcore_roofline(shallow, 16).t_host)


# ---------------------------------------------------------------------------
# Budget-aware autotuning (kernels/autotune.py, cache v5)
# ---------------------------------------------------------------------------

def test_autotune_budget_aware_plan_carries_tile():
    from repro.kernels import autotune
    spec = diffusion(2, 1)
    grid = (140, 140)
    budget = _forcing_budget(spec, grid)
    tuned = autotune.plan(grid, spec, backend="interpret", n_steps=8,
                          hbm_budget=budget)
    assert tuned.tile is not None
    tp = TilePlan(spec, grid, bx=tuned.bx, bt=tuned.bt, tile=tuned.tile)
    assert tp.device_bytes(2) <= budget
    # without a budget the same problem resolves in-core (no tile)
    assert autotune.plan(grid, spec, backend="interpret",
                         n_steps=8).tile is None


def test_autotune_cache_key_distinguishes_budgets():
    from repro.kernels import autotune
    spec = diffusion(2, 1)
    ks = {autotune._key(spec, (64, 128), "float32", "interpret",
                        pm.V5E.vmem_bytes, "v5e", hbm_budget=hb)
          for hb in (None, 1 << 20, 1 << 24)}
    assert len(ks) == 3
    # a legacy source= grid streams like a declared source operand and
    # must split cache entries (it changes sizing and routing)
    k_src = autotune._key(spec, (64, 128), "float32", "interpret",
                          pm.V5E.vmem_bytes, "v5e", extra_streams=1)
    assert "|axs|" in k_src and k_src not in ks


def test_cache_version_mismatch_logs_found_vs_expected(tmp_path,
                                                       monkeypatch,
                                                       caplog):
    """A stale cache drop must say which version was found and which
    was expected, so docs/autotuning.md's --retune guidance matches
    observed behavior."""
    from repro.kernels import autotune
    path = tmp_path / "stale.json"
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(path))
    autotune._MEM.clear()
    path.write_text(json.dumps(
        {"version": 4,
         "some|v4|key": {"bx": 256, "bt": 4, "variant": "revolving",
                         "source": "measured"}}))
    with caplog.at_level(logging.WARNING, logger="repro.autotune"):
        assert autotune._load_cache() == {}
    assert "version 4" in caplog.text
    assert f"version {autotune._CACHE_VERSION}" in caplog.text
    assert "--retune" in caplog.text
    # a missing/empty cache is normal operation: no noise
    caplog.clear()
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE",
                       str(tmp_path / "absent.json"))
    autotune._MEM.clear()
    with caplog.at_level(logging.WARNING, logger="repro.autotune"):
        assert autotune._load_cache() == {}
    assert not caplog.text


# ---------------------------------------------------------------------------
# Serving: oversized requests succeed via the out-of-core route
# ---------------------------------------------------------------------------

def test_service_serves_oversized_requests_outofcore():
    """An oversized bucket routes out-of-core instead of failing, and
    check=True (bitwise vs the in-core solo run) passes unchanged —
    clients cannot tell the difference."""
    from repro.serving import StencilRequest, StencilService
    from repro.kernels import ref
    spec = diffusion(2, 1, boundary="clamp")
    reqs = [StencilRequest(uid=i, x=_rand((48, 140), seed=i), spec=spec,
                           n_steps=3) for i in range(5)]
    budget = _forcing_budget(spec, (48, 140), batch=4)
    svc = StencilService(max_batch=4, backend="interpret", bx=128, bt=2,
                         check=True, hbm_budget=budget)
    done = svc.run(list(reqs))
    assert sorted(c.uid for c in done) == list(range(5))
    # the full bucket exceeded the budget; the single-request one fit
    assert svc.metrics["outofcore_dispatches"] == 1
    assert svc.metrics["dispatches"] == 2
    for r in reqs:
        got = next(c for c in done if c.uid == r.uid).result
        want = ref.stencil_multistep(r.x, r.spec, r.n_steps)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=5e-5, atol=5e-5)
