"""Deep-halo multi-device stencil parity (distributed/halo.py).

The sharded runner must be numerically identical (fp32 tolerance) to
the single-device oracle ``kernels/ref.py`` for radius 1-4, 2D and 3D,
``bt`` in {1, 2, 4}, and odd shard-unaligned grid sizes — on 2 and 4
devices. Multi-device runs happen in subprocesses with
``--xla_force_host_platform_device_count`` (same pattern as
tests/test_distributed.py) so the main test process keeps the host's
real device view; tuner-level device awareness is tested in-process.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import jax.numpy as jnp
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

TOL = "rtol=5e-5, atol=5e-5"


def _run(script: str, devices: int) -> str:
    env = dict(os.environ,
               PYTHONPATH=SRC,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                         env=env, capture_output=True, text=True,
                         timeout=900)
    assert out.returncode == 0, f"stdout:{out.stdout}\nstderr:{out.stderr}"
    return out.stdout


@pytest.mark.parametrize("devices", [2, 4])
def test_halo_parity_2d_radius_bt_sweep(devices):
    """Radius 1-4 x bt {1,2,4} on a shard-unaligned 2D grid (67 rows),
    with a remainder sweep (n_steps=5) — bit-accurate vs the oracle."""
    _run(f"""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core.stencil import diffusion
        from repro.kernels import ops, ref
        assert len(jax.devices()) == {devices}
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal((67, 261)), jnp.float32)
        for radius in (1, 2, 3, 4):
            spec = diffusion(2, radius)
            want = ref.stencil_multistep(x, spec, 5)
            for bt in (1, 2, 4):
                got = ops.stencil_run(x, spec, 5, bx=128, bt=bt,
                                      backend="interpret",
                                      n_devices={devices})
                np.testing.assert_allclose(
                    np.asarray(got), np.asarray(want), {TOL},
                    err_msg=f"r={{radius}} bt={{bt}}")
        print("OK")
    """, devices=devices)


def test_halo_parity_3d():
    """Radius 1-4 on a shard-unaligned 3D grid (23 planes over 4
    devices -> 6-plane shards), deep halos where they fit the shard."""
    _run("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core.stencil import diffusion
        from repro.kernels import ops, ref
        assert len(jax.devices()) == 4
        rng = np.random.default_rng(1)
        x = jnp.asarray(rng.standard_normal((23, 9, 133)), jnp.float32)
        cases = {1: (1, 2, 4), 2: (1, 2), 3: (1, 2), 4: (1,)}
        for radius, bts in cases.items():
            spec = diffusion(3, radius)
            want = ref.stencil_multistep(x, spec, 3)
            for bt in bts:
                got = ops.stencil_run(x, spec, 3, bx=128, bt=bt,
                                      backend="interpret", n_devices=4)
                np.testing.assert_allclose(
                    np.asarray(got), np.asarray(want), """ + TOL + """,
                    err_msg=f"r={radius} bt={bt}")
        print("OK")
    """, devices=4)


def test_halo_source_term_and_overlap_schedules():
    """The per-step additive source (Hotspot power) shards with the
    grid, and the overlapped interior/edge schedule equals the plain
    exchange-then-compute schedule."""
    _run("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core.stencil import hotspot2d, diffusion
        from repro.kernels import ref
        from repro.distributed import halo
        rng = np.random.default_rng(2)
        x = jnp.asarray(rng.standard_normal((45, 197)), jnp.float32)
        src = jnp.asarray(rng.standard_normal((45, 197)), jnp.float32) * .1
        spec = hotspot2d()
        want = ref.stencil_multistep(x, spec, 4, src)
        outs = {}
        for ov in (True, False):
            got = halo.stencil_run_sharded(x, spec, 4, n_devices=4,
                                           bx=128, bt=2, source=src,
                                           overlap=ov, backend="interpret")
            np.testing.assert_allclose(
                np.asarray(got), np.asarray(want), """ + TOL + """)
            outs[ov] = np.asarray(got)
        np.testing.assert_array_equal(outs[True], outs[False])
        # 3D with source, unaligned over 4
        x3 = jnp.asarray(rng.standard_normal((13, 9, 133)), jnp.float32)
        s3 = jnp.asarray(rng.standard_normal((13, 9, 133)), jnp.float32) * .1
        spec3 = diffusion(3, 1)
        want3 = ref.stencil_multistep(x3, spec3, 4, s3)
        got3 = halo.stencil_run_sharded(x3, spec3, 4, n_devices=4,
                                        bx=128, bt=2, source=s3, backend="interpret")
        np.testing.assert_allclose(
            np.asarray(got3), np.asarray(want3), """ + TOL + """)
        print("OK")
    """, devices=4)


def test_halo_overlap_parity_3d_and_program():
    """Fused halo packing: the overlapped interior/edge schedule stays
    bitwise-equal to the plain exchange-then-compute schedule for 3D
    multi-sweep runs (remainder sweep included) and for a multi-field
    StencilProgram, on 4 forced devices."""
    _run("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core.stencil import StencilProgram, Sweep, diffusion
        from repro.distributed import halo
        assert len(jax.devices()) == 4
        rng = np.random.default_rng(3)
        # 3D, n_steps=5 with bt=2 -> schedule [2, 2, 1] (packed strips
        # shrink at the remainder sweep).
        x3 = jnp.asarray(rng.standard_normal((40, 9, 133)), jnp.float32)
        for radius in (1, 2):
            spec = diffusion(3, radius)
            outs = {ov: np.asarray(halo.stencil_run_sharded(
                        x3, spec, 5, n_devices=4, bx=128, bt=2,
                        overlap=ov, backend="interpret")) for ov in (True, False)}
            np.testing.assert_array_equal(
                outs[True], outs[False], err_msg=f"3d r={radius}")
        # Multi-field program: groups alternate, per-dispatch exchange.
        x = jnp.asarray(rng.standard_normal((48, 140)), jnp.float32)
        p = StencilProgram((Sweep("a", diffusion(2, 1), field="u"),
                            Sweep("b", diffusion(2, 2), field="u")),
                           name="p")
        outs = {ov: np.asarray(halo.stencil_program_run_sharded(
                    {"u": x}, p, 3, n_devices=4, bx=128,
                    overlap=ov, backend="interpret")["u"]) for ov in (True, False)}
        np.testing.assert_array_equal(outs[True], outs[False])
        print("OK")
    """, devices=4)


def test_halo_extreme_shard_sizes():
    """Shards as small as the halo itself (S == h and S == 2h), and a
    last shard that is pure padding (H < (n-1)*S is impossible, but
    H barely over (n-1)*S leaves a nearly-empty shard)."""
    _run("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core.stencil import diffusion
        from repro.kernels import ref
        from repro.distributed import halo
        rng = np.random.default_rng(3)
        spec = diffusion(2, 2)
        # 13 rows over 4 devices: S=4, h=r*bt=4 -> S == h (overlap falls
        # back internally); last shard holds rows 12..15 = 1 real row.
        x = jnp.asarray(rng.standard_normal((13, 140)), jnp.float32)
        want = ref.stencil_multistep(x, spec, 4)
        got = halo.stencil_run_sharded(x, spec, 4, n_devices=4,
                                       bx=128, bt=2, backend="interpret")
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), """ + TOL + """)
        # S == 2h exactly (16 rows over 2 devices, h=4): the overlapped
        # schedule has no interior strip at all.
        x2 = jnp.asarray(rng.standard_normal((16, 140)), jnp.float32)
        want2 = ref.stencil_multistep(x2, spec, 2)
        got2 = halo.stencil_run_sharded(x2, spec, 2, n_devices=2,
                                        bx=128, bt=2, overlap=True, backend="interpret")
        np.testing.assert_allclose(
            np.asarray(got2), np.asarray(want2), """ + TOL + """)
        print("OK")
    """, devices=4)


# ---------------------------------------------------------------------------
# Batched grids through the sharded runner (forced 4 devices).
# ---------------------------------------------------------------------------

def test_halo_batched_grid_sharding_parity():
    """B in {1, 3} (never divisible by 4 -> grid sharding) on a
    shard-unaligned grid, bt in {1, 4}: equal to the batched oracle
    AND bitwise-equal to a Python loop of single-problem sharded
    runs."""
    _run("""
        import numpy as np, jax, jax.numpy as jnp
        assert len(jax.devices()) == 4
        from repro.core.stencil import diffusion
        from repro.kernels import ref
        from repro.distributed import halo
        rng = np.random.default_rng(21)
        spec = diffusion(2, 2, boundary="clamp")
        for B in (1, 3):
            x = jnp.asarray(rng.standard_normal((B, 45, 141)),
                            jnp.float32)
            assert halo.shard_strategy(x.shape, spec, 4) == "grid"
            want = ref.stencil_multistep(x, spec, 5)
            for bt in (1, 4):
                got = halo.stencil_run_sharded(x, spec, 5, n_devices=4,
                                               bx=128, bt=bt, backend="interpret")
                np.testing.assert_allclose(
                    np.asarray(got), np.asarray(want), """ + TOL + """,
                    err_msg=f"B={B} bt={bt}")
                solo = jnp.stack([halo.stencil_run_sharded(
                    x[b], spec, 5, n_devices=4, bx=128, bt=bt, backend="interpret")
                    for b in range(B)])
                np.testing.assert_array_equal(
                    np.asarray(got), np.asarray(solo),
                    err_msg=f"solo-loop B={B} bt={bt}")
        print("OK")
    """, devices=4)


def test_halo_batch_axis_sharding_parity_and_scalars():
    """B % n == 0 takes the batch-sharding path: parity vs the oracle
    and vs the B=1-at-a-time grid-sharded runs, 2D with per-problem
    scalars and 3D with a source operand."""
    _run("""
        import numpy as np, jax, jax.numpy as jnp
        assert len(jax.devices()) == 4
        from repro.core.stencil import (AuxOperand, StencilSpec,
                                        diffusion, shift)
        from repro.kernels import ops, ref
        from repro.distributed import halo
        rng = np.random.default_rng(22)
        spec = diffusion(2, 1, boundary="clamp")
        x = jnp.asarray(rng.standard_normal((8, 21, 140)), jnp.float32)
        assert halo.shard_strategy(x.shape, spec, 4) == "batch"
        got = halo.stencil_run_sharded(x, spec, 5, n_devices=4,
                                       bx=128, bt=2, backend="interpret")
        want = ref.stencil_multistep(x, spec, 5)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   """ + TOL + """)
        # per-problem scalars shard with their problems
        def upd(fields, spec):
            j, c, s = fields["x"], fields["c"], fields["scalars"]
            lap = (shift(j, 0, -1, "clamp") + shift(j, 0, 1, "clamp")
                   + shift(j, 1, -1, "clamp") + shift(j, 1, 1, "clamp")
                   - 4.0 * j)
            return j + s[0] * c * lap
        vspec = StencilSpec(dims=2, radius=1, boundary="clamp",
                            update=upd, n_scalars=1,
                            aux=(AuxOperand("c", role="coeff"),),
                            name="varcoef_b")
        c = jnp.asarray(rng.uniform(0.05, 0.2, x.shape), jnp.float32)
        scal = jnp.asarray(rng.uniform(0.05, 0.3, (8, 5, 1)),
                           jnp.float32)
        got = ops.stencil_run(x, vspec, 5, bx=128, bt=2,
                              backend="interpret", n_devices=4,
                              aux={"c": c}, scalars=scal)
        want = ref.stencil_multistep(x, vspec, 5, aux={"c": c},
                                     scalars=scal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   """ + TOL + """)
        # 3D batch sharding with a source term
        x3 = jnp.asarray(rng.standard_normal((4, 9, 8, 133)),
                         jnp.float32)
        s3 = jnp.asarray(rng.standard_normal((4, 9, 8, 133)),
                         jnp.float32) * .1
        spec3 = diffusion(3, 1)
        assert halo.shard_strategy(x3.shape, spec3, 4) == "batch"
        got3 = halo.stencil_run_sharded(x3, spec3, 4, n_devices=4,
                                        bx=128, bt=2, source=s3, backend="interpret")
        want3 = ref.stencil_multistep(x3, spec3, 4, s3)
        np.testing.assert_allclose(np.asarray(got3), np.asarray(want3),
                                   """ + TOL + """)
        print("OK")
    """, devices=4)


def test_halo_batched_acceptance_B125():
    """Acceptance: on 4 forced devices, batched == Python loop of
    single-problem runs (bitwise) for B in {1, 2, 5}, both boundary
    modes, 2D r in {1, 4} and 3D r1."""
    _run("""
        import numpy as np, jax, jax.numpy as jnp
        assert len(jax.devices()) == 4
        from repro.core.stencil import diffusion
        from repro.kernels import ops
        rng = np.random.default_rng(23)
        for boundary in ("dirichlet0", "clamp"):
            for radius in (1, 4):
                spec = diffusion(2, radius, boundary=boundary)
                for B in (1, 2, 5):
                    x = jnp.asarray(
                        rng.standard_normal((B, 45, 140)), jnp.float32)
                    got = ops.stencil_run(x, spec, 3, bx=128, bt=2,
                                          backend="interpret",
                                          n_devices=4)
                    solo = jnp.stack([ops.stencil_run(
                        x[b], spec, 3, bx=128, bt=2,
                        backend="interpret", n_devices=4)
                        for b in range(B)])
                    np.testing.assert_array_equal(
                        np.asarray(got), np.asarray(solo),
                        err_msg=f"{boundary} r={radius} B={B}")
            spec3 = diffusion(3, 1, boundary=boundary)
            x3 = jnp.asarray(rng.standard_normal((2, 13, 8, 133)),
                             jnp.float32)
            got3 = ops.stencil_run(x3, spec3, 3, bx=128, bt=2,
                                   backend="interpret", n_devices=4)
            solo3 = jnp.stack([ops.stencil_run(
                x3[b], spec3, 3, bx=128, bt=2, backend="interpret",
                n_devices=4) for b in range(2)])
            np.testing.assert_array_equal(np.asarray(got3),
                                          np.asarray(solo3),
                                          err_msg=boundary)
        print("OK")
    """, devices=4)


def test_shard_strategy_prefers_batch_axis():
    """The documented preference: a device-divisible batch always
    takes batch-axis sharding; everything else grid-shards."""
    from repro.core.stencil import diffusion
    from repro.distributed import halo
    spec = diffusion(2, 1)
    assert halo.shard_strategy((4, 32, 140), spec, 4) == "batch"
    assert halo.shard_strategy((8, 32, 140), spec, 4) == "batch"
    assert halo.shard_strategy((3, 32, 140), spec, 4) == "grid"
    assert halo.shard_strategy((1, 32, 140), spec, 4) == "grid"
    assert halo.shard_strategy((32, 140), spec, 4) == "grid"
    assert halo.shard_strategy((4, 32, 140), spec, 1) == "grid"
    spec3 = diffusion(3, 1)
    assert halo.shard_strategy((4, 8, 9, 140), spec3, 2) == "batch"
    assert halo.shard_strategy((9, 8, 140), spec3, 2) == "grid"


# ---------------------------------------------------------------------------
# In-process: single-device generic path + tuner device awareness
# ---------------------------------------------------------------------------

@pytest.fixture(autouse=True)
def _isolated_cache(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE",
                       str(tmp_path / "autotune.json"))


def test_sharded_generic_path_on_one_device():
    """n_devices=1 exercises the full slab/ghost/validity machinery on
    the host's real device — the edge-device logic with no neighbors."""
    from repro.core.stencil import diffusion
    from repro.kernels import ref
    from repro.distributed import halo
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.standard_normal((21, 261)), jnp.float32)
    spec = diffusion(2, 3)
    want = ref.stencil_multistep(x, spec, 4)
    for ov in (True, False):
        got = halo.stencil_run_sharded(x, spec, 4, n_devices=1, bx=128,
                                       bt=2, overlap=ov, backend="interpret")
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=5e-5, atol=5e-5)


def test_sharded_rejects_missing_devices():
    from repro.core.stencil import diffusion
    from repro.distributed import halo
    x = jnp.zeros((16, 128), jnp.float32)
    with pytest.raises(ValueError, match="devices"):
        halo.stencil_run_sharded(x, diffusion(2, 1), 1, n_devices=4096, backend="interpret")


def test_sharded_rejects_radius_deeper_than_shard():
    """A shard must be able to hold even a bt=1 halo; silently clamping
    would mis-assemble the slabs (wrong results, not an error)."""
    from repro.core.stencil import diffusion
    from repro.kernels import ops
    # 12 rows over 4 devices -> 3-row shards < radius 4
    x = jnp.zeros((12, 256), jnp.float32)
    with pytest.raises(ValueError, match="radius"):
        ops.stencil_run(x, diffusion(2, 4), 2, bx=256, bt=1,
                        backend="interpret", n_devices=4)


def test_sharded_runner_is_memoized():
    """Identical static configurations must reuse one jitted program —
    the autotuner's timing repeats depend on hitting the jit cache."""
    from repro.core.stencil import diffusion
    from repro.distributed import halo
    rng = np.random.default_rng(5)
    spec = diffusion(2, 1)
    before = len(halo._RUNNERS)
    for _ in range(3):
        x = jnp.asarray(rng.standard_normal((20, 140)), jnp.float32)
        halo.stencil_run_sharded(x, spec, 2, n_devices=1, bx=128, bt=2, backend="interpret")
    assert len(halo._RUNNERS) == before + 1


def test_autotune_device_aware_halo_fits_shard():
    """With the grid sharded 8 ways the tuner may not pick a bt whose
    halo exceeds one shard (r=4, S=8 -> bt <= 2)."""
    from repro.core.stencil import diffusion
    from repro.kernels import autotune
    tuned = autotune.plan((64, 512), diffusion(2, 4),
                          backend="interpret", n_devices=8)
    assert tuned.bt * 4 <= 8


def test_autotune_cache_key_includes_device_count():
    from repro.core.stencil import diffusion
    from repro.kernels import autotune
    from repro.core.perf_model import V5E
    spec = diffusion(2, 1)
    vm = V5E.vmem_bytes
    k1 = autotune._key(spec, (16, 256), "float32", "reference", vm, "v5e")
    k2 = autotune._key(spec, (16, 256), "float32", "reference", vm, "v5e",
                       n_devices=4)
    assert k1 != k2 and "|nd1|" in k1 and "|nd4|" in k2


def test_select_config_models_exchange_tradeoff():
    """Device-aware ranking: the collective term exists only for the
    sharded case, and slab recompute scales the local terms."""
    from repro.core.perf_model import stencil_roofline, select_config
    from repro.core.blocking import BlockPlan
    from repro.core.stencil import diffusion
    spec = diffusion(2, 2)
    plan = BlockPlan(spec, (4096, 8192), bx=512, bt=4)
    single = stencil_roofline(plan, 32, chips=1)
    shard = stencil_roofline(plan, 32, chips=8, halo_exchange=True)
    assert single.collective_bytes == 0
    assert shard.collective_bytes > 0
    # per-chip work shrinks ~8x but carries the slab-recompute factor
    assert shard.flops > single.flops  # global redundant flops grew
    assert 0.0 <= shard.exposed_collective_fraction <= 1.0
    # all shortlisted sharded plans keep their halo inside one shard
    for p in select_config(spec, (64, 8192), 32, top_k=3, n_devices=8):
        assert p.halo <= 8
