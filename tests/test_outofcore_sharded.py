"""Composed out-of-core x multi-device streaming (the PR-9 tentpole).

Each device owns a contiguous slab of the leading axis and streams
that slab's tiles through the unchanged in-core engine; slabs live in
per-device **host** buffers and exchange ``r*bt``-deep ghost rows at
tile granularity via ``distributed.halo.gather_slab``. The contract is
the solo out-of-core runner's, unchanged: **bitwise equality with the
single-device in-core engine** on the same (bx, bt, variant) — every
matrix assertion below is ``assert_array_equal``, no tolerances.

Multi-device runs happen in subprocesses with
``--xla_force_host_platform_device_count`` (same pattern as
tests/test_halo.py) so the main test process keeps the host's real
device view; pure-host pieces (gather_slab, the metrics contract) run
in-process.
"""
import os
import subprocess
import sys
import textwrap
import warnings

import numpy as np
import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(script: str, devices: int = 4) -> str:
    env = dict(os.environ,
               PYTHONPATH=SRC,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                         env=env, capture_output=True, text=True,
                         timeout=900)
    assert out.returncode == 0, f"stdout:{out.stdout}\nstderr:{out.stderr}"
    return out.stdout


# ---------------------------------------------------------------------------
# Acceptance matrix (forced 4 devices): radius {1,2,4} x {2D,3D} x
# bt {1,2,4} x both boundary modes, forced-tiny budgets/tiles,
# n_steps=5 so bt 2/4 exercise the remainder sweep. Bitwise vs the
# single-device in-core engine through the public ops entry point.
# ---------------------------------------------------------------------------

def test_sharded_outofcore_parity_2d_matrix():
    """2D, shard-unaligned extent (259 rows -> S=65, last slab 64),
    budget pinned just under the ghost-charged per-device shard so
    ops.stencil_run must take the composed route. The extent is tall
    enough that even the deepest ghost (r=4, bt=4 -> 32/side) leaves a
    1-slice tile streamable under that budget."""
    _run("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core.blocking import shard_resident_bytes
        from repro.core.stencil import diffusion
        from repro.kernels import ops
        assert len(jax.devices()) == 4
        rng = np.random.default_rng(9)
        x = jnp.asarray(rng.standard_normal((259, 140)), jnp.float32)
        for boundary in ("dirichlet0", "clamp"):
            for radius in (1, 2, 4):
                spec = diffusion(2, radius, boundary=boundary)
                for bt in (1, 2, 4):
                    want = np.asarray(ops.stencil_run(
                        x, spec, 5, bx=128, bt=bt,
                        backend="interpret"))
                    budget = shard_resident_bytes(
                        spec, x.shape, 4, n_devices=4, bt=bt) - 1
                    got = ops.stencil_run(
                        x, spec, 5, bx=128, bt=bt, backend="interpret",
                        n_devices=4, hbm_budget=budget)
                    assert isinstance(got, np.ndarray)  # host result
                    np.testing.assert_array_equal(
                        got, want,
                        err_msg=f"r={radius} bt={bt} {boundary}")
        print("OK")
    """)


def test_sharded_outofcore_parity_3d_matrix():
    """3D, 39 planes over 4 devices (S=10): r=4/bt=4 makes the ghost
    (16) deeper than a whole neighbor slab, so gather_slab must walk
    PAST the adjacent owner. Explicit tiny tiles (budget-independent)
    keep every combination streamable."""
    _run("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core.stencil import diffusion
        from repro.kernels import ops
        from repro.outofcore import stencil_run_outofcore
        assert len(jax.devices()) == 4
        rng = np.random.default_rng(10)
        x = jnp.asarray(rng.standard_normal((39, 8, 128)), jnp.float32)
        for boundary in ("dirichlet0", "clamp"):
            for radius in (1, 2, 4):
                spec = diffusion(3, radius, boundary=boundary)
                for bt in (1, 2, 4):
                    want = np.asarray(ops.stencil_run(
                        x, spec, 5, bx=128, bt=bt,
                        backend="interpret"))
                    m = {}
                    got = stencil_run_outofcore(
                        x, spec, 5, bx=128, bt=bt, backend="interpret",
                        tile=3, n_devices=4, metrics=m)
                    assert m["n_devices"] == 4, m
                    assert m["slab_extents"] == [10, 10, 10, 9], m
                    assert m["halo_rows_exchanged"] > 0, m
                    np.testing.assert_array_equal(
                        got, want,
                        err_msg=f"r={radius} bt={bt} {boundary}")
        print("OK")
    """)


def test_sharded_operands_scalars_batched():
    """Source/aux/scalars/batched grids through the composed route —
    bitwise vs the solo in-core run (operands slice from full host
    arrays; the batch axis rides whole on every slab)."""
    _run("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core.stencil import (AuxOperand, StencilSpec,
                                        diffusion, shift)
        from repro.kernels import ops
        from repro.outofcore import stencil_run_outofcore
        assert len(jax.devices()) == 4
        rng = np.random.default_rng(11)

        # Aux operand (hotspot: clamp + power source term)
        from repro.apps import hotspot
        spec = hotspot.spec_of(hotspot.HotspotParams())
        x = jnp.asarray(rng.standard_normal((52, 140)), jnp.float32)
        p = jnp.asarray(rng.standard_normal((52, 140)), jnp.float32)
        want = np.asarray(ops.stencil_run(
            x, spec, 4, bx=128, bt=2, backend="interpret",
            aux={"power": p}))
        got = stencil_run_outofcore(
            x, spec, 4, bx=128, bt=2, backend="interpret", tile=5,
            n_devices=4, aux={"power": p})
        np.testing.assert_array_equal(got, want, err_msg="aux")

        # Legacy source= grid
        spec2 = diffusion(2, 1, boundary="clamp")
        s = jnp.asarray(rng.standard_normal((52, 140)), jnp.float32)
        want = np.asarray(ops.stencil_run(
            x, spec2, 4, bx=128, bt=2, backend="interpret", source=s))
        got = stencil_run_outofcore(
            x, spec2, 4, bx=128, bt=2, backend="interpret", tile=5,
            n_devices=4, source=s)
        np.testing.assert_array_equal(got, want, err_msg="source")

        # Variable coefficient + per-step scalars (n_steps, k): sweep
        # slices replicate to every device
        def upd(fields, sp):
            c, q, xx = fields["k"], fields["scalars"][0], fields["x"]
            return xx + q * 0.1 * (c * shift(xx, 0, 1, sp.boundary)
                                   - c * xx)
        spec3 = StencilSpec(dims=2, radius=1, boundary="clamp",
                            update=upd,
                            aux=(AuxOperand("k", role="coeff"),),
                            n_scalars=1, name="scal_t")
        k = jnp.asarray(rng.standard_normal((52, 140)), jnp.float32)
        scal = jnp.asarray(rng.standard_normal((4, 1)), jnp.float32)
        want = np.asarray(ops.stencil_run(
            x, spec3, 4, bx=128, bt=2, backend="interpret",
            aux={"k": k}, scalars=scal))
        got = stencil_run_outofcore(
            x, spec3, 4, bx=128, bt=2, backend="interpret", tile=5,
            n_devices=4, aux={"k": k}, scalars=scal)
        np.testing.assert_array_equal(got, want, err_msg="scalars")

        # Batched grid (B=3): slabs shard grid axis 1, batch whole
        xb = jnp.asarray(rng.standard_normal((3, 52, 140)), jnp.float32)
        m = {}
        want = np.asarray(ops.stencil_run(
            xb, spec2, 4, bx=128, bt=2, backend="interpret"))
        got = stencil_run_outofcore(
            xb, spec2, 4, bx=128, bt=2, backend="interpret", tile=5,
            n_devices=4, metrics=m)
        assert m["n_devices"] == 4, m
        np.testing.assert_array_equal(got, want, err_msg="batched")
        print("OK")
    """)


def test_sharded_program_per_sweep_route():
    """ops.stencil_program_run with n_devices=4 + a tiny budget routes
    EVERY sweep through the composed runner — bitwise vs the solo
    in-core program run."""
    _run("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core.stencil import StencilProgram, Sweep, diffusion
        from repro.kernels import ops
        assert len(jax.devices()) == 4
        rng = np.random.default_rng(12)
        x = jnp.asarray(rng.standard_normal((60, 140)), jnp.float32)
        p = StencilProgram((Sweep("a", diffusion(2, 1), field="u"),
                            Sweep("b", diffusion(2, 2,
                                                 boundary="clamp"),
                                  field="u")), name="p9")
        want = np.asarray(ops.stencil_program_run(
            x, p, 3, bx=128, bt=1, backend="interpret"))
        # Budget below every sweep's ghost-charged per-device shard
        # (r=2: 19 slices of the 60-row grid) but above the 1-slice
        # tile's working set, so both sweeps stream.
        ws = 60 * 140 * 4 * 2
        got = ops.stencil_program_run(
            x, p, 3, bx=128, bt=1, backend="interpret",
            n_devices=4, hbm_budget=ws // 4)
        np.testing.assert_array_equal(np.asarray(got), want)
        print("OK")
    """)


def test_sharded_kernel_pipeline():
    """pipeline="kernel" composes per device: each device runs its
    chunks as persistent calls. Bitwise either way; metrics record the
    pipeline actually used."""
    _run("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core.stencil import diffusion
        from repro.kernels import engine, ops
        from repro.outofcore import stencil_run_outofcore
        assert len(jax.devices()) == 4
        rng = np.random.default_rng(13)
        x = jnp.asarray(rng.standard_normal((64, 140)), jnp.float32)
        spec = diffusion(2, 1)
        want = np.asarray(ops.stencil_run(
            x, spec, 3, bx=128, bt=2, backend="interpret"))
        m = {}
        got = stencil_run_outofcore(
            x, spec, 3, bx=128, bt=2, backend="interpret", tile=6,
            n_devices=4, pipeline="kernel", metrics=m)
        assert m["pipeline_requested"] == "kernel"
        if engine.kernel_pipeline_available("interpret")[0]:
            assert m["pipeline"] == "kernel" and m["n_chunks"] >= 4, m
        else:
            assert m["pipeline"] == "host" and m["fallback_reason"]
        assert m["n_devices"] == 4
        np.testing.assert_array_equal(got, want)
        print("OK")
    """)


# ---------------------------------------------------------------------------
# Satellite: batched sharded PROGRAMS with B % n_devices != 0 fall
# back from batch-axis to grid sharding with a warning (halo.py),
# instead of raising.
# ---------------------------------------------------------------------------

def test_program_batched_indivisible_falls_back_to_grid():
    _run("""
        import warnings
        import numpy as np, jax, jax.numpy as jnp
        from repro.core.stencil import StencilProgram, Sweep, diffusion
        from repro.distributed import halo
        assert len(jax.devices()) == 4
        rng = np.random.default_rng(14)
        p = StencilProgram((Sweep("a", diffusion(2, 1), field="u"),),
                           name="pb")
        xb = jnp.asarray(rng.standard_normal((3, 33, 140)), jnp.float32)
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            got = halo.stencil_program_run_sharded(
                {"u": xb}, p, 3, n_devices=4, bx=128, backend="interpret")["u"]
        assert any("falling back" in str(x.message) for x in w), \\
            [str(x.message) for x in w]
        # bitwise parity vs the solo Python loop over problems
        solo = jnp.stack([halo.stencil_program_run_sharded(
            {"u": xb[b]}, p, 3, n_devices=4, bx=128, backend="interpret")["u"]
            for b in range(3)])
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(solo))
        # divisible batches keep the batch-axis strategy, silently
        xb4 = jnp.asarray(rng.standard_normal((4, 33, 140)),
                          jnp.float32)
        with warnings.catch_warnings(record=True) as w2:
            warnings.simplefilter("always")
            halo.stencil_program_run_sharded(
                {"u": xb4}, p, 2, n_devices=4, bx=128, backend="interpret")
        assert not [x for x in w2
                    if "falling back" in str(x.message)]
        print("OK")
    """)


# ---------------------------------------------------------------------------
# In-process units: gather_slab and the extended metrics contract.
# ---------------------------------------------------------------------------

def test_gather_slab_units():
    from repro.distributed.halo import gather_slab
    bounds = [(0, 5), (5, 10), (10, 15)]
    slabs = [np.arange(lo, hi, dtype=np.float32).reshape(-1, 1)
             for lo, hi in bounds]

    # interior range within one owner: zero-copy view, zero foreign
    rows, foreign = gather_slab(slabs, bounds, 6, 9, owner=1)
    np.testing.assert_array_equal(rows[:, 0], [6, 7, 8])
    assert foreign == 0
    assert rows.base is not None        # a view, not a copy

    # range spanning all three owners, owned by the middle one
    rows, foreign = gather_slab(slabs, bounds, 3, 12, owner=1)
    np.testing.assert_array_equal(rows[:, 0], np.arange(3, 12))
    assert foreign == 4                 # rows 3,4 (d0) + 10,11 (d2)

    # ghost deeper than a neighbor slab: walks past the adjacent owner
    rows, foreign = gather_slab(slabs, bounds, 0, 15, owner=2)
    np.testing.assert_array_equal(rows[:, 0], np.arange(15))
    assert foreign == 10

    # leading-axis position is selectable
    rows, _ = gather_slab([s.T.copy() for s in slabs],
                          bounds, 4, 11, ax=1, owner=0)
    np.testing.assert_array_equal(rows[0], np.arange(4, 11))

    with pytest.raises(ValueError):
        gather_slab(slabs, bounds, 10, 16)      # beyond coverage
    with pytest.raises(ValueError):
        gather_slab(slabs, bounds, 7, 7)        # empty range


def test_solo_metrics_carry_sharding_fields():
    """The extended metrics contract is unconditional: a 1-device run
    reports n_devices=1, its own extent, and zero halo traffic."""
    from repro.core.stencil import diffusion
    from repro.outofcore import stencil_run_outofcore
    x = np.random.default_rng(15).standard_normal(
        (40, 140)).astype(np.float32)
    m: dict = {}
    stencil_run_outofcore(x, diffusion(2, 1), 2, bx=128, bt=1,
                          backend="interpret", tile=10, metrics=m)
    assert m["n_devices"] == 1
    assert m["slab_extents"] == [40]
    assert m["halo_rows_exchanged"] == 0
    assert m["halo_bytes_exchanged"] == 0
