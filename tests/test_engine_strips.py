"""The 2D revolving kernel's register strips (kernels/engine.py).

The kernel runs its fused steps on row strips of a window of whole lane
tiles, and skips the boundary fill where a strip holds no cell outside
the grid. Only data movement changed, so every result here is bitwise
equal to the engine's other 2D kernel (``multioperand``: full-height
windows, filled at every step, no strip code) and to ``kernels/ref.py``.

On the CPU, XLA contracts ``a * b + c`` into fused multiply-adds in
different places in different programs (the reference, each
interpreted kernel), so every check runs twice: here within float32
rounding, and bitwise in one child process whose XLA may not emit FMA
instructions (``--xla_cpu_max_isa=AVX``).
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import blocking
from repro.core.stencil import AuxOperand, StencilSpec, diffusion, shift
from repro.kernels import engine, ref

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
TOL = dict(rtol=3e-5, atol=3e-5)
BX = 128
# Five tiles of 128 columns: the lane-aligned windows of tiles 1 and 2
# lie inside the grid, those of tiles 0, 3 and 4 reach past its edges.
WIDTH = 520


def _rand(shape, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal(shape), jnp.float32)


def _rows(halo: int) -> int:
    """A panel of three strips, the middle one inside the grid, whose
    height is not a multiple of the strip."""
    strip = blocking.strip_rows(BX, halo, 1 << 20)
    return 2 * strip + 2 * blocking.row_halo(halo) + 5


def _assert_fast_path_runs(rows: int, halo: int, lo=0, hi=None):
    padded = blocking.round_up(rows, 8)
    strip = blocking.strip_rows(BX, halo, padded)
    n = -(-padded // strip)
    edge = blocking.edge_strips(padded, strip, halo, lo,
                                rows if hi is None else hi)
    assert n >= 3 and padded % strip and 0 < edge < n


def _full_height(x, spec, bt, **kw):
    return engine.stencil_call(x, spec, bx=BX, bt=bt,
                               variant="multioperand",
                               backend="interpret", **kw)


def _strips(x, spec, bt, **kw):
    return engine.stencil_call(x, spec, bx=BX, bt=bt, variant="revolving",
                               backend="interpret", **kw)


def _close(got, want):
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)


def _equal(got, want):
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# Each check compares the strip kernel with its oracles through ``same``:
# ``_close`` in this process, ``_equal`` in the child without FMA.

def _plain(boundary, r, bt):
    def check(same):
        spec = diffusion(2, r, boundary=boundary)
        rows = _rows(bt * r)
        _assert_fast_path_runs(rows, bt * r)
        x = _rand((rows, WIDTH), seed=r * 10 + bt)
        got = _strips(x, spec, bt)
        same(got, _full_height(x, spec, bt))
        same(got, ref.stencil_multistep(x, spec, bt))
    return check


def _short(boundary):
    """A panel shorter than one strip runs as one strip."""
    def check(same):
        spec = diffusion(2, 1, boundary=boundary)
        x = _rand((21, WIDTH), seed=3)
        assert blocking.strip_rows(BX, 8, 24) == 24
        got = _strips(x, spec, 8)
        same(got, _full_height(x, spec, 8))
        same(got, ref.stencil_multistep(x, spec, 8))
    return check


def _varcoef(fields, spec):
    """``x + s * k * lap(x)``: a coeff operand and a per-step scalar."""
    x, k, b = fields["x"], fields["k"], spec.boundary
    lap = (shift(x, 0, -1, b) + shift(x, 0, 1, b) + shift(x, 1, -1, b)
           + shift(x, 1, 1, b) - 4.0 * x)
    return x + fields["scalars"][0] * k * lap


VARCOEF = StencilSpec(dims=2, radius=1, boundary="clamp", update=_varcoef,
                      aux=(AuxOperand("k", role="coeff"),
                           AuxOperand("q", role="source")),
                      n_scalars=1, name="varcoef")


def _operands(same):
    """A source grid, a coeff operand and per-step scalars."""
    bt = 8
    rows = _rows(bt)
    _assert_fast_path_runs(rows, bt)
    shape = (rows, WIDTH)
    x = _rand(shape, 0)
    aux = {"k": 0.2 * jnp.abs(_rand(shape, 1)), "q": 0.01 * _rand(shape, 2)}
    scal = jnp.linspace(0.5, 1.0, bt, dtype=jnp.float32).reshape(bt, 1)
    got = _strips(x, VARCOEF, bt, aux=aux, scalars=scal)
    same(got, _full_height(x, VARCOEF, bt, aux=aux, scalars=scal))
    same(got, ref.stencil_multistep(x, VARCOEF, bt, aux=aux, scalars=scal))


def _program(same):
    """A two-stage fused program: each step applies both sweeps."""
    specs = (diffusion(2, 1, boundary="clamp"), diffusion(2, 2))
    bt = 4                                      # halo 4 * (1 + 2)
    rows = _rows(12)
    _assert_fast_path_runs(rows, 12)
    x = _rand((rows, WIDTH), seed=7)

    def call(variant):
        return engine.stencil_call_program(x, specs, bx=BX, bt=bt,
                                           variant=variant,
                                           backend="interpret")

    got = call("revolving")
    same(got, call("multioperand"))
    want = x
    for _ in range(bt):
        for sp in specs:
            want = ref.stencil_step(want, sp)
    same(got, want)


def _batch(same):
    """A batch equals the vmap oracle and each problem's solo run."""
    spec = diffusion(2, 1, boundary="clamp")
    bt = 8
    xs = _rand((3, _rows(bt), WIDTH), seed=11)
    got = _strips(xs, spec, bt)
    same(got, engine.stencil_call_vmap(xs, spec, bx=BX, bt=bt,
                                       backend="interpret"))
    for b in range(3):
        same(got[b], _strips(xs[b], spec, bt))
    same(got, ref.stencil_multistep(xs, spec, bt))


def _interval(boundary):
    """Rows outside a traced ``[lo, hi)`` are outside the grid, so the
    rows inside evolve as a grid of their own. The strips holding ``lo``
    and ``hi`` take the boundary path in the panel's middle; the first
    strip lies wholly outside."""
    def check(same):
        spec = diffusion(2, 1, boundary=boundary)
        bt = 8
        strip = blocking.strip_rows(BX, bt, 1 << 20)
        rows = 4 * strip + 21
        lo, hi = strip + 48, rows - 45
        _assert_fast_path_runs(rows, bt, lo, hi)
        x = _rand((rows, WIDTH), seed=5)

        def run(variant):
            fn = jax.jit(lambda x, lo, hi: engine.stencil_call(
                x, spec, bx=BX, bt=bt, variant=variant,
                backend="interpret", valid_lo=lo, valid_hi=hi))
            return fn(x, jnp.int32(lo), jnp.int32(hi))[lo:hi]

        got = run("revolving")
        same(got, run("multioperand"))
        same(got, ref.stencil_multistep(x[lo:hi], spec, bt))
    return check


CHECKS = {f"{b}_r{r}_bt{bt}": _plain(b, r, bt)
          for b in ("clamp", "dirichlet0")
          for r in (1, 2, 3, 4) for bt in (1, 8)}
CHECKS.update({"short_clamp": _short("clamp"),
               "short_dirichlet0": _short("dirichlet0"),
               "source_coeff_scalars": _operands,
               "two_stage_program": _program,
               "batch": _batch,
               "interval_clamp": _interval("clamp"),
               "interval_dirichlet0": _interval("dirichlet0")})


def bitwise_report() -> dict:
    """Every check under ``_equal``: case -> None, or why it failed."""
    out = {}
    for case, check in CHECKS.items():
        try:
            check(_equal)
            out[case] = None
        except AssertionError as e:
            out[case] = str(e)[:2000]
    return out


@pytest.fixture(scope="module")
def without_fma():
    """``bitwise_report()`` from a child process whose XLA emits no
    fused multiply-add."""
    script = """
        import json
        import test_engine_strips
        print(json.dumps(test_engine_strips.bitwise_report()))
    """
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([SRC, os.path.dirname(__file__)]),
               XLA_FLAGS="--xla_cpu_max_isa=AVX")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(script)],
                         env=env, capture_output=True, text=True,
                         timeout=900)
    assert out.returncode == 0, f"stdout:{out.stdout}\nstderr:" \
                                f"{out.stderr[-4000:]}"
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("case", sorted(CHECKS))
def test_strip_kernel_matches_its_oracles(case, without_fma):
    """Within float32 rounding here; bitwise where XLA emits no FMA."""
    CHECKS[case](_close)
    assert without_fma[case] is None, without_fma[case]


def _hotspot():
    from repro.apps import hotspot
    return hotspot.spec_of(hotspot.HotspotParams())


@pytest.mark.parametrize("make_spec,boundary", [
    (_hotspot, "clamp"), (lambda: diffusion(2, 1), "dirichlet0")],
    ids=["hotspot2d", "diffusion2d_r1"])
def test_strip_geometry_of_the_hotspot_plans(make_spec, boundary, tmp_path,
                                             monkeypatch):
    """8192 rows, radius 1: the model plans ``bx=256 bt=8`` for either
    boundary, with or without a source grid. A strip fills the 64
    vector registers (3 lane tiles at bx=128, 4 at bx=256), and a tile
    inside the grid's columns sends its first and last strip down the
    boundary path."""
    from repro.core.perf_model import V5E
    from repro.kernels import autotune
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "at.json"))
    spec = make_spec()
    tuned = autotune.plan((8192, 8192), spec, backend="pallas", tpu=V5E,
                          n_steps=64, measure=False, use_cache=False)
    assert (spec.boundary, tuned.bx, tuned.bt, tuned.variant) == \
        (boundary, 256, 8, "revolving")
    strip = blocking.strip_rows(tuned.bx, spec.halo(tuned.bt), 8192)
    assert strip == 112
    assert blocking.edge_strips(8192, strip, spec.halo(tuned.bt), 0,
                                8192) == 2
    assert blocking.strip_rows(128, 8, 8192) == 152
    assert blocking.edge_strips(8192, 152, 8, 0, 8192) == 2
    # A deep halo keeps at least twice its rows per strip.
    assert blocking.strip_rows(1024, 16, 8192) == 32
