"""The stencil IR (thesis ch.5, generalized per the high-order follow-up).

A ``StencilSpec`` is a small intermediate representation of one explicit
structured-mesh update, rich enough that "any explicit solver" is a
config for the one blocked engine (``kernels/engine.py``) rather than a
new kernel — the direction of Zohouri et al.'s high-order work
(arXiv:2002.05983) and Kamalakkannan et al.'s solver generator
(arXiv:2101.01177). A spec fixes:

* **tap layout** — ``star`` (the thesis's first- to fourth-order
  benchmarks: per-axis weight rows in ``axis_weights``) or ``box`` (a
  general ``(2r+1,)*dims`` weight tensor in ``box_weights``, diagonal
  taps included), or a ``custom`` per-cell ``update`` callable for
  nonlinear / variable-coefficient updates (SRAD's diffusion step);
* **boundary mode** — ``"dirichlet0"`` (reads outside the grid return
  0, the thesis's fixed-halo convention) or ``"clamp"``
  (edge-replicate, Rodinia's clamped indexing — what SRAD and Hotspot
  actually use). The mode applies at *true grid edges only*: the
  multi-device runner keeps exchanging ghost cells across shard edges;
* **auxiliary operands** — named per-cell input grids with a role:
  ``"source"`` (added to the cell after every update step — Hotspot's
  power term) or ``"coeff"`` (a step-constant coefficient field the
  ``update`` reads, with its own boundary behavior — variable-
  coefficient updates). Every operand is windowed/halo-exchanged by
  the engine exactly like the main grid;
* **per-step scalars** — ``n_scalars`` runtime scalars per fused time
  step (SRAD's per-iteration ``q0^2`` from its global reduction).

For star layouts the update at cell ``x`` is

    out[x] = c_center * in[x]
           + sum_axis sum_{o in [-r..r], o != 0} w[axis, r+o] * in[x + o*e_axis]
           + sum_{source operands} s[x]

and the temporally-blocked kernels agree with the naive reference
bitwise up to float association for either boundary mode.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

BOUNDARIES = ("dirichlet0", "clamp")
AUX_ROLES = ("source", "coeff")


# ---------------------------------------------------------------------------
# Boundary-aware neighbor reads — the one shared definition of what a
# tap means. The oracle applies these to the whole grid (so the array
# edge IS the grid boundary); the engine's plugins apply them to
# windows whose out-of-grid cells were pre-filled by the engine, so the
# array edge is only ever the (cropped-away) window rim.
# ---------------------------------------------------------------------------

def shift(x: jax.Array, axis: int, offset: int,
          boundary: str = "dirichlet0") -> jax.Array:
    """x shifted so out[i] = x[i + offset] along ``axis``.

    Out-of-range reads follow ``boundary``: zero-filled for
    ``dirichlet0``, edge-replicated for ``clamp``. The edge replicate is
    a select of the broadcast edge slice over the zero-filled shift
    (Mosaic lowers no edge-mode pad), so both modes lower inside a
    TPU kernel.
    """
    if offset == 0:
        return x
    r = abs(offset)
    pad = [(0, 0)] * x.ndim
    pad[axis] = (r, r)
    idx = [slice(None)] * x.ndim
    idx[axis] = slice(r + offset, r + offset + x.shape[axis])
    out = jnp.pad(x, pad)[tuple(idx)]
    if boundary != "clamp":
        return out
    n = x.shape[axis]
    edge = jax.lax.slice_in_dim(x, n - 1 if offset > 0 else 0,
                                n if offset > 0 else 1, axis=axis)
    pos = jax.lax.broadcasted_iota(jnp.int32, x.shape, axis)
    outside = pos >= n - offset if offset > 0 else pos < -offset
    return jnp.where(outside, edge, out)


def shift_nd(x: jax.Array, offsets, boundary: str = "dirichlet0") -> jax.Array:
    """Multi-axis ``shift`` (box taps). Per-axis composition is exact
    for both boundary modes (corner reads clamp/zero per axis)."""
    out = x
    for axis, off in enumerate(offsets):
        if off:
            out = shift(out, axis, off, boundary)
    return out


@dataclasses.dataclass(frozen=True)
class AuxOperand:
    """A named per-cell input grid that rides along with the main grid.

    ``role``:
      * ``"source"`` — added to every cell after each update step (the
        Hotspot power term). Center-tap only, so its boundary mode is
        irrelevant (out-of-grid cells are zeroed).
      * ``"coeff"`` — a step-constant coefficient field handed to the
        spec's ``update`` callable; may be tapped at neighbor offsets,
        so it carries a boundary mode (``None`` inherits the spec's).
    """

    name: str
    role: str = "source"
    boundary: Optional[str] = None

    def __post_init__(self):
        if self.role not in AUX_ROLES:
            raise ValueError(f"aux role must be one of {AUX_ROLES}, "
                             f"got {self.role!r}")
        if self.boundary is not None and self.boundary not in BOUNDARIES:
            raise ValueError(f"aux boundary must be None or one of "
                             f"{BOUNDARIES}, got {self.boundary!r}")

    def boundary_of(self, spec: "StencilSpec") -> str:
        return self.boundary if self.boundary is not None else spec.boundary


@dataclasses.dataclass(frozen=True)
class StencilSpec:
    """One structured-mesh update in ``dims`` dimensions, radius ``r``.

    Exactly one of the three layouts is active:
      * star   — ``axis_weights[a, r + o]`` weights the neighbor at
        offset ``o`` along axis ``a``; the center column must be zero
        (the center coefficient is held once in ``center``);
      * box    — ``box_weights`` is a full ``(2r+1,)*dims`` tensor
        (center included; ``center`` is derived from it);
      * custom — ``update(fields, spec)`` computes one step per cell.
        ``fields`` maps ``"x"`` to the main grid/window, every coeff
        operand name to its grid/window, and (if ``n_scalars > 0``)
        ``"scalars"`` to that step's ``(n_scalars,)`` vector. Neighbor
        reads inside ``update`` must go through :func:`shift` /
        :func:`shift_nd` with the spec's boundary mode and must stay
        within ``radius``. Custom updates are 2D-only for now (the 3D
        engine streams planes; its plugin contract differs).
    """

    dims: int
    radius: int
    center: float = 0.0
    axis_weights: Optional[Tuple[Tuple[float, ...], ...]] = None
    name: str = "stencil"
    boundary: str = "dirichlet0"
    box_weights: Optional[tuple] = None
    aux: Tuple[AuxOperand, ...] = ()
    n_scalars: int = 0
    update: Optional[Callable] = None

    def __post_init__(self):
        if self.dims not in (2, 3):
            raise ValueError(f"dims must be 2 or 3, got {self.dims}")
        if not 1 <= self.radius <= 4:
            raise ValueError(f"radius must be in 1..4, got {self.radius}")
        if self.boundary not in BOUNDARIES:
            raise ValueError(f"boundary must be one of {BOUNDARIES}, "
                             f"got {self.boundary!r}")
        n_layouts = sum(p is not None
                        for p in (self.axis_weights, self.box_weights,
                                  self.update))
        if n_layouts != 1:
            raise ValueError(
                "exactly one of axis_weights (star), box_weights (box) or "
                f"update (custom) must be set; got {n_layouts}")
        if self.axis_weights is not None:
            aw = np.asarray(self.axis_weights, dtype=np.float64)
            if aw.shape != (self.dims, 2 * self.radius + 1):
                raise ValueError(
                    f"axis_weights must have shape "
                    f"{(self.dims, 2*self.radius+1)}, got {aw.shape}")
            if np.any(aw[:, self.radius] != 0.0):
                raise ValueError("center column of axis_weights must be 0 "
                                 "(use `center` instead)")
        if self.box_weights is not None:
            bw = np.asarray(self.box_weights, dtype=np.float64)
            want = (2 * self.radius + 1,) * self.dims
            if bw.shape != want:
                raise ValueError(
                    f"box_weights must have shape {want}, got {bw.shape}")
            # `center` is derived from the tensor so the two can never
            # disagree (flops/points accounting reads the tensor).
            ctr = float(bw[(self.radius,) * self.dims])
            object.__setattr__(self, "center", ctr)
        if self.update is not None and self.dims != 2:
            raise ValueError("custom `update` specs are 2D-only for now")
        names = [op.name for op in self.aux]
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate aux operand names: {names}")
        if any(n in ("x", "scalars") for n in names):
            raise ValueError('aux operand names "x" and "scalars" are '
                             'reserved')
        if any(op.role == "coeff" for op in self.aux) and self.update is None:
            raise ValueError("coeff aux operands require a custom `update` "
                             "(linear layouts have no use for them)")
        if self.n_scalars and self.update is None:
            raise ValueError("n_scalars > 0 requires a custom `update`")
        if self.n_scalars < 0:
            raise ValueError("n_scalars must be >= 0")

    # ---- layout ---------------------------------------------------------

    @property
    def layout(self) -> str:
        if self.update is not None:
            return "custom"
        return "box" if self.box_weights is not None else "star"

    # ---- derived quantities used by the performance model & benchmarks ----

    @property
    def points(self) -> int:
        """Number of taps per cell update.

        Star: the thesis's ``2*dims*r + 1``-point count. Box: nonzero
        entries of the weight tensor. Custom: the full ``(2r+1)^dims``
        dependency cone (a conservative proxy for the model).
        """
        if self.layout == "star":
            return 2 * self.dims * self.radius + 1
        if self.layout == "box":
            return int(np.count_nonzero(
                np.asarray(self.box_weights, dtype=np.float64)))
        return (2 * self.radius + 1) ** self.dims

    @property
    def flops_per_cell(self) -> int:
        """FLOPs per cell update: one multiply per tap + (taps-1) adds.

        Matches the thesis's counting (first-order 2D 5-point = 9 FLOPs,
        first-order 3D 7-point = 13 FLOPs).
        """
        return 2 * self.points - 1

    @property
    def weights(self) -> np.ndarray:
        return np.asarray(self.axis_weights, dtype=np.float32)

    @property
    def box(self) -> np.ndarray:
        return np.asarray(self.box_weights, dtype=np.float32)

    @property
    def source_operands(self) -> Tuple[AuxOperand, ...]:
        return tuple(op for op in self.aux if op.role == "source")

    @property
    def coeff_operands(self) -> Tuple[AuxOperand, ...]:
        return tuple(op for op in self.aux if op.role == "coeff")

    def halo(self, bt: int) -> int:
        """Halo width consumed by ``bt`` fused time steps (thesis §5.3.2)."""
        return bt * self.radius


# ---------------------------------------------------------------------------
# Factories for the stencils evaluated in the thesis (Tables 5-2, 5-6, 5-7)
# plus IR-level helpers.
# ---------------------------------------------------------------------------

def diffusion(dims: int, radius: int = 1,
              boundary: str = "dirichlet0") -> StencilSpec:
    """High-order diffusion stencil (thesis Table 5-7, 'Diffusion 2D/3D').

    Symmetric star: every tap at distance d along any axis has weight
    1/(points-1) * (1/d) normalized so all weights (incl. center) sum to 1
    — a stable diffusion operator for any radius.
    """
    raw = np.zeros((dims, 2 * radius + 1), dtype=np.float64)
    for a in range(dims):
        for o in range(1, radius + 1):
            raw[a, radius + o] = 1.0 / o
            raw[a, radius - o] = 1.0 / o
    total = raw.sum()
    center = 0.4
    raw *= (1.0 - center) / total
    suffix = "" if boundary == "dirichlet0" else "_clamp"
    return StencilSpec(dims=dims, radius=radius, center=center,
                       axis_weights=tuple(map(tuple, raw)),
                       boundary=boundary,
                       name=f"diffusion{dims}d_r{radius}{suffix}")


def hotspot2d(sdc: float = 0.1, r_amb: float = 0.05) -> StencilSpec:
    """Hotspot-like 5-point stencil (thesis §4.3.1.2) without the power term.

    The full Rodinia Hotspot (with the power grid as a source operand)
    lives in ``repro.apps.hotspot``; this spec captures its temperature
    stencil under the ch.5 template's Dirichlet-zero convention.
    """
    w = sdc
    aw = np.zeros((2, 3), dtype=np.float64)
    aw[:, 0] = w
    aw[:, 2] = w
    center = 1.0 - 4.0 * w - r_amb
    return StencilSpec(dims=2, radius=1, center=center,
                       axis_weights=tuple(map(tuple, aw)), name="hotspot2d")


def hotspot3d() -> StencilSpec:
    """7-point stencil analogous to Rodinia Hotspot3D's temperature update."""
    aw = np.zeros((3, 3), dtype=np.float64)
    aw[:, 0] = 0.12
    aw[:, 2] = 0.12
    return StencilSpec(dims=3, radius=1, center=1.0 - 6 * 0.12 - 0.02,
                       axis_weights=tuple(map(tuple, aw)), name="hotspot3d")


def _nested_tuple(a) -> tuple:
    """A numpy tensor as fully-nested (hashable) tuples."""
    if isinstance(a, np.ndarray) and a.ndim > 1:
        return tuple(_nested_tuple(row) for row in a)
    return tuple(float(v) for v in a)


def box_spec(weights, boundary: str = "dirichlet0",
             name: str = "box") -> StencilSpec:
    """A general box stencil from a ``(2r+1,)*dims`` weight tensor."""
    bw = np.asarray(weights, dtype=np.float64)
    if bw.ndim not in (2, 3) or len(set(bw.shape)) != 1 or bw.shape[0] % 2 == 0:
        raise ValueError(
            f"box weights must be a (2r+1,)*dims tensor, got {bw.shape}")
    radius = bw.shape[0] // 2
    return StencilSpec(dims=bw.ndim, radius=radius, center=0.0,
                       box_weights=_nested_tuple(bw),
                       boundary=boundary, name=name)


def star_as_box(spec: StencilSpec) -> StencilSpec:
    """The same stencil as ``spec`` re-expressed as a box weight tensor
    (star taps embedded on the axes) — layout parity made testable."""
    if spec.layout != "star":
        raise ValueError("star_as_box needs a star-layout spec")
    r, d = spec.radius, spec.dims
    bw = np.zeros((2 * r + 1,) * d, dtype=np.float64)
    ctr = (r,) * d
    bw[ctr] = spec.center
    aw = np.asarray(spec.axis_weights, dtype=np.float64)
    for a in range(d):
        for o in range(-r, r + 1):
            if o == 0:
                continue
            idx = list(ctr)
            idx[a] = r + o
            bw[tuple(idx)] += aw[a, r + o]
    return StencilSpec(dims=d, radius=r, center=0.0,
                       box_weights=_nested_tuple(bw),
                       boundary=spec.boundary, aux=spec.aux,
                       name=f"{spec.name}_as_box")


ALL_BENCH_SPECS = tuple(
    [diffusion(2, r) for r in (1, 2, 3, 4)]
    + [diffusion(3, r) for r in (1, 2, 3, 4)]
    + [hotspot2d(), hotspot3d()]
)


# ---------------------------------------------------------------------------
# Multi-sweep solver programs (the DAG layer above single sweeps).
#
# A ``StencilProgram`` names a list of sweeps, each a StencilSpec applied
# to one *evolving field*; sweeps may read other evolving fields or
# step-constant program inputs through their spec's aux operands (names
# resolve to evolving fields first, then to inputs). One "program step"
# runs every sweep once, in declaration order — the DAG edges (implicit
# producer/consumer ones plus explicit ``after``) are validated to be
# consistent with that order, following Kamalakkannan et al.'s
# multi-sweep chaining (arXiv:2101.01177).
#
# Cross-sweep *fusion*: maximal runs of consecutive sweeps that pass
# ``_can_fuse`` execute as ONE engine dispatch per program step (the
# engine re-imposes each sweep's own boundary fill before its apply, so
# fused execution is bitwise-equal to the per-sweep dispatch loop).
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Sweep:
    """One named application of a ``StencilSpec`` to an evolving field.

    ``field`` is the grid this sweep overwrites (the update's ``"x"``).
    ``after`` lists names of *earlier* sweeps this one must follow —
    pure documentation/validation, since execution order is declaration
    order. ``barrier=True`` forbids fusing this sweep with its
    predecessor even when ``_can_fuse`` would allow it.
    """

    name: str
    spec: StencilSpec
    field: str = "u"
    after: Tuple[str, ...] = ()
    barrier: bool = False

    def __post_init__(self):
        object.__setattr__(self, "after", tuple(self.after))
        if not self.name or not isinstance(self.name, str):
            raise ValueError("sweep name must be a non-empty string")
        if not self.field or not isinstance(self.field, str):
            raise ValueError(
                f"sweep {self.name!r}: field must be a non-empty string")
        if self.field in ("x", "scalars"):
            raise ValueError(
                f'sweep {self.name!r}: field names "x" and "scalars" are '
                f"reserved")


def _can_fuse(program: "StencilProgram", group, sweep: Sweep) -> bool:
    """May ``sweep`` join the fused ``group`` (run of earlier sweeps)?

    Legality rules (see docs/solvers.md):
      * no barrier, and same evolving field as the group;
      * no sweep in the group nor the candidate reads ANY evolving
        field through aux — fused stages see the previous stage's
        window rim, which is stale for other fields;
      * 3D additionally: equal radii, same boundary, star/box layouts
        only, no aux operands, no scalars (the plane-streaming kernel
        cycles one homogeneous stage shape).
    """
    if sweep.barrier:
        return False
    if sweep.field != group[0].field:
        return False
    for s in (*group, sweep):
        if program.evolving_reads(s):
            return False
    if program.dims == 3:
        a, b = group[0].spec, sweep.spec
        for sp in (a, b):
            if sp.layout == "custom" or sp.aux or sp.n_scalars:
                return False
        if b.radius != a.radius or b.boundary != a.boundary:
            return False
    return True


@dataclasses.dataclass(frozen=True)
class StencilProgram:
    """A small DAG of named sweeps over named evolving fields.

    Hashable and comparable by value (sweep list + name), so a program
    is a valid jit static argument, autotune cache key component and
    serving bucket key.
    """

    sweeps: Tuple[Sweep, ...]
    name: str = "program"

    def __post_init__(self):
        object.__setattr__(self, "sweeps", tuple(self.sweeps))
        if not self.sweeps:
            raise ValueError("a StencilProgram needs at least one sweep")
        names = [s.name for s in self.sweeps]
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate sweep names: {names}")
        dims = {s.spec.dims for s in self.sweeps}
        if len(dims) != 1:
            raise ValueError(
                f"all sweeps must share one dims, got {sorted(dims)}")
        fields = set(s.field for s in self.sweeps)
        by_pos = {s.name: i for i, s in enumerate(self.sweeps)}
        for i, s in enumerate(self.sweeps):
            for op in s.spec.aux:
                if op.name == s.field:
                    raise ValueError(
                        f"sweep {s.name!r} reads its own field "
                        f"{s.field!r} as an aux operand; the written "
                        f'field is the update\'s "x"')
            for dep in s.after:
                if dep not in by_pos:
                    raise ValueError(
                        f"sweep {s.name!r}: after={dep!r} names no sweep "
                        f"in {names}")
                if by_pos[dep] >= i:
                    raise ValueError(
                        f"sweep {s.name!r}: after={dep!r} must name an "
                        f"earlier sweep (execution order is declaration "
                        f"order)")
        for f in fields:
            if f in ("x", "scalars"):
                raise ValueError(f"field name {f!r} is reserved")

    # ---- namespace ------------------------------------------------------

    @property
    def dims(self) -> int:
        return self.sweeps[0].spec.dims

    @property
    def fields(self) -> Tuple[str, ...]:
        """Evolving field names, in first-written order."""
        return tuple(dict.fromkeys(s.field for s in self.sweeps))

    @property
    def n_fields(self) -> int:
        return len(self.fields)

    @property
    def input_names(self) -> Tuple[str, ...]:
        """Step-constant program inputs (aux names that are not fields)."""
        fields = set(self.fields)
        out = []
        for s in self.sweeps:
            for op in s.spec.aux:
                if op.name not in fields and op.name not in out:
                    out.append(op.name)
        return tuple(out)

    def evolving_reads(self, sweep: Sweep) -> Tuple[str, ...]:
        """Names of evolving fields ``sweep`` reads through aux."""
        fields = set(self.fields)
        return tuple(op.name for op in sweep.spec.aux if op.name in fields)

    def dependencies(self) -> dict:
        """sweep name -> names of earlier sweeps whose writes it consumes
        (implicit RAW/WAW edges plus the explicit ``after`` edges)."""
        last_writer: dict = {}
        deps = {}
        for s in self.sweeps:
            d = set(s.after)
            if s.field in last_writer:
                d.add(last_writer[s.field])
            for nm in self.evolving_reads(s):
                if nm in last_writer:
                    d.add(last_writer[nm])
            deps[s.name] = tuple(sorted(d))
            last_writer[s.field] = s.name
        return deps

    @property
    def n_scalars(self) -> int:
        return sum(s.spec.n_scalars for s in self.sweeps)

    # ---- fusion ---------------------------------------------------------

    def fuse_groups(self) -> Tuple[Tuple[Sweep, ...], ...]:
        """Maximal runs of consecutive fusable sweeps (each run = one
        engine dispatch per program step)."""
        groups: list = []
        for s in self.sweeps:
            if groups and _can_fuse(self, groups[-1], s):
                groups[-1].append(s)
            else:
                groups.append([s])
        return tuple(tuple(g) for g in groups)

    @property
    def fully_fused(self) -> bool:
        return len(self.fuse_groups()) == 1

    @staticmethod
    def group_radius(group) -> int:
        """Halo consumed by one pass over a fused group."""
        return sum(s.spec.radius for s in group)

    @property
    def max_group_radius(self) -> int:
        return max(self.group_radius(g) for g in self.fuse_groups())

    # ---- planning & caching --------------------------------------------

    def cache_token(self) -> str:
        """Autotune cache-key head: every field of every sweep that can
        change the winning plan (same name-as-weights-proxy convention
        as StencilSpec — weight *values* ride on the spec name)."""
        parts = []
        for s in self.sweeps:
            sp = s.spec
            ax = ",".join(f"{op.name}:{op.role[0]}" for op in sp.aux) or "-"
            parts.append(
                f"{s.name}>{s.field}@{sp.name}"
                f"(d{sp.dims},r{sp.radius},b{sp.boundary},L{sp.layout},"
                f"ax[{ax}],sc{sp.n_scalars}{',B' if s.barrier else ''})")
        return f"P[{self.name}]{{{';'.join(parts)}}}"

    def plan_proxy(self) -> "ProgramPlanProxy":
        """A StencilSpec-shaped view for the blocking/roofline planners.

        ``radius`` is the worst per-dispatch halo (max over fuse groups
        of the group's summed radii); ``points``/``flops_per_cell``
        count every sweep of one program step; ``aux`` holds the
        step-constant inputs plus one synthetic coeff entry per evolving
        field beyond the first (they are HBM-resident too).
        """
        fields = self.fields
        aux: list = []
        seen = set()
        for s in self.sweeps:
            for op in s.spec.aux:
                if op.name in fields or op.name in seen:
                    continue
                seen.add(op.name)
                aux.append(op)
        for f in fields[1:]:
            aux.append(AuxOperand(name=f"__field__{f}", role="coeff"))
        return ProgramPlanProxy(
            dims=self.dims,
            radius=self.max_group_radius,
            points=sum(s.spec.points for s in self.sweeps),
            flops_per_cell=sum(s.spec.flops_per_cell for s in self.sweeps),
            aux=tuple(aux),
            n_scalars=self.n_scalars,
            boundary=self.sweeps[0].spec.boundary,
            name=f"program:{self.name}",
        )

    @staticmethod
    def single(spec: StencilSpec, field: str = "u",
               name: Optional[str] = None) -> "StencilProgram":
        """The one-sweep program equivalent to running ``spec``."""
        return StencilProgram(
            sweeps=(Sweep(name=spec.name, spec=spec, field=field),),
            name=name if name is not None else spec.name)


@dataclasses.dataclass(frozen=True)
class ProgramPlanProxy:
    """Duck-typed StencilSpec stand-in for ``core.blocking`` planners.

    ``BlockPlan`` / ``select_config`` / ``plan_tiles`` only read the
    attributes below; a fused group's combined radius may exceed
    StencilSpec's own radius cap (4), hence a separate type rather than
    a synthesized spec.
    """

    dims: int
    radius: int
    points: int
    flops_per_cell: int
    aux: Tuple[AuxOperand, ...]
    n_scalars: int
    boundary: str
    name: str
    layout: str = "program"

    def halo(self, bt: int) -> int:
        return bt * self.radius

    @property
    def source_operands(self) -> Tuple[AuxOperand, ...]:
        return tuple(op for op in self.aux if op.role == "source")

    @property
    def coeff_operands(self) -> Tuple[AuxOperand, ...]:
        return tuple(op for op in self.aux if op.role == "coeff")
