"""Program spans (``repro.spans``): nothing with the profiler off, exact
self times when nested, one profiler session at a time, and the spans
``ops.stencil_run`` and ``StencilService.flush`` record on the
profiler's host plane."""
import itertools

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import spans
from repro.core.stencil import AuxOperand, StencilSpec, diffusion
from repro.kernels import ops
from repro.serving import StencilRequest, StencilService


@pytest.fixture(autouse=True)
def _fresh(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE",
                       str(tmp_path / "autotune.json"))
    spans.reset()
    yield
    spans.reset()


class _Annotation:
    """Stands in for ``jax.profiler.TraceAnnotation``; counts builds."""

    built = 0

    def __init__(self, name, **meta):
        type(self).built += 1
        self.meta = meta

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set_metadata(self, **meta):
        self.meta.update(meta)


@pytest.fixture
def fake_profiler(monkeypatch):
    """A profiler switch, a clock that ticks 10 ns a read, and a
    TraceAnnotation that counts how often it is built."""
    state = {"on": True, "reads": 0}
    ticks = itertools.count(0, 10)

    def clock():
        state["reads"] += 1
        return next(ticks)

    monkeypatch.setattr(spans, "_is_enabled", lambda: state["on"])
    monkeypatch.setattr(spans, "_clock", clock)
    monkeypatch.setattr(spans, "TraceAnnotation", _Annotation)
    _Annotation.built = 0
    return state


def test_profiler_off_records_nothing_and_builds_nothing(fake_profiler):
    fake_profiler["on"] = False
    with spans.span("a", k=lambda: pytest.fail("meta built")) as sp:
        sp.set(more=lambda: pytest.fail("meta built"))
        with spans.span("b"):
            pass
    assert spans.span("a") is spans.span("b")    # one shared null span
    assert spans.snapshot() == {}
    assert _Annotation.built == 0 and fake_profiler["reads"] == 0


def test_profiler_off_builds_no_trace_annotation_for_real(monkeypatch):
    """With no profiler session, the real off path never reaches JAX's
    TraceAnnotation and leaves no aggregate behind."""
    assert not jax.profiler.TraceAnnotation.is_enabled()
    calls = []
    monkeypatch.setattr(spans, "TraceAnnotation",
                        lambda *a, **k: calls.append(a))
    x = jnp.ones((16, 128), jnp.float32)
    ops.stencil_run(x, diffusion(2, 1), 3, bx=128, bt=2,
                    backend="interpret")
    assert calls == [] and spans.snapshot() == {}


def test_nested_spans_give_self_time(fake_profiler):
    # Clock reads, 10 ns apart: outer 0, inner 10..20, inner 30..40,
    # outer ends at 50.
    with spans.span("outer", n=lambda: 3) as sp:
        sp.set(done=True)
        with spans.span("inner"):
            pass
        with spans.span("inner"):
            pass
    snap = spans.snapshot()
    assert snap["outer"] == {"count": 1, "total_ns": 50, "self_ns": 30,
                             "max_ns": 50}
    assert snap["inner"] == {"count": 2, "total_ns": 20, "self_ns": 20,
                             "max_ns": 10}
    assert _Annotation.built == 3
    # The snapshot is a copy.
    snap["outer"]["count"] = 99
    assert spans.snapshot()["outer"]["count"] == 1


def test_new_session_clears_the_previous_aggregates(fake_profiler):
    with spans.span("setup"):
        pass
    with spans.span("setup"):          # the same session: accumulates
        pass
    assert spans.snapshot()["setup"]["count"] == 2
    fake_profiler["on"] = False
    with spans.span("untraced"):       # between sessions
        pass
    assert set(spans.snapshot()) == {"setup"}   # kept until the next
    fake_profiler["on"] = True
    with spans.span("window"):
        pass
    assert set(spans.snapshot()) == {"window"}


def test_stencil_run_records_plan_and_one_sweep_per_dispatch(traced):
    x = jnp.asarray(np.random.default_rng(0).standard_normal((16, 128)),
                    jnp.float32)
    spec = diffusion(2, 1)
    ops.stencil_run(x, spec, 5, bx=128, bt=2, backend="interpret")
    d0 = ops.dispatch_count()
    _, snap, events = traced(lambda: [
        jax.block_until_ready(ops.stencil_run(
            x, spec, 5, bx=128, bt=2, backend="interpret"))
        for _ in range(2)])
    sweeps = ops.dispatch_count() - d0
    assert sweeps == 6                          # 2 + 2 + 1 steps, twice
    assert {k: v["count"] for k, v in snap.items()} == {
        "ops.stencil_run": 2, "ops.plan": 2, "ops.sweep": sweeps}
    run = snap["ops.stencil_run"]
    assert run["self_ns"] == run["total_ns"] - sum(
        snap[k]["total_ns"] for k in ("ops.plan", "ops.sweep"))
    assert [e[3]["bt"] for e in events if e[0] == "ops.sweep"] == \
        [2, 2, 1] * 2


SCALED = StencilSpec(dims=2, radius=1, boundary="clamp",
                     update=lambda f, sp: f["x"] * f["k"],
                     aux=(AuxOperand("k", role="coeff"),
                          AuxOperand("q", role="source")),
                     name="scaled")


@pytest.mark.parametrize("spec,operands,boundary,streams", [
    (diffusion(2, 1), (), "dirichlet0", 1),
    (diffusion(2, 1, boundary="clamp"), ("source",), "clamp", 2),
    (SCALED, ("k", "q"), "clamp", 3),
    (diffusion(3, 1), (), "dirichlet0", 1),
], ids=["sourceless", "legacy_source", "coeff_and_source", "3d"])
def test_sweep_span_names_boundary_and_streams(fake_profiler, monkeypatch,
                                                spec, operands, boundary,
                                                streams):
    """The grids a sweep streams: the state, one grid for all sources,
    one per coeff operand."""
    seen = []

    class Recorded(_Annotation):
        def __init__(self, name, **meta):
            super().__init__(name, **meta)
            seen.append((name, self.meta))

    monkeypatch.setattr(spans, "TraceAnnotation", Recorded)
    shape = (16, 128) if spec.dims == 2 else (8, 8, 128)
    x = jnp.ones(shape, jnp.float32)
    kw = {"source": x} if operands == ("source",) else \
        {"aux": {n: x for n in operands}} if operands else {}
    ops.stencil_sweep(x, spec, bx=128, bt=1, backend="interpret", **kw)
    meta = dict(seen)["ops.sweep"]
    assert (meta["bt"], meta["boundary"], meta["streams"]) == \
        (1, boundary, streams)


def test_service_records_one_flush_and_per_bucket_spans(traced):
    spec = diffusion(2, 1)

    def reqs(uids, shape):
        rng = np.random.default_rng(uids[0])
        return [StencilRequest(uid=u, x=rng.standard_normal(shape)
                               .astype(np.float32), spec=spec, n_steps=2)
                for u in uids]

    svc = StencilService(max_batch=2, backend="interpret", bx=128, bt=1)
    # Warm every bucket the traced flushes use.
    svc.run(reqs([0, 1], (16, 128)) + reqs([2], (16, 128))
            + reqs([3], (8, 128)))

    def flushes():
        a = svc.run(reqs([10, 11, 12], (16, 128)))    # buckets 2 + 1
        b = svc.run(reqs([13], (8, 128)))             # bucket 1
        return a + b

    done, snap, events = traced(flushes)
    assert sorted(c.uid for c in done) == [10, 11, 12, 13]
    counts = {k: v["count"] for k, v in snap.items()}
    assert counts == {"service.flush": 2, "service.group": 2,
                      "service.stack": 3, "service.dispatch": 3,
                      "service.device_wait": 3, "service.to_host": 3}
    assert [e[3]["buckets"] for e in events
            if e[0] == "service.flush"] == [2, 1]
    assert [(e[3]["bucket"], e[3]["pad"]) for e in events
            if e[0] == "service.dispatch"] == [(2, 0), (1, 0), (1, 0)]


def test_failed_bucket_runs_its_requests_under_solo_spans(traced):
    class Poison:
        ndim, shape, dtype = 2, (16, 128), np.dtype(np.float32)

        def __array__(self, dtype=None, copy=None):
            raise RuntimeError("poisoned")

    spec = diffusion(2, 1)
    svc = StencilService(max_batch=2, backend="interpret", bx=128, bt=1)
    good = np.ones((16, 128), np.float32)
    done, snap, events = traced(lambda: svc.run([
        StencilRequest(uid=0, x=good, spec=spec, n_steps=1),
        StencilRequest(uid=1, x=Poison(), spec=spec, n_steps=1)]))
    assert [c.error is None for c in sorted(done, key=lambda c: c.uid)] \
        == [True, False]
    assert snap["service.solo"]["count"] == 2
    assert sorted(e[3]["uid"] for e in events
                  if e[0] == "service.solo") == [0, 1]
    assert "service.dispatch" not in snap    # the stack raised first


def test_profiler_host_plane_nests_program_spans(traced):
    """On the profiler's own trace the program spans sit inside an
    annotation the caller opened, with their stats."""
    x = jnp.zeros((16, 128), jnp.float32)
    spec = diffusion(2, 1)
    ops.stencil_run(x, spec, 2, bx=128, bt=2, backend="interpret")

    def call():
        with jax.profiler.TraceAnnotation("test.outer"):
            return jax.block_until_ready(ops.stencil_run(
                x, spec, 2, bx=128, bt=2, backend="interpret"))

    _, _, events = traced(call)
    by = {e[0]: e for e in events}
    assert set(by) == {"test.outer", "ops.stencil_run", "ops.plan",
                       "ops.sweep"}

    def inside(child, parent):
        return by[parent][1] <= by[child][1] <= by[child][2] \
            <= by[parent][2]

    assert inside("ops.stencil_run", "test.outer")
    assert inside("ops.plan", "ops.stencil_run")
    assert inside("ops.sweep", "ops.stencil_run")
    assert by["ops.plan"][2] <= by["ops.sweep"][1]
    assert by["ops.stencil_run"][3] == {"shape": "(16, 128)",
                                        "n_steps": 2}
    # A 16-row panel is one strip, and it reaches past both grid edges.
    assert by["ops.sweep"][3] == {"bt": 2, "boundary": "dirichlet0",
                                  "streams": 1, "strip": 16,
                                  "edge_strips": 1}
