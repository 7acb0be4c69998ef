"""Stencil serving front-end (serving/stencil_service.py).

The service's contract is *exactness with throughput*: every served
result equals the request's solo run bitwise (batching, bucketing and
padding are invisible to clients), compilation is bounded by bucketing,
and completions map back to the right uids in any arrival order.
"""
import json

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.stencil import AuxOperand, StencilSpec, diffusion, \
    hotspot2d, shift
from repro.kernels import ops, ref
from repro.serving import StencilRequest, StencilService


@pytest.fixture(autouse=True)
def _isolated_cache(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE",
                       str(tmp_path / "autotune.json"))


def _rand(shape, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal(shape), jnp.float32)


def _mixed_workload(n=9):
    """Interleaved specs/shapes: three compilation groups."""
    reqs = []
    for i in range(n):
        if i % 3 == 0:
            spec, shape = diffusion(2, 1), (12, 132)
        elif i % 3 == 1:
            spec, shape = hotspot2d(), (12, 132)
        else:
            spec, shape = diffusion(2, 2, boundary="clamp"), (10, 140)
        reqs.append(StencilRequest(uid=i, x=_rand(shape, seed=i),
                                   spec=spec, n_steps=3))
    return reqs


def test_service_results_equal_solo_runs():
    """check=True asserts bitwise equality inside the flush; here we
    also pin every result against the jnp oracle."""
    reqs = _mixed_workload()
    svc = StencilService(max_batch=4, backend="interpret", bx=128, bt=2,
                         check=True)
    done = svc.run(list(reqs))
    assert sorted(c.uid for c in done) == list(range(len(reqs)))
    by_uid = {c.uid: c for c in done}
    for r in reqs:
        want = ref.stencil_multistep(r.x, r.spec, r.n_steps)
        np.testing.assert_allclose(np.asarray(by_uid[r.uid].result),
                                   np.asarray(want),
                                   rtol=5e-5, atol=5e-5)


def test_service_buckets_bound_compilation():
    """17 same-key requests with max_batch=8 -> chunks 8+8+1: three
    dispatches but only TWO compiled programs (the B=8 bucket is
    reused; the trailing single request rides a B=1 bucket). An odd
    trailing chunk (e.g. 3) pads up to the next power of two."""
    spec = diffusion(2, 1)
    reqs = [StencilRequest(uid=i, x=_rand((10, 132), seed=i), spec=spec,
                           n_steps=2) for i in range(17)]
    svc = StencilService(max_batch=8, backend="interpret", bx=128, bt=2)
    done = svc.run(reqs)
    assert len(done) == 17
    assert svc.metrics["dispatches"] == 3
    assert svc.metrics["problems"] == 17
    assert len(svc._dispatchers) == 2          # (key, 8) and (key, 1)
    assert svc.metrics["pad_rows"] == 0
    # an odd trailing chunk pads up to the next power of two
    svc2 = StencilService(max_batch=8, backend="interpret", bx=128, bt=2)
    done2 = svc2.run([StencilRequest(uid=i, x=_rand((10, 132), seed=i),
                                     spec=spec, n_steps=2)
                      for i in range(11)])     # 8 + 3 -> pad 1
    assert len(done2) == 11
    assert svc2.metrics["dispatches"] == 2
    assert svc2.metrics["pad_rows"] == 1
    # padding is invisible: results still exact
    for c in done2:
        want = ref.stencil_multistep(_rand((10, 132), seed=c.uid),
                                     spec, 2)
        np.testing.assert_allclose(np.asarray(c.result),
                                   np.asarray(want),
                                   rtol=5e-5, atol=5e-5)


def test_service_aux_and_scalars():
    """Hotspot-style source operands and per-request scalars batch
    correctly through the service."""
    spec = StencilSpec(dims=2, radius=1, center=1.0,
                       axis_weights=((0.0, 0.0, 0.0),) * 2,
                       aux=(AuxOperand("p"),), name="svc_src")

    def upd(fields, s):
        j, c, sc = fields["x"], fields["c"], fields["scalars"]
        lap = (shift(j, 0, -1, "clamp") + shift(j, 0, 1, "clamp")
               + shift(j, 1, -1, "clamp") + shift(j, 1, 1, "clamp")
               - 4.0 * j)
        return j + sc[0] * c * lap

    vspec = StencilSpec(dims=2, radius=1, boundary="clamp", update=upd,
                        n_scalars=1,
                        aux=(AuxOperand("c", role="coeff"),),
                        name="svc_vc")
    reqs = []
    for i in range(3):
        reqs.append(StencilRequest(
            uid=i, x=_rand((12, 132), seed=i), spec=spec, n_steps=2,
            aux={"p": _rand((12, 132), seed=50 + i)}))
    for i in range(3, 6):
        reqs.append(StencilRequest(
            uid=i, x=_rand((12, 132), seed=i), spec=vspec, n_steps=2,
            aux={"c": _rand((12, 132), seed=50 + i) * 0.1},
            scalars=jnp.asarray([[0.2], [0.1]], jnp.float32)))
    svc = StencilService(max_batch=4, backend="interpret", bx=128, bt=2,
                         check=True)
    done = svc.run(reqs)
    by_uid = {c.uid: c for c in done}
    for r in reqs:
        want = ref.stencil_multistep(r.x, r.spec, r.n_steps, aux=r.aux,
                                     scalars=r.scalars)
        np.testing.assert_allclose(np.asarray(by_uid[r.uid].result),
                                   np.asarray(want),
                                   rtol=5e-5, atol=5e-5)
    assert svc.metrics["dispatches"] == 2      # one per spec group


def test_service_rejects_pre_batched_requests():
    svc = StencilService(backend="interpret", bx=128, bt=1)
    with pytest.raises(ValueError, match="single problems"):
        svc.submit(StencilRequest(uid=0, x=_rand((2, 12, 132)),
                                  spec=diffusion(2, 1), n_steps=1))
    with pytest.raises(ValueError, match="max_batch"):
        StencilService(max_batch=0)


def test_service_metrics_and_busy_fraction(traced):
    """The counters, and the flush's span tree: the service keeps no
    host-clock busy fraction; under a profiler a flush records one
    ``service.flush`` with grouping and, per bucket, stack, dispatch,
    device wait and to-host nested inside it."""
    reqs = _mixed_workload(6)        # three groups of two
    svc = StencilService(max_batch=4, backend="interpret", bx=128, bt=2)
    svc.run(_mixed_workload(6))      # compiles outside the trace
    m0 = dict(svc.metrics)
    done, snap, events = traced(lambda: svc.run(reqs))
    assert len(done) == 6
    d = {k: svc.metrics[k] - m0[k] for k in m0}
    assert d == {"dispatches": 3, "problems": 6, "pad_rows": 0,
                 "outofcore_dispatches": 0, "failed": 0,
                 "bucket_failures": 0}
    assert not hasattr(svc, "device_busy_fraction")
    counts = {k: v["count"] for k, v in snap.items()}
    assert counts == {"service.flush": 1, "service.group": 1,
                      "service.stack": 3, "service.dispatch": 3,
                      "service.device_wait": 3, "service.to_host": 3}
    (flush,) = [e for e in events if e[0] == "service.flush"]
    assert flush[3]["requests"] == 6 and flush[3]["buckets"] == 3
    for name, a, b, _ in events:
        assert flush[1] <= a <= b <= flush[2], name
    uids = sorted(u for e in events if e[0] == "service.dispatch"
                  for u in json.loads(e[3]["uids"]))
    assert uids == list(range(6))
    # Each bucket is stacked before its dispatch, and read back after.
    order = [e[0] for e in events if e[0] != "service.flush"]
    assert order == (["service.group"]
                     + ["service.stack", "service.dispatch"] * 3
                     + ["service.device_wait", "service.to_host"] * 3)
    f = snap["service.flush"]
    kids = sum(v["total_ns"] for k, v in snap.items()
               if k != "service.flush")
    assert f["self_ns"] == f["total_ns"] - kids > 0


def test_service_autotuned_blocking_resolves_per_group():
    """bx/bt left None resolve through the (batch-aware) autotuner
    once per (key, bucket), and the results stay exact."""
    reqs = [StencilRequest(uid=i, x=_rand((16, 300), seed=i),
                           spec=diffusion(2, 1), n_steps=2)
            for i in range(3)]
    svc = StencilService(max_batch=4, backend="interpret", check=True)
    done = svc.run(reqs)
    assert len(done) == 3
    (key_bucket,) = list(svc._resolved)
    bx, bt, variant = svc._resolved[key_bucket]
    assert bx % 128 == 0 and bt >= 1 and variant is not None


# --------------------------------------------------------------------------
# Per-request error isolation: a poisoned request fails ALONE
# --------------------------------------------------------------------------

class _PoisonGrid:
    """Quacks like a (16, 132) float32 grid until materialization —
    the shape/dtype pass submit() and bucketing (the compilation key
    hashes names and shapes, not values), then np.asarray raises, the
    way a corrupt client buffer or a poisoned aux value would."""
    ndim = 2
    shape = (16, 132)
    dtype = np.dtype(np.float32)

    def __array__(self, dtype=None, copy=None):
        raise RuntimeError("poisoned request payload")


def _iso_workload(spec):
    return [
        StencilRequest(uid=0, x=_rand((16, 132), 0), spec=spec,
                       n_steps=2),
        StencilRequest(uid=1, x=_PoisonGrid(), spec=spec, n_steps=2),
        StencilRequest(uid=2, x=_rand((16, 132), 2), spec=spec,
                       n_steps=2),
    ]


def test_failed_request_does_not_poison_its_bucket():
    spec = diffusion(2, 1)
    svc = StencilService(max_batch=4, backend="interpret", bx=128,
                         bt=1)
    done = svc.run(_iso_workload(spec))
    assert len(done) == 3            # every request completes
    by_uid = {c.uid: c for c in done}
    # the poisoned request fails, carrying its exception
    assert by_uid[1].result is None
    assert isinstance(by_uid[1].error, RuntimeError)
    assert "poisoned" in str(by_uid[1].error)
    # its bucket-mates still get results, equal to their solo runs
    for uid in (0, 2):
        assert by_uid[uid].error is None
        want = ops.stencil_run(_rand((16, 132), uid), spec, 2,
                               bx=128, bt=1, backend="interpret")
        np.testing.assert_array_equal(by_uid[uid].result,
                                      np.asarray(want))


def test_failed_request_metrics_accounting():
    spec = diffusion(2, 1)
    svc = StencilService(max_batch=4, backend="interpret", bx=128,
                         bt=1)
    svc.run(_iso_workload(spec))
    m = svc.metrics
    assert m["failed"] == 1          # exactly the poisoned request
    assert m["problems"] == 2        # only successes count as served
    # the solo retries that actually ran are real dispatches (the
    # bucket's own dispatch never completed, so: one per survivor)
    assert m["dispatches"] == 2


def test_error_isolation_with_healthy_second_bucket():
    """A poisoned bucket must not take down OTHER buckets already
    grouped in the same flush."""
    spec = diffusion(2, 1)
    other = hotspot2d()
    svc = StencilService(max_batch=4, backend="interpret", bx=128,
                         bt=1)
    reqs = _iso_workload(spec) + [
        StencilRequest(uid=3, x=_rand((12, 132), 3), spec=other,
                       n_steps=2),
    ]
    done = svc.run(reqs)
    by_uid = {c.uid: c for c in done}
    assert len(done) == 4
    assert by_uid[1].error is not None
    want = ops.stencil_run(_rand((12, 132), 3), other, 2, bx=128,
                           bt=1, backend="interpret")
    np.testing.assert_array_equal(by_uid[3].result, np.asarray(want))
    assert svc.metrics["failed"] == 1


def test_all_healthy_flush_reports_no_failures():
    spec = diffusion(2, 1)
    svc = StencilService(max_batch=4, backend="interpret", bx=128,
                         bt=1)
    reqs = [StencilRequest(uid=i, x=_rand((16, 132), i), spec=spec,
                           n_steps=2) for i in range(3)]
    done = svc.run(reqs)
    assert all(c.error is None for c in done)
    assert svc.metrics["failed"] == 0
    assert svc.metrics["problems"] == 3


def test_service_still_serves_after_a_poisoned_flush():
    """The service object survives: the flush after a failure serves
    normally (no stuck queue, no corrupted dispatcher cache)."""
    spec = diffusion(2, 1)
    svc = StencilService(max_batch=4, backend="interpret", bx=128,
                         bt=1)
    svc.run(_iso_workload(spec))
    done = svc.run([StencilRequest(uid=9, x=_rand((16, 132), 9),
                                   spec=spec, n_steps=2)])
    assert len(done) == 1 and done[0].error is None
    want = ops.stencil_run(_rand((16, 132), 9), spec, 2, bx=128,
                           bt=1, backend="interpret")
    np.testing.assert_array_equal(done[0].result, np.asarray(want))
