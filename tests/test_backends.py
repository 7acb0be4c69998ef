"""Multi-backend execution: the differential acceptance matrix, the
GPU backend's validation gates, the v9 per-backend autotune cache, the
corrupt-cache hardening, the composed out-of-core x multi-device
routing, and the perf trajectory / regression gate.

Tolerance policy (docs/portability.md):

  * ``interpret`` is the ground-truth backend — the Pallas kernel body
    executed in Python. Everything engine-family (interpret, pallas,
    gpu) is the SAME traced computation, so where two engine backends
    both run, agreement is **bitwise**.
  * ``reference`` (the jit-compiled jnp oracle) associates float adds
    differently, so interpret-vs-reference agreement is to the repo's
    standing tolerance ``rtol=atol=3e-5`` (same as tests/test_engine).

The matrix below parametrizes over ``ops.backend_pairs()``: on a CPU
host that is (interpret, reference); a TPU host adds (interpret,
pallas) and a GPU host (interpret, gpu) — the pass widens by itself on
bigger hardware, with no test edits.
"""
import json
import logging
import types

import numpy as np
import jax.numpy as jnp
import pytest

from repro import compat
from repro.core import perf_model as pm
from repro.core.stencil import StencilProgram, Sweep, diffusion
from repro.kernels import autotune, engine, ops

TOL = dict(rtol=3e-5, atol=3e-5)


@pytest.fixture(autouse=True)
def _isolated_cache(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE",
                       str(tmp_path / "autotune.json"))
    autotune._MEM.clear()


def _rand(shape, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal(shape), jnp.float32)


def _agree(a, b, pair):
    """Apply the tolerance policy for one backend pair."""
    a, b = np.asarray(a), np.asarray(b)
    if "reference" in pair:
        np.testing.assert_allclose(a, b, **TOL)
    else:           # engine-family backends: same trace, bitwise
        np.testing.assert_array_equal(a, b)


# --------------------------------------------------------------------------
# Backend discovery
# --------------------------------------------------------------------------

def test_available_backends_always_include_the_oracles():
    avail = compat.available_backends()
    assert "interpret" in avail and "reference" in avail
    # compiled backends only where their platform actually is
    if compat.platform() != "tpu":
        assert "pallas" not in avail
    if compat.platform() != "gpu":
        assert "gpu" not in avail


def test_backend_pairs_all_anchor_on_interpret():
    pairs = ops.backend_pairs()
    assert pairs, "at least (interpret, reference) must be testable"
    assert all(oracle == "interpret" for oracle, _ in pairs)
    assert ("interpret", "reference") in pairs


def test_resolve_auto_matches_platform():
    resolved = ops.resolve_backend("auto")
    if compat.platform() == "tpu":
        assert resolved == "pallas"
    elif compat.platform() == "gpu" and compat.has_gpu_pallas():
        assert resolved == "gpu"
    else:
        assert resolved == "interpret"
    # explicit names pass through untouched
    assert ops.resolve_backend("reference") == "reference"


# --------------------------------------------------------------------------
# The differential acceptance matrix: engine / program / out-of-core
# on every pair this host can run.
# --------------------------------------------------------------------------

@pytest.mark.parametrize("pair", ops.backend_pairs(),
                         ids=lambda p: f"{p[0]}-vs-{p[1]}")
@pytest.mark.parametrize("dims", [2, 3])
def test_matrix_stencil_run(pair, dims):
    spec = diffusion(dims, 1)
    shape = (24, 8, 132)[-dims:] if dims == 3 else (24, 132)
    x = _rand(shape)
    outs = [ops.stencil_run(x, spec, 3, bx=128, bt=2, backend=b)
            for b in pair]
    _agree(outs[0], outs[1], pair)


@pytest.mark.parametrize("pair", ops.backend_pairs(),
                         ids=lambda p: f"{p[0]}-vs-{p[1]}")
def test_matrix_batched_run(pair):
    spec = diffusion(2, 1)
    x = _rand((3, 16, 132))
    outs = [ops.stencil_run(x, spec, 2, bx=128, bt=1, backend=b)
            for b in pair]
    _agree(outs[0], outs[1], pair)


@pytest.mark.parametrize("pair", ops.backend_pairs(),
                         ids=lambda p: f"{p[0]}-vs-{p[1]}")
def test_matrix_program_run(pair):
    prog = StencilProgram((Sweep("heat", diffusion(2, 1)),), name="p")
    x = _rand((20, 132))
    outs = [ops.stencil_program_run(x, prog, 3, bx=128, bt=1,
                                    backend=b) for b in pair]
    _agree(outs[0], outs[1], pair)


@pytest.mark.parametrize("pair", ops.backend_pairs(),
                         ids=lambda p: f"{p[0]}-vs-{p[1]}")
def test_matrix_outofcore_run(pair):
    """Out-of-core routing under a forced budget must agree with the
    same problem run in-core on the oracle: the acceptance matrix's
    third row. (The reference backend never routes out-of-core — it
    already lives on the host — so it runs in-core and the comparison
    is exactly the documented tolerance.)"""
    spec = diffusion(2, 1)
    x = _rand((64, 132))
    oracle, other = pair
    want = ops.stencil_run(x, spec, 2, bx=128, bt=1, backend=oracle,
                           hbm_budget=40_000)     # forces tiling
    got = ops.stencil_run(x, spec, 2, bx=128, bt=1, backend=other,
                          hbm_budget=40_000)
    _agree(want, got, pair)


# --------------------------------------------------------------------------
# GPU backend: validation gates (testable with zero GPUs — every gate
# fires before any lowering).
# --------------------------------------------------------------------------

def test_gpu_variants_matrix():
    assert engine.variants_for(2, "gpu") == ("multioperand",)
    assert engine.variants_for(3, "gpu") == ()
    # default (TPU) menu is unchanged
    assert "revolving" in engine.variants_for(2)
    assert engine.variants_for(3)


def test_gpu_3d_raises_not_implemented():
    with pytest.raises(NotImplementedError,
                       match="sequential-grid|persistent scratch"):
        engine.stencil_call(jnp.zeros((8, 8, 128), jnp.float32),
                            diffusion(3, 1), bx=128, bt=1,
                            backend="gpu")


def test_gpu_revolving_variant_rejected():
    with pytest.raises(ValueError, match="not available on the 'gpu'"):
        engine.stencil_call(jnp.zeros((16, 128), jnp.float32),
                            diffusion(2, 1), bx=128, bt=1,
                            variant="revolving", backend="gpu")


@pytest.mark.skipif(compat.platform() == "gpu",
                    reason="needs a non-GPU host")
def test_gpu_on_non_gpu_host_raises():
    with pytest.raises(RuntimeError, match="GPU host platform"):
        engine.stencil_call(jnp.zeros((16, 128), jnp.float32),
                            diffusion(2, 1), bx=128, bt=1,
                            variant="multioperand", backend="gpu")


def test_engine_rejects_unknown_backend():
    with pytest.raises(ValueError, match="unknown engine backend"):
        engine.stencil_call(jnp.zeros((16, 128), jnp.float32),
                            diffusion(2, 1), bx=128, bt=1,
                            backend="reference")


def test_compiler_params_for_selects_per_backend():
    assert compat.compiler_params_for("pallas", n_grid=2) is not None
    params = compat.compiler_params_for("pallas", 1, 64 * 2 ** 20)
    assert params.vmem_limit_bytes == 64 * 2 ** 20
    if not compat.has_gpu_pallas():
        with pytest.raises(ImportError):
            compat.gpu_compiler_params()


# --------------------------------------------------------------------------
# v8 autotune cache: per-backend device specs + pipeline mode join the key
# --------------------------------------------------------------------------

def _devices_of_kind(monkeypatch, kind):
    monkeypatch.setattr(pm.jax, "devices",
                        lambda *a: [types.SimpleNamespace(device_kind=kind)])


def test_device_spec_registry(monkeypatch):
    # pallas is looked up by the chip's device kind, not assumed
    _devices_of_kind(monkeypatch, "TPU v5 lite")
    assert pm.device_spec_for("pallas") is pm.V5E
    assert pm.device_spec_for("interpret") is pm.CPU_HOST
    assert pm.device_spec_for("reference") is pm.CPU_HOST
    assert pm.device_spec_for("gpu") is pm.GPU_GENERIC
    with pytest.raises(ValueError, match="anything-else"):
        pm.device_spec_for("anything-else")
    # the CPU host keeps the V5E HBM default so out-of-core routing
    # thresholds stay one number everywhere (outofcore.route_decision)
    assert pm.CPU_HOST.hbm_bytes == pm.V5E.hbm_bytes
    assert pm.CPU_HOST.vmem_bytes == pm.V5E.vmem_bytes


def test_device_spec_for_rejects_unknown_device_kind(monkeypatch):
    # this host's own device (a CPU) is no TPU
    with pytest.raises(ValueError, match="no device spec"):
        pm.device_spec_for("pallas")
    _devices_of_kind(monkeypatch, "TPU v9 imaginary")
    with pytest.raises(ValueError, match="TPU v9 imaginary"):
        pm.device_spec_for("pallas")


def test_vmem_budget_is_capacity_less_headroom():
    assert pm.V5E.vmem_capacity == 128 * 2 ** 20
    assert pm.V5E.vmem_bytes == pm.V5E.vmem_capacity - pm.VMEM_HEADROOM


def test_tpu_compiler_params_rejects_unknown_keyword():
    with pytest.raises(TypeError):
        compat.tpu_compiler_params(vmem_limit_byte=2 ** 20)
    assert compat.tpu_compiler_params(
        vmem_limit_bytes=2 ** 20).vmem_limit_bytes == 2 ** 20


def test_cache_version_is_9():
    # v9: out-of-core x multi-device plans exist and the routing
    # predicate charges ghost bytes per shard — v8 sharded entries
    # were tuned for a raise, not a runner, and must drop.
    assert autotune._CACHE_VERSION == 9


def test_backend_joins_cache_key_via_device_spec():
    spec = diffusion(2, 1)
    k_int = autotune._key(spec, (64, 256), "float32", "interpret",
                          pm.CPU_HOST.vmem_bytes, pm.CPU_HOST.name)
    k_tpu = autotune._key(spec, (64, 256), "float32", "pallas",
                          pm.V5E.vmem_bytes, pm.V5E.name)
    k_gpu = autotune._key(spec, (64, 256), "float32", "gpu",
                          pm.GPU_GENERIC.vmem_bytes,
                          pm.GPU_GENERIC.name)
    assert len({k_int, k_tpu, k_gpu}) == 3
    assert "cpu-host" in k_int and "gpu-a100-class" in k_gpu


def test_pipeline_mode_joins_cache_key():
    """v8: host-loop vs in-kernel DMA winners must not share a slot —
    the persistent kernel has different optimal (bx, bt, tile)."""
    spec = diffusion(2, 1)
    k_host = autotune._key(spec, (64, 256), "float32", "interpret",
                           pm.CPU_HOST.vmem_bytes, pm.CPU_HOST.name)
    k_kern = autotune._key(spec, (64, 256), "float32", "interpret",
                           pm.CPU_HOST.vmem_bytes, pm.CPU_HOST.name,
                           pipeline="kernel")
    assert k_host != k_kern
    assert k_host.endswith("|plhost") and k_kern.endswith("|plkernel")


def test_plan_defaults_to_backend_device_spec(tmp_path, monkeypatch):
    """plan() with no explicit tpu= ranks against the resolved
    backend's device spec — visible through the persisted cache key."""
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE",
                       str(tmp_path / "c.json"))
    autotune._MEM.clear()
    tuned = autotune.plan((48, 260), diffusion(2, 1),
                          backend="interpret", measure=True)
    assert tuned.source == "measured"
    data = json.loads((tmp_path / "c.json").read_text())
    keys = [k for k in data if k != "version"]
    assert keys and all("cpu-host" in k for k in keys)


# --------------------------------------------------------------------------
# Corrupt-cache hardening (satellite: _load_cache must never crash)
# --------------------------------------------------------------------------

def test_corrupt_cache_garbage_bytes_retunes(tmp_path, monkeypatch,
                                             caplog):
    """Truncated/garbage cache bytes must log found-vs-expected (like
    the version-mismatch path) and retune — never crash."""
    path = tmp_path / "autotune.json"
    path.write_bytes(b'{"version": 7, "k": {"bx": 128, "bt"')  # truncated
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(path))
    autotune._MEM.clear()
    with caplog.at_level(logging.WARNING, logger="repro.autotune"):
        tuned = autotune.plan((48, 260), diffusion(2, 1),
                              backend="interpret", n_steps=4,
                              measure=True)
    assert "not valid JSON" in caplog.text
    assert f"version {autotune._CACHE_VERSION}" in caplog.text
    assert "--retune" in caplog.text
    # planning still succeeded, and the re-measured winner persisted
    # over the corpse with a clean stamp
    assert tuned.source == "measured"
    data = json.loads(path.read_text())
    assert data["version"] == autotune._CACHE_VERSION


@pytest.mark.parametrize("garbage", [b"\x00\xff\xfe garbage",
                                     b"[1, 2, 3]", b'"just a string"'])
def test_corrupt_cache_shapes_never_crash(tmp_path, monkeypatch,
                                          caplog, garbage):
    path = tmp_path / "autotune.json"
    path.write_bytes(garbage)
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(path))
    autotune._MEM.clear()
    with caplog.at_level(logging.WARNING, logger="repro.autotune"):
        assert autotune._load_cache() == {}
    assert "autotune cache" in caplog.text


def test_malformed_entries_dropped_intact_ones_survive(tmp_path,
                                                       monkeypatch,
                                                       caplog):
    path = tmp_path / "autotune.json"
    good = {"bx": 128, "bt": 2, "variant": "revolving",
            "source": "measured"}
    path.write_text(json.dumps({"version": autotune._CACHE_VERSION,
                                "good|key": good,
                                "bad1": "not-a-dict",
                                "bad2": {"bx": 128}}))
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(path))
    autotune._MEM.clear()
    with caplog.at_level(logging.WARNING, logger="repro.autotune"):
        data = autotune._load_cache()
    assert data["good|key"] == good
    assert "bad1" not in data and "bad2" not in data
    assert "malformed" in caplog.text


# --------------------------------------------------------------------------
# Out-of-core x multi-device now COMPOSES (the v8 unified
# NotImplementedError is gone): every former raise path routes
# through the composed per-device streaming runner instead.
# --------------------------------------------------------------------------

def test_ooc_sharding_composes_no_raise_anywhere():
    """The three former raise sites (autotune.plan, ops.stencil_run,
    ops.stencil_program_run) all plan/route instead of raising."""
    spec = diffusion(2, 1)
    # autotune.plan: returns a real out-of-core plan for nd > 1
    tuned = autotune.plan((4096, 4096), spec, backend="interpret",
                          n_devices=2, hbm_budget=1_000_000,
                          use_cache=False)
    assert tuned.bx >= 128 and tuned.bt >= 1
    # ops entry points: both complete and stay exact (single forced
    # device here — the forced-4-device matrix lives in
    # tests/test_outofcore_sharded.py)
    x = jnp.asarray(np.random.default_rng(3).standard_normal(
        (96, 140)).astype(np.float32))
    want = np.asarray(ops.stencil_run(x, spec, 2, bx=128, bt=1,
                                      backend="interpret"))
    got = ops.stencil_run(x, spec, 2, backend="interpret", n_devices=1,
                          hbm_budget=100_000, bx=128, bt=1)
    np.testing.assert_array_equal(np.asarray(got), want)
    prog = StencilProgram((Sweep("heat", spec),), name="p")
    got_p = ops.stencil_program_run(x, prog, 2, bx=128, bt=1,
                                    backend="interpret", n_devices=1,
                                    hbm_budget=100_000)
    np.testing.assert_array_equal(np.asarray(got_p), want)


def test_no_sharded_outofcore_error_symbol():
    """The dead unified-error helper is gone from the public surface."""
    import repro.outofcore as ooc
    from repro.outofcore import runner
    assert not hasattr(ooc, "sharded_outofcore_error")
    assert not hasattr(runner, "sharded_outofcore_error")
    assert "sharded_outofcore_error" not in ooc.__all__


# --------------------------------------------------------------------------
# Dispatch-count accounting (satellite): nested program runs and the
# out-of-core route
# --------------------------------------------------------------------------

def test_dispatch_count_nested_program_runs():
    prog = StencilProgram(
        (Sweep("ha", diffusion(2, 1), field="u"),
         Sweep("hb", diffusion(2, 2, boundary="clamp"), field="u")),
        name="two")
    fields = {"u": _rand((16, 132))}
    ops.reset_dispatch_count()
    assert ops.dispatch_count() == 0
    ops.stencil_program_run(dict(fields), prog, 2, bx=128, bt=1,
                            backend="interpret")
    first = ops.dispatch_count()
    # two-sweep program, groups alternate: one dispatch per group per
    # step (or fewer if the program fuses — either way > 0 and
    # deterministic)
    assert first > 0
    # a second, nested-style run ACCUMULATES (no hidden reset inside)
    ops.stencil_program_run(dict(fields), prog, 2, bx=128, bt=1,
                            backend="interpret")
    assert ops.dispatch_count() == 2 * first
    ops.reset_dispatch_count()
    assert ops.dispatch_count() == 0


def test_dispatch_count_outofcore_route():
    spec = diffusion(2, 1)
    x = _rand((64, 132))
    ops.reset_dispatch_count()
    ops.stencil_run(x, spec, 4, bx=128, bt=2, backend="interpret",
                    hbm_budget=40_000)      # forces the tiled route
    # out-of-core counts one dispatch per blocked sweep (ceil(4/2)),
    # NOT one per streamed tile — fused-vs-looped comparisons must
    # stay apples-to-apples (see kernels/ops.py accounting note)
    assert ops.dispatch_count() == 2
    # in-core run of the same schedule counts identically
    ops.reset_dispatch_count()
    ops.stencil_run(x, spec, 4, bx=128, bt=2, backend="interpret")
    assert ops.dispatch_count() == 2


# --------------------------------------------------------------------------
# Perf trajectory + regression gate
# --------------------------------------------------------------------------

def _fake_bench(tmp_path, us=100.0, gcells=1.0, dispatches=4):
    payload = {"generated_by": "benchmarks.solvers", "smoke": True,
               "rows": [{"name": "solver_x_fused", "us": us,
                         "derived": "d", "gcells_per_s": gcells,
                         "dispatches": dispatches}]}
    (tmp_path / "BENCH_solvers.json").write_text(json.dumps(payload))
    return payload


def test_trajectory_extract_and_kinds(tmp_path):
    from benchmarks import trajectory as tj
    _fake_bench(tmp_path)
    metrics = tj.collect(str(tmp_path))
    assert metrics["solvers/solver_x_fused/us_per_call"] == {
        "value": 100.0, "kind": "time"}
    assert metrics["solvers/solver_x_fused/gcells_per_s"]["kind"] == \
        "rate"
    assert metrics["solvers/solver_x_fused/dispatches"]["kind"] == \
        "count"


def test_trajectory_append_only_and_noise_band(tmp_path):
    from benchmarks import trajectory as tj
    t = {"version": tj.TRAJECTORY_VERSION, "entries": []}
    m1 = {"s/x/us_per_call": {"value": 100.0, "kind": "time"},
          "s/x/dispatches": {"value": 4, "kind": "count"}}
    tj.append(t, m1, {}, "pr7")
    assert len(t["entries"]) == 1
    # same label: one more sample, noise re-derives from the spread
    m2 = {"s/x/us_per_call": {"value": 120.0, "kind": "time"},
          "s/x/dispatches": {"value": 4, "kind": "count"}}
    tj.append(t, m2, {}, "pr7")
    assert len(t["entries"]) == 1
    slot = t["entries"][0]["metrics"]["s/x/us_per_call"]
    assert slot["samples"] == [100.0, 120.0]
    assert slot["value"] == 100.0          # time keeps the best
    assert slot["noise"] == pytest.approx(20.0 / 110.0)
    assert t["entries"][0]["metrics"]["s/x/dispatches"]["noise"] == 0.0
    # new label: append-only — a second entry, the first untouched
    tj.append(t, m1, {}, "pr8")
    assert [e["label"] for e in t["entries"]] == ["pr7", "pr8"]
    assert t["entries"][0]["metrics"]["s/x/us_per_call"][
        "samples"] == [100.0, 120.0]


def test_trajectory_fraction_kind_and_gate_rule():
    """Measured overlap fractions: absolute noise band, min as the
    representative (lower is better), absolute gate allowance."""
    import sys
    sys.path.insert(0, "tools")
    try:
        import perf_gate
    finally:
        sys.path.pop(0)
    from benchmarks import trajectory as tj

    payload = {"generated_by": "benchmarks.outofcore", "rows": [
        {"name": "outofcore_tile8",
         "measured_exposed_transfer_fraction": 0.2,
         "measured_exposed_transfer_fraction_serial": 0.5}]}
    metrics = tj.extract_metrics(payload)
    key = "outofcore/outofcore_tile8/measured_exposed_transfer_fraction"
    assert metrics[key] == {"value": 0.2, "kind": "fraction"}
    # the _serial twin is context, not a gated metric
    assert len(metrics) == 1

    t = {"version": tj.TRAJECTORY_VERSION, "entries": []}
    tj.append(t, metrics, {}, "pr8")
    tj.append(t, {key: {"value": 0.25, "kind": "fraction"}}, {}, "pr8")
    slot = t["entries"][0]["metrics"][key]
    assert slot["value"] == 0.2            # fraction keeps the min
    # absolute band: spread 0.05 is under the 0.1 floor
    assert slot["noise"] == pytest.approx(0.1)

    entry = t["entries"][-1]
    ok_fresh = {key: {"value": 0.35, "kind": "fraction"}}
    bad_fresh = {key: {"value": 0.75, "kind": "fraction"}}
    # allowed = 0.2 + 0.1 (noise) + 0.1 * 1.0 (margin) = 0.4
    failures, passes, _ = perf_gate.check(ok_fresh, entry, margin=1.0)
    assert passes and not failures
    failures, _, _ = perf_gate.check(bad_fresh, entry, margin=1.0)
    assert len(failures) == 1 and "fraction" in failures[0]


def test_perf_gate_passes_then_fails_on_degraded_fixture(tmp_path):
    """The acceptance demo: the gate passes on the records the
    trajectory was built from, and fails on a synthetically degraded
    copy (100x slower, +10 dispatches)."""
    import sys
    sys.path.insert(0, "tools")
    try:
        import perf_gate
    finally:
        sys.path.pop(0)
    from benchmarks import trajectory as tj

    bench = tmp_path / "bench"
    bench.mkdir()
    _fake_bench(bench)
    metrics = tj.collect(str(bench))
    t = {"version": tj.TRAJECTORY_VERSION, "entries": []}
    tj.append(t, metrics, {}, "pr7")

    fresh = tj.collect(str(bench))
    failures, passes, skipped = perf_gate.check(
        fresh, t["entries"][-1], margin=1.0)
    assert not failures and passes and not skipped

    bad = tmp_path / "bad"
    bad.mkdir()
    _fake_bench(bad, us=100.0 * 100, gcells=1.0 / 100,
                dispatches=4 + 10)
    degraded = tj.collect(str(bad))
    failures, _, _ = perf_gate.check(degraded, t["entries"][-1],
                                     margin=4.0)
    # every tracked metric regressed: time, rate AND the exact count
    assert len(failures) == 3
    assert any("count" in f for f in failures)


def test_perf_gate_skips_unregenerated_metrics(tmp_path):
    import sys
    sys.path.insert(0, "tools")
    try:
        import perf_gate
    finally:
        sys.path.pop(0)
    from benchmarks import trajectory as tj
    entry = {"label": "pr7", "metrics": {
        "a/x/us_per_call": {"value": 1.0, "kind": "time",
                            "noise": 0.1},
        "b/y/us_per_call": {"value": 1.0, "kind": "time",
                            "noise": 0.1}}}
    fresh = {"a/x/us_per_call": {"value": 1.0, "kind": "time"}}
    failures, passes, skipped = perf_gate.check(fresh, entry,
                                                margin=1.0)
    assert not failures and len(passes) == 1
    assert skipped == ["b/y/us_per_call"]


def test_committed_trajectory_is_valid_and_gateable():
    """The repo's own perf/trajectory.json must load, be non-empty,
    and carry the fields the gate needs."""
    from benchmarks import trajectory as tj
    t = tj.load_trajectory("perf/trajectory.json")
    assert t["entries"], "committed trajectory must hold >= 1 entry"
    last = t["entries"][-1]
    assert last["metrics"]
    for key, m in last["metrics"].items():
        assert m["kind"] in ("time", "rate", "count", "fraction"), key
        assert "value" in m and "noise" in m and m["samples"], key
    # headline summaries exist for the GCell/s-reporting suites
    assert any("best_gcells_per_s" in h
               for h in last["suites"].values())
