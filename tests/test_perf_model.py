"""Tests for the thesis's pipeline model (ch.3 closed forms) and the TPU
roofline adaptation (§5.4): algebraic properties the thesis derives.
"""
import numpy as np
import pytest
pytest.importorskip(
    "hypothesis",
    reason="dev-only dependency — pip install -r requirements-dev.txt")
from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core import perf_model as pm
from repro.core import pipeline_model as pl
from repro.core.blocking import BlockPlan
from repro.core.stencil import diffusion


# ---------------------------------------------------------------------------
# ch.3 pipeline model
# ---------------------------------------------------------------------------

def test_eq_3_1_and_3_2():
    p = pl.PipelineParams(P=100, L=1000, f_max=250e6)
    assert pl.t_cycle(p, 1) == 100 + 999
    assert pl.t_seconds(p, 1) == pytest.approx((100 + 999) / 250e6)


def test_ii_model_barriers_equal_stalls():
    """Thesis §3.1.1: N_b barriers act like N_d stalls (Eqs 3-3/3-4)."""
    assert pl.ii_ndrange(3) == pl.ii_single_work_item(3)


@settings(max_examples=30, deadline=None)
@given(n_p=st.integers(2, 64), L=st.integers(10 ** 6, 10 ** 8))
def test_speedup_approaches_np_with_bandwidth(n_p, L):
    """§3.1.2: with ample bandwidth, speedup ≈ N_p (for L >> N_p·P, the
    thesis's own caveat); with saturated bandwidth it is capped by the
    memory branch of Eq. 3-8."""
    p = pl.PipelineParams(P=200, L=L, f_max=200e6)
    ample = pl.speedup_from_parallelism(p, ii=1, n_p=n_p, n_m=4, bw=1e9)
    assert ample == pytest.approx(n_p, rel=0.05)
    starved = pl.speedup_from_parallelism(p, ii=1, n_p=n_p, n_m=4, bw=4.0)
    assert starved <= n_p * 1.01
    assert starved == pytest.approx(1.0, rel=0.1)  # BW-bound: no speedup


def test_runtime_ii_dominates():
    assert pl.ii_effective(1.0, 3.5) == 3.5
    assert pl.ii_runtime_data_parallel(8, 4, 16) == 2.0


# ---------------------------------------------------------------------------
# §5.4 roofline model
# ---------------------------------------------------------------------------

def test_temporal_blocking_cuts_memory_term():
    """Doubling bt halves sweeps -> halves HBM bytes (same n_steps)."""
    spec = diffusion(2, 1)
    g = (4096, 16384)
    t1 = pm.stencil_roofline(BlockPlan(spec, g, bx=1024, bt=1), 16)
    t4 = pm.stencil_roofline(BlockPlan(spec, g, bx=1024, bt=4), 16)
    assert t4.hbm_bytes == pytest.approx(t1.hbm_bytes / 4)
    # compute term grows only by the (small) redundancy factor
    assert t4.t_compute < t1.t_compute * 1.05 * 4


def test_optimal_bt_saturates():
    """Thesis law: perf rises with bt until redundant compute dominates
    (memory-bound -> compute-bound crossover)."""
    spec = diffusion(2, 1)
    g = (4096, 16384)
    perf = {}
    for bt in (1, 2, 4, 8, 16):
        plan = BlockPlan(spec, g, bx=256, bt=bt)
        perf[bt] = pm.predict_gcells_per_s(plan, 64)
    assert perf[4] > perf[1]           # blocking helps at first
    best = max(perf, key=perf.get)
    assert best >= 4
    # once compute-bound, more bt only adds redundancy
    t16 = pm.stencil_roofline(BlockPlan(spec, g, bx=256, bt=16), 64)
    assert t16.dominant == "compute"


def test_larger_bx_lowers_redundancy_at_high_bt():
    spec = diffusion(2, 4)
    g = (4096, 2 ** 16)
    small = BlockPlan(spec, g, bx=256, bt=8)
    large = BlockPlan(spec, g, bx=2048, bt=8)
    assert large.redundancy < small.redundancy


def test_select_config_prunes_to_top_k():
    spec = diffusion(2, 1)
    plans = pm.select_config(spec, (4096, 16384), n_steps=64, top_k=3)
    assert len(plans) == 3
    # returned plans are sorted by predicted time: the roofline plus
    # the modeled dispatch time, which the ranking charges every plan
    terms = [pm.stencil_roofline(p, 64) for p in plans]
    times = [t.t_predicted + t.t_dispatch for t in terms]
    assert times == sorted(times)


def test_roofline_terms_and_dominant():
    t = pm.RooflineTerms(t_compute=1.0, t_memory=2.0, t_collective=0.5,
                         flops=1, hbm_bytes=1, collective_bytes=1)
    assert t.dominant == "memory" and t.t_predicted == 2.0


def test_lm_roofline_and_model_flops():
    terms = pm.lm_roofline(1e12, 1e11, 1e9, chips=1)
    assert terms.t_compute == pytest.approx(1e12 / pm.V5E.peak_flops_bf16)
    assert pm.model_flops_train(1e9, 1e6) == 6e15
    assert pm.model_flops_decode(1e9, 1e6) == 2e15


def test_projection_device_is_faster():
    """§5.7.3 analog: the projected device lowers every roofline term."""
    spec = diffusion(3, 1)
    plan = BlockPlan(spec, (256, 512, 512), bx=256, bt=2)
    now = pm.stencil_roofline(plan, 32, tpu=pm.V5E)
    nxt = pm.stencil_roofline(plan, 32, tpu=pm.V5P_PROJECTION)
    assert nxt.t_compute < now.t_compute
    assert nxt.t_memory < now.t_memory
