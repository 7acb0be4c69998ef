"""``correct``: sound runs pass, and the control and every fault a cell
can have come out false, through the whole harness (chip check
skipped) at tiny sizes on the CPU."""
import time

import pytest

from bench import harness
from bench.tests import faults
from bench.tests.tiny import tiny_root

SOLVES = ["hotspot2d.solve", "diffusion3d_r4.solve"]
SERVE = "hotspot2d.ensemble"
SEED = 2 ** 31 + 99


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("bench"))


def _run(root, cell, monkeypatch=None, fault=None):
    if fault is not None:
        from repro.kernels import ops
        config = harness.find_cell(root, cell).config
        monkeypatch.setattr(ops, "stencil_run",
                            fault(config, ops.stencil_run))
    result, _ = harness.run_cell(root, cell, SEED, 0.2, False,
                                 time.perf_counter())
    return result


@pytest.mark.parametrize("cell", SOLVES + [SERVE])
def test_sound_run_is_correct(root, cell):
    r = _run(root, cell)
    assert r["correct"], r["checks"]
    assert r["checks"]["rel_err"]["value"] <= \
        r["checks"]["rel_err"]["limit"]
    assert list(r)[-1] == "checks"


@pytest.mark.parametrize("cell", SOLVES + [SERVE])
@pytest.mark.parametrize("fault", [faults.control, faults.unchanged,
                                   faults.altered],
                         ids=["control", "unchanged", "altered"])
def test_control_and_faults_are_not_correct(root, cell, fault,
                                            monkeypatch):
    r = _run(root, cell, monkeypatch, fault)
    assert not r["correct"], r["checks"]
    assert r["checks"]["rel_err"]["value"] > \
        r["checks"]["rel_err"]["limit"]


def test_half_batch_left_out_is_not_correct(root, monkeypatch):
    """Arrivals faster than the interpreter serves them fill batches of
    two, and every request is compared."""
    import json
    mix = root / "bench" / "traffic" / "ensemble.json"
    dense = dict(json.loads(mix.read_text()), rate_per_s=2000, sample=64)
    monkeypatch.setattr(harness, "load_json", lambda p: dense if
                        p == mix else json.loads(p.read_text()))
    r = _run(root, SERVE, monkeypatch, faults.half_batch)
    assert not r["correct"], r["checks"]


def test_missing_answers_are_not_correct(root, monkeypatch):
    """A request that never gets its answer fails the run."""
    from repro.serving import StencilService
    real = StencilService.flush

    def lose_odd(self):
        return [c for c in real(self) if c.uid < 0 or c.uid % 2 == 0]
    monkeypatch.setattr(StencilService, "flush", lose_odd)
    r = _run(root, SERVE)
    assert not r["correct"]
    assert r["checks"]["missing"]["value"] > 0
    assert r["failed"] > 0
