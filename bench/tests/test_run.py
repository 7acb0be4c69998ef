"""``bench/run.py`` refuses to run where it cannot measure a chip."""
import json
import os
import shutil
import subprocess
import sys

from bench.tests.tiny import BENCH, ROOT

ARGS = ["--workload", "hotspot2d.solve", "--seed", "3000000000",
        "--seconds", "1", "--trace", "0"]


def _run(root, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, str(root / "bench" / "run.py"),
                           *ARGS], cwd=root, env=env, capture_output=True,
                          text=True, timeout=120)


def _no_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        if isinstance(obj, dict) and "correct" in obj:
            return False
    return True


def test_no_tpu_exits_nonzero_without_a_result():
    p = _run(ROOT)
    assert p.returncode == 1, p.stderr
    assert _no_result(p.stdout)
    assert "found no TPU" in p.stderr
    assert "platform cpu" in p.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    p = _run(tmp_path)
    assert p.returncode == 2, p.stderr
    assert _no_result(p.stdout)
