"""CPU rehearsal of each cell: control flow and the contract's line;
the measurement path refuses to run without a TPU."""

import os
import shutil
import subprocess
import sys

import pytest

from perfbench.tests.helpers import rehearse

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELLS = ["palfa.search", "gbncc.dedisp"]


@pytest.mark.parametrize("workload", CELLS)
def test_rehearsal_prints_a_correct_line_without_metrics(workload):
    rc, lines = rehearse(workload)
    assert rc == 0
    res = lines[-1]
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        assert key in res
    assert list(res)[-1] == "checks"
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    assert res["metrics"] == {}          # a CPU run prints no metric
    assert res["device"]["platform"] == "cpu"


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", "palfa.search",
         "--seed", "3000000021", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_measurement_path_refuses_without_tpu():
    p = _run(ROOT)
    assert p.returncode != 0
    assert not [l for l in p.stdout.splitlines() if l.startswith("{")]


def test_bare_directory_refuses(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "perfbench"), tmp_path / "perfbench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    p = _run(str(tmp_path))
    assert p.returncode != 0
    assert not [l for l in p.stdout.splitlines() if l.startswith("{")]
