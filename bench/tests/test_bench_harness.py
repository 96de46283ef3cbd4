"""The harness refuses what it must not measure, and finds cells by name."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import run

ROOT = Path(__file__).resolve().parents[2]
MANIFEST = json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("cell", [w["name"] for w in MANIFEST["workloads"]])
def test_no_tpu_is_refused(cell):
    with pytest.raises(run.Refused, match="no TPU"):
        run.measure(run.Cell.from_manifest(MANIFEST, cell), 1, 1.0, False)


@pytest.mark.parametrize("var", run.FORBIDDEN_ENV)
def test_kernel_selectors_are_refused(monkeypatch, var):
    monkeypatch.setenv(var, "xla")
    cell = run.Cell.from_manifest(MANIFEST, MANIFEST["workloads"][0]["name"])
    with pytest.raises(run.Refused, match=var):
        run.measure(cell, 1, 1.0, False, require_tpu=False)


def test_unknown_workload_is_refused():
    with pytest.raises(run.Refused, match="no workload"):
        run.Cell.from_manifest(MANIFEST, "no-such-cell")


def _run_cli(root: Path, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, *MANIFEST["command"][1:], "--workload",
         MANIFEST["workloads"][0]["name"], "--seed", str(2**31 + 7),
         "--seconds", "1", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)


def test_cli_without_a_tpu_prints_no_result():
    proc = _run_cli(ROOT)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


def test_bare_checkout_is_refused(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's files holds
    no program to measure."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in MANIFEST["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run_cli(tmp_path)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "src/repro" in proc.stderr


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 5, 2**40 + 3])
def test_program_seed_is_stable_and_fits_a_key(seed):
    s = run.program_seed(seed)
    assert s == run.program_seed(seed) and 0 <= s < 2**30
