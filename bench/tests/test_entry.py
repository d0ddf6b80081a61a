"""The command fails, with no result line, where it must not measure."""

import os
import shutil
import subprocess
import sys

import pytest

import spec

WORKLOAD = "qwen3-0.6b.1node.s2048"


def launch(root):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", WORKLOAD, "--seed",
         "3000000000", "--seconds", "1", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_no_result():
    p = launch(spec.ROOT)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_benchmark_alone_no_result(tmp_path):
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(spec.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = launch(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        spec.peaks_for("TPU v9 imaginary")
    assert spec.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
