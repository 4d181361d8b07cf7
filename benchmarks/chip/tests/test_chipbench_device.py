"""No chip, too few chips, or a chip without published peaks: an error,
never a fallback; and the command prints no result then."""
import os
import shutil
import subprocess
import sys

import chipbench_testkit as kit
import pytest

from chipbench import device


def test_unknown_device_kind_is_an_error():
    with pytest.raises(device.DeviceError, match="no published peaks"):
        device.peaks_for("TPU v99 imaginary")
    assert device.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_missing_tpu_is_an_error():
    # the test suite runs JAX on the CPU
    with pytest.raises(device.DeviceError, match="TPU"):
        device.require_tpu(1)


def _run(cwd, env_extra=None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(env_extra or {})}
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "qwen05b-dense.longgen", "--seed", "5000000000", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=300)


def test_command_without_tpu_exits_nonzero_and_prints_nothing():
    p = _run(kit.REPO)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_command_without_the_program_exits_nonzero(tmp_path):
    # a directory that holds only BENCHMARK.json and the benchmark
    shutil.copy(os.path.join(kit.REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(kit.CHIP, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
