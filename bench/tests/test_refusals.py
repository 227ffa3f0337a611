"""The harness refuses what it cannot measure."""
import os
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def test_unknown_device_kind_is_refused():
    import run
    with pytest.raises(SystemExit, match="no peaks for device kind"):
        run.device_peaks("TPU v9 imaginary")
    assert run.device_peaks("TPU v5 lite")["bf16_flops"] == 197e12


def test_command_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(BENCH, "run.py"),
                        "--workload", "qwen3-0.6b.ft-wave", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr
