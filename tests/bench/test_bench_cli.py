"""The benchmark's command off the chip: it exits non-zero and prints no
result line."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]


@pytest.mark.parametrize("trace", ["0", "1"])
def test_command_on_cpu_exits_nonzero_without_a_result(trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "ssfn_mnist_m20.train_gossip", "--seed", str(2 ** 31 + 5),
         "--seconds", "1", "--trace", trace],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0
    assert "no accelerator" in proc.stderr
    for line in proc.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)


def test_unknown_workload_exits_nonzero():
    proc = subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload", "nope",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode != 0 and proc.stdout == ""
    assert "no workload 'nope'" in proc.stderr
