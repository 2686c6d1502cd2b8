"""Without an accelerator the benchmark reports nothing."""
import os
import subprocess
import sys

import pytest

from bench import harness
from conftest import ROOT


def test_run_refuses_a_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "engine.train.p32", "--seed", "1", "--seconds", "1",
                        "--trace", "1"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no accelerator" in p.stderr


def test_accelerator_refuses_cpu_and_unknown_kinds():
    with pytest.raises(harness.NoChip):
        harness.accelerator(1)
