"""Without a TPU the benchmark exits non-zero and prints no result."""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.mark.parametrize("script,args", [
    ("benchmark/run.py", ["--workload", "sf1.q6", "--seed", "1",
                          "--seconds", "1", "--trace", "0"]),
    ("benchmark/prove.py", ["--workload", "sf1.q6", "--seeds", "1"]),
])
def test_exits_non_zero_and_prints_nothing(script, args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, script] + args, cwd=REPO, env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr
