"""The benchmark refuses to measure without a TPU."""
import json
import os
import subprocess
import sys

import small  # noqa: F401
import harness


def test_run_exits_nonzero_on_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    name = harness.spec()["workloads"][0]["name"]
    p = subprocess.run([sys.executable, str(harness.BENCH / "run.py"),
                        "--workload", name, "--seed", "1", "--seconds", "1",
                        "--trace", "0"], env=env, capture_output=True,
                       text=True, timeout=300, cwd=harness.CHECKOUT)
    assert p.returncode != 0
    assert "no result" in p.stderr
    for line in p.stdout.splitlines():
        try:
            json.loads(line)
        except ValueError:
            continue
        raise AssertionError(f"printed a result: {line}")
