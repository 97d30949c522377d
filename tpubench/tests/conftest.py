import json
import os
import subprocess
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(REPO, "src")]

#: a run with a fault planted underneath the timed path (tests only)
_RUNNER = ("import sys; sys.path.insert(0, sys.argv[1]); import run; "
           "run.run(sys.argv[3:], fault=sys.argv[2])")


class Result:
    def __init__(self, proc):
        self.rc = proc.returncode
        self.stdout = proc.stdout
        self.stderr = proc.stderr
        lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
        self.last = None
        if lines:
            try:
                self.last = json.loads(lines[-1])
            except ValueError:
                pass


@pytest.fixture
def bench(tmp_path):
    """Run the benchmark in a child process on the CPU:
    ``bench(args, fault=None, checkout=REPO)``."""

    def go(args, fault=None, checkout=REPO, timeout=300):
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
        if fault is None:
            cmd = [sys.executable, os.path.join(checkout, "tpubench", "run.py")]
        else:
            cmd = [sys.executable, "-c", _RUNNER,
                   os.path.join(checkout, "tpubench"), fault]
        return Result(subprocess.run(cmd + list(args), cwd=checkout, env=env,
                                     timeout=timeout, capture_output=True,
                                     text=True))

    return go
