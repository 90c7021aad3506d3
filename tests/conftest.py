"""Shared fixtures of the tier-1 suite."""
import os
import re
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture
def rerun_on_cpu_devices():
    """``rerun(nodeid, n)``: run one test in a fresh process whose JAX
    sees ``n`` CPU devices
    (``XLA_FLAGS=--xla_force_host_platform_device_count=n``), and fail
    with its output unless it passed. A test that needs several devices
    calls it when its own process has fewer, so that it runs, and not
    skips, under a plain ``pytest``."""
    def rerun(nodeid: str, n: int) -> None:
        env = dict(os.environ, JAX_PLATFORMS="cpu")
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " --xla_force_host"
                            f"_platform_device_count={n}").strip()
        env["PYTHONPATH"] = os.pathsep.join(
            [str(ROOT / "src")] + [p for p in [env.get("PYTHONPATH")] if p])
        p = subprocess.run(
            [sys.executable, "-m", "pytest", "-q", "-p", "no:cacheprovider",
             "-p", "no:xdist", nodeid],
            cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
        assert p.returncode == 0 and re.search(r"\b1 passed", p.stdout), (
            p.stdout[-4000:] + p.stderr[-4000:])
    return rerun
