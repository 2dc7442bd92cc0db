"""Shared runner for the multi-device subprocess cases.

A plain helper module (same pattern as ``_propcheck``) so both
``test_distributed.py`` and ``conftest.py`` can import it without relying on
``conftest`` being importable as a module (it is not under
``--import-mode=importlib``).
"""

import os
import subprocess
import sys

import pytest

_HERE = os.path.dirname(__file__)


def run_distributed_case(case: str, timeout: int = 480,
                         devices: int = 8) -> str:
    """Run one tests/distributed_cases.py case in a subprocess with
    ``devices`` fake CPU devices (the main pytest process stays on the
    1-device topology) and
    return its stdout; pytest.fail with the child's output on any failure —
    an import/compat break in the subprocess must read as itself, not as
    ``assert 1 == 0`` around a CompletedProcess repr."""
    env = dict(os.environ)
    env["PYTHONPATH"] = (os.path.join(_HERE, "..", "src") + os.pathsep
                         + env.get("PYTHONPATH", ""))
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    cmd = [sys.executable, os.path.join(_HERE, "distributed_cases.py"), case]
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=timeout, env=env)
    except subprocess.TimeoutExpired as e:
        pytest.fail(
            f"case {case!r} timed out after {timeout}s\n"
            f"--- captured stdout ---\n{e.stdout or ''}\n"
            f"--- captured stderr ---\n{e.stderr or ''}",
            pytrace=False)
    if proc.returncode != 0:
        pytest.fail(
            f"case {case!r} exited {proc.returncode}\n"
            f"--- child stdout ---\n{proc.stdout}\n"
            f"--- child stderr ---\n{proc.stderr}",
            pytrace=False)
    return proc.stdout
