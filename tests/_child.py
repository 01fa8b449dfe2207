"""Run a Python snippet in a child process with forced host devices.

The pytest process keeps the single real CPU device, so anything that
needs a multi-device mesh runs in a child that sets
``--xla_force_host_platform_device_count`` before JAX is imported.  The
child imports the library from ``src``.

Each call site passes a ``timeout`` of about twice its child's measured
time: a hung child fails its test within minutes, naming the test and
showing what the child printed so far.
"""

from __future__ import annotations

import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


def _text(stream) -> str:
    if isinstance(stream, bytes):
        return stream.decode(errors="replace")
    return stream or ""


def run_child(code: str, devices: int = 8, *, timeout: float) -> str:
    """Run dedented ``code`` with ``devices`` host devices; return stdout.

    Fails the calling test if the child exits non-zero or outlives
    ``timeout`` seconds."""
    prog = (
        "import os\n"
        "os.environ['XLA_FLAGS'] = "
        f"'--xla_force_host_platform_device_count={devices}'\n"
        + textwrap.dedent(code)
    )
    env = dict(os.environ, PYTHONPATH=SRC)
    caller = os.environ.get("PYTEST_CURRENT_TEST", "run_child").split()[0]
    try:
        out = subprocess.run([sys.executable, "-c", prog],
                             capture_output=True, text=True,
                             timeout=timeout, env=env)
    except subprocess.TimeoutExpired as e:
        pytest.fail(f"{caller}: child still running after {timeout} s\n"
                    f"--- stdout ---\n{_text(e.stdout)}\n"
                    f"--- stderr ---\n{_text(e.stderr)}", pytrace=False)
    assert out.returncode == 0, (
        f"{caller}: child failed:\n{out.stdout}\n{out.stderr}")
    return out.stdout


def assert_mesh_ok(out: str, tag: str, data: int, model: int) -> None:
    """Assert the ``<tag>_OK data model`` line of a child that checks
    several meshes and prints one ``_OK`` or ``_FAIL`` line per mesh."""
    assert f"{tag}_OK {data} {model}" in out.splitlines(), (
        f"mesh ({data}, {model}) did not pass; child output:\n{out}")
