"""Shared test utilities.

NOTE: no XLA_FLAGS here by design — tests see the real 1-device CPU; tests
that need multiple host devices spawn a subprocess (see run_subprocess).
"""

import os
import subprocess
import sys
import textwrap

import pytest

# fixtures/ holds broken-on-purpose trees for the analysis suite — some
# files deliberately do not parse, and fixture test_kernels.py stubs would
# basename-collide with the real ones
collect_ignore = ["fixtures"]


def run_subprocess(code: str, devices: int = 8) -> str:
    """Run `code` in a fresh python with N fake host devices; assert rc==0."""
    prelude = (
        "import os\n"
        f'os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={devices}"\n'
        "import sys\n"
        f"sys.path.insert(0, {os.path.join(os.path.dirname(__file__), '..', 'src')!r})\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", prelude + textwrap.dedent(code)],
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, (
        f"subprocess failed\nstdout:\n{proc.stdout}\nstderr:\n{proc.stderr}")
    return proc.stdout


@pytest.fixture
def subproc():
    return run_subprocess
