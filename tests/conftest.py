"""Test fixtures.

Parity: ``python/ray/tests/conftest.py`` (``ray_start_regular:419``,
``ray_start_cluster:500``). TPU tests run on a virtual 8-device CPU mesh
(``XLA_FLAGS=--xla_force_host_platform_device_count=8``), the JAX analogue of
the reference's fake-GPU configs (SURVEY.md §4). The environment may name
another platform in ``JAX_PLATFORMS`` (the machine with the chip sets
``tpu,cpu``), so the env is force-set here: this process and every worker it
spawns inherit it before jax is imported.
"""

import os

# Force-set (not setdefault): see above.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import pytest  # noqa: E402


@pytest.fixture
def ray_start_regular():
    import ray_tpu

    rt = ray_tpu.init(num_cpus=4, ignore_reinit_error=True)
    yield rt
    ray_tpu.shutdown()


@pytest.fixture
def ray_start_cluster():
    from ray_tpu.cluster_utils import Cluster

    cluster = Cluster(initialize_head=True, head_node_args={"num_cpus": 2})
    yield cluster
    cluster.shutdown()


@pytest.fixture(scope="session")
def cpu_mesh_devices():
    import jax

    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual CPU devices, got {len(devs)}"
    return devs


def pytest_sessionfinish(session, exitstatus):
    """Post-suite hygiene: a test that leaks a BLOCKED non-daemon thread
    (e.g. a pool worker parked in an unbounded get after its cluster died)
    would wedge interpreter shutdown forever. Print the evidence, then arm
    a watchdog that bounds the exit at 90s — the suite's verdict is already
    decided at this point."""
    import faulthandler
    import os
    import sys
    import threading
    import time

    stragglers = [
        t
        for t in threading.enumerate()
        if t is not threading.main_thread() and not t.daemon and t.is_alive()
    ]
    if stragglers:
        sys.stderr.write(
            f"\n[conftest] {len(stragglers)} non-daemon thread(s) still "
            f"alive at exit: {[t.name for t in stragglers]}\n"
        )
        faulthandler.dump_traceback(file=sys.stderr)

    status = int(exitstatus)

    def _watchdog():
        time.sleep(90)
        sys.stderr.write("[conftest] exit watchdog fired: hard-exiting\n")
        sys.stderr.flush()
        os._exit(status)

    threading.Thread(target=_watchdog, name="exit-watchdog", daemon=True).start()
