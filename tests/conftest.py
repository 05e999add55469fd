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


# A test's own time limit. On an idle machine the longest test of tier 1 takes
# about 340 s (the learner group's restart test sits out a 300 s update
# timeout by design); every wait under it is bounded, and yet a run has been
# seen to stop for good in that test's last seconds, and once just after it,
# with the five other workers long done: the whole run then sat until its
# caller's limit cut it, and counted nothing past the last full line of dots.
# So a test that is still going after TEST_LIMIT_S fails where it stands, with
# every thread's stack on the real stderr, and the run goes on to its end.
# Where the main thread cannot be interrupted (blocked inside native code) the
# stacks are all there is: the process is left alone. Where it can be, but sits
# in a finalizer under the collector, Python prints and swallows whatever the
# alarm raises there, and the next piece of garbage blocks again: at the second
# cut of such a test its xdist worker ends itself. Under ``--dist loadfile``
# xdist alone would hand the file back with the test the worker died in still
# to run, to the next worker and the next, each for the whole of the limit,
# until it has replaced four workers a process and gives the run up (seen: five
# failures of the one test, and no test after it run);
# ``pytest_handlecrashitem`` below takes that test out of what is handed on, so
# it fails once, the next worker runs the rest of its file, and the hang costs
# TEST_LIMIT_S + TEST_CUT_AGAIN_S and the new worker's collection.
TEST_LIMIT_S = 480
# what is left of a test that was cut (its ``finally``, its fixtures' teardown)
# may be stuck on the same thing: it is cut again, this often
TEST_CUT_AGAIN_S = 60
_real_stderr_fd = 2


def pytest_configure(config):
    # global capture is suspended here, so this is the terminal's stderr and
    # not the file a test's output is captured in
    global _real_stderr_fd
    import os

    _real_stderr_fd = os.dup(2)


@pytest.hookimpl(optionalhook=True)
def pytest_handlecrashitem(crashitem, report, sched):
    """A test whose worker died is reported failed (xdist does that) and is
    not run again: the load-scope scheduler puts the worker's file back in its
    queue with the test it died in among those still to run."""
    queue = getattr(sched, "workqueue", {})
    for scope, tests in list(queue.items()):
        if crashitem in tests:
            tests[crashitem] = True
            if all(tests.values()):
                del queue[scope]


def _in_a_finalizer(frame):
    while frame is not None and frame.f_code.co_name != "__del__":
        frame = frame.f_back
    return frame is not None


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_protocol(item, nextitem):
    """Setup, call and teardown of one test, under one limit."""
    import faulthandler
    import os
    import signal
    import time

    started = time.monotonic()
    cuts = 0

    def _out_of_time(signum, frame):
        nonlocal cuts
        cuts += 1
        faulthandler.cancel_dump_traceback_later()
        signal.alarm(TEST_CUT_AGAIN_S)
        os.write(
            _real_stderr_fd,
            f"\n[conftest] {item.nodeid} is still running after "
            f"{time.monotonic() - started:.0f}s; every thread's stack:\n".encode(),
        )
        faulthandler.dump_traceback(file=_real_stderr_fd, all_threads=True)
        if cuts > 1 and _in_a_finalizer(frame) and hasattr(item.config, "workerinput"):
            os.write(
                _real_stderr_fd,
                f"[conftest] {item.nodeid} sits in a finalizer, where nothing "
                "raised can travel: its worker ends here, the test counts as "
                "failed, and the next worker runs the rest of its file\n".encode(),
            )
            os._exit(1)
        pytest.fail(
            f"{item.nodeid} ran into the {TEST_LIMIT_S}s limit that "
            "tests/conftest.py gives every test",
            pytrace=True,
        )

    # pytest's own faulthandler_timeout uses the same one timer
    dump_later = not item.config.getini("faulthandler_timeout")
    try:
        previous = signal.signal(signal.SIGALRM, _out_of_time)
    except ValueError:  # not the main thread: the stacks alone
        previous = None
    else:
        signal.alarm(TEST_LIMIT_S)
    if dump_later:
        faulthandler.dump_traceback_later(
            TEST_LIMIT_S + 30, file=_real_stderr_fd
        )
    try:
        yield
    finally:
        if dump_later:
            faulthandler.cancel_dump_traceback_later()
        if previous is not None:
            signal.alarm(0)
            signal.signal(signal.SIGALRM, previous)
