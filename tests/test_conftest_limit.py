"""The time limit ``tests/conftest.py`` gives every test: a run of pytest in a
directory of its own, under a copy of that conftest with the limit turned down
to four seconds, over tests that never end. The run has one xdist worker, as
the driver's has six: a test that sits in a finalizer ends its worker."""

import os
import re
import shutil
import subprocess
import sys
import textwrap

import pytest

CONFTEST = os.path.join(os.path.dirname(__file__), "conftest.py")

PROBE = """
import gc
import threading
import time

import pytest

import conftest

conftest.TEST_LIMIT_S = 4
conftest.TEST_CUT_AGAIN_S = 1


def test_before():
    pass


def test_sleeps_for_good():
    time.sleep(600)


def test_waits_for_a_lock_nobody_releases():
    lock = threading.Lock()
    lock.acquire()
    lock.acquire()


@pytest.fixture
def teardown_sleeps_for_good():
    yield
    time.sleep(600)


def test_body_and_finally_and_teardown_all_block(teardown_sleeps_for_good):
    try:
        time.sleep(600)
    finally:
        time.sleep(600)


class BlocksWhenCollected:
    def __init__(self, lock):
        self.lock, self.me = lock, self

    def __del__(self):
        self.lock.acquire()


def test_blocks_in_a_finalizer_under_the_collector():
    # what the alarm raises in a finalizer is printed and swallowed, and the
    # next object's finalizer blocks again
    lock = threading.Lock()
    lock.acquire()
    for _ in range(50):
        BlocksWhenCollected(lock)
    gc.collect()


def test_after():
    # under the limit, and past the alarm the test before left armed if it did
    time.sleep(1.5)
"""


@pytest.fixture(scope="module")
def probe_run(tmp_path_factory):
    root = tmp_path_factory.mktemp("limit_probe")
    tests = root / "tests"
    tests.mkdir()
    shutil.copy(CONFTEST, tests / "conftest.py")
    (tests / "test_probe.py").write_text(textwrap.dedent(PROBE))
    return subprocess.run(
        [sys.executable, "-m", "pytest", "tests", "-v", "-p", "no:cacheprovider",
         "-p", "no:randomly", "-n", "1", "--dist", "loadfile"],
        cwd=root, capture_output=True, text=True, timeout=240,
    )


def test_the_run_reaches_its_end_and_counts_every_test(probe_run):
    assert probe_run.returncode == 1, probe_run.stdout + probe_run.stderr
    assert "4 failed, 2 passed, 1 error in" in probe_run.stdout, probe_run.stdout


@pytest.mark.parametrize(
    "name, outcome",
    [
        ("test_before", "PASSED"),
        ("test_sleeps_for_good", "FAILED"),
        ("test_waits_for_a_lock_nobody_releases", "FAILED"),
        ("test_body_and_finally_and_teardown_all_block", "FAILED"),
        ("test_body_and_finally_and_teardown_all_block", "ERROR"),
        ("test_blocks_in_a_finalizer_under_the_collector", "FAILED"),
        ("test_after", "PASSED"),
    ],
)
def test_each_test_that_blocks_fails_and_its_neighbours_pass(probe_run, name, outcome):
    assert f"{outcome} tests/test_probe.py::{name}" in probe_run.stdout, probe_run.stdout


def test_the_stacks_of_a_test_that_was_cut_reach_the_real_stderr(probe_run):
    err = probe_run.stderr
    assert err.count("[conftest] tests/test_probe.py::") == 8, err
    assert re.search(r"test_sleeps_for_good is still running after \d+s", err), err
    assert re.search(r'test_probe\.py", line \d+ in test_sleeps_for_good', err), err
    assert "ran into the 4s limit that tests/conftest.py gives every test" in probe_run.stdout


def test_a_test_that_sits_in_a_finalizer_ends_its_worker_and_fails_once(probe_run):
    name = "tests/test_probe.py::test_blocks_in_a_finalizer_under_the_collector"
    assert probe_run.stderr.count(f"[conftest] {name} is still running after") == 2, probe_run.stderr
    assert f"[conftest] {name} sits in a finalizer" in probe_run.stderr
    assert probe_run.stdout.count(f"FAILED {name}") == 2, probe_run.stdout  # its line, and the summary's
    assert f"crashed while running '{name}'" in probe_run.stdout
    assert "replacing crashed worker gw0" in probe_run.stdout
    assert "[gw1]" in probe_run.stdout and "PASSED tests/test_probe.py::test_after" in probe_run.stdout
