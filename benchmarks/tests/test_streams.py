"""The readers of a token's way out of the replica and of the controller's
health probe (``harness/streams.py``) on the records kept beside this file
(``streams_sample/``: three files made by hand, as the head would write an
engine's, a caller's and the controller's), without a file, outside the window,
and in a rehearsal of both serving kinds from end to end."""

import json
import os

import pytest

import tiny  # noqa: I001 - benchmarks/tests is on sys.path under pytest (rootdir conftest)
from benchmarks.harness import loops, streams

SAMPLE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "streams_sample")
CTX = {"window": (1000.0, 1010.0)}
# by hand, over the two streams of each file that ended inside the window
WANT = {
    "token_held_ms": (4_000_000 + 9_000_000) / (4 + 6) / 1e6,
    "stream_wake_ms": (800_000 + 1_200_000) / (4 + 6) / 1e6,
    "stream_send_ms": (2_000_000 + 4_000_000) / (4 + 6) / 1e6,
    "stream_transit_ms": (1_600_000 + 2_900_000) / (4 + 5) / 1e6,  # the item without a stamp is not counted
    "stream_gap_max_ms": 45.0,
    "health_probe_ms": 12.0,  # of the probes sent at 995, 1000 and 1005 s
}


@pytest.mark.parametrize("name", sorted(WANT))
def test_each_reader_gives_the_number_computed_by_hand(monkeypatch, name):
    monkeypatch.setattr(loops, "directory", lambda: SAMPLE)
    assert tiny.reader(name)(CTX) == pytest.approx(WANT[name], rel=1e-12)


@pytest.mark.parametrize("name", sorted(WANT))
def test_a_reader_finds_nothing_without_records_or_outside_the_window(monkeypatch, tmp_path, name):
    monkeypatch.setattr(loops, "directory", lambda: None)  # a program that wrote no loops/
    assert tiny.reader(name)(CTX) is None
    monkeypatch.setattr(loops, "directory", lambda: str(tmp_path))  # or an empty one
    assert tiny.reader(name)(CTX) is None
    monkeypatch.setattr(loops, "directory", lambda: SAMPLE)
    assert tiny.reader(name)({"window": (1040.0, 1050.0)}) is None  # every record ended, every budget ran out, before
    assert tiny.reader(name)({"window": (900.0, 980.0)}) is None  # or came after


def test_a_missed_probe_reads_its_budget_and_a_window_between_two_probes_reads_the_one_in_flight():
    # the probe sent at 1010 s was never answered: in a window that holds it, the reading is its 10 s
    assert streams.health_probe_ms({"window": (1005.0, 1015.0)}, SAMPLE) == 10_000.0
    # no probe was sent in these 2 s; the one sent at 1005 s could still have been in flight
    assert streams.health_probe_ms({"window": (1006.0, 1008.0)}, SAMPLE) == 12.0
    assert streams.health_probe_ms({"window": (1016.0, 1018.0)}, SAMPLE) == 10_000.0


def test_a_stream_that_ended_without_a_token_is_in_no_window():
    recs = loops.load("llm-", "llm_stream", SAMPLE)
    assert [r["tokens"] for r in recs] == [4, 0, 6, 5]
    inside = streams.ended_in_window(CTX, "llm-", "llm_stream", "t_last_back", SAMPLE)
    assert [r["request"] for r in inside] == [0, 1]
    assert streams.segment_ms([recs[1]], "held") is None


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return tiny.build(str(tmp_path_factory.mktemp("bench_streams")))


@pytest.mark.parametrize("workload", ["tiny-batch", "tiny-online"])
def test_a_rehearsal_of_each_serving_kind_prints_the_six(tree, workload):
    proc = tiny.run_cell(tree, workload, trace=1, seconds=3.0)
    assert proc.returncode == 3, proc.stdout[-3000:] + proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    m = {k: v["value"] for k, v in line["metrics"].items()}
    for name in WANT:
        assert line["metrics"][name]["unit"] == "ms" and m[name] >= 0, name
    # a probe of a sound replica is answered well inside its budget
    assert m["health_probe_ms"] < 10_000.0
