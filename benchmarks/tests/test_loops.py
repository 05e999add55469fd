"""The readers of the program's loop records (``harness/loops.py``) on the
recorded sample kept beside them (``harness/loops_sample/``: a stretch of a
rehearsal of each cell kind, as the head wrote it), without a file, and in a
rehearsal of each cell from end to end."""

import json
import os

import pytest

import tiny  # noqa: I001 - benchmarks/tests is on sys.path under pytest (rootdir conftest)
from benchmarks.harness import loops

SAMPLE = os.path.join(tiny.ROOT, "benchmarks", "harness", "loops_sample")
SERVE_CTX = {"window": (1790521482.09, 1790521482.17)}
TRAIN_CTX = {"cell": {"traffic": {"warmup_steps": 2}}, "durations": [0.02] * 7}
NEW = {
    "serve-gptj6b-batch": ("queue_wait_ms.batch", "prefill_ms.batch", "dispatch_gap_ms.batch", "device_wait_ms.batch"),
    "train-gptj4l-ingest": ("step_data_wait_ms.ingest", "host_to_device_ms.ingest", "report_ms.ingest"),
}


reader = tiny.reader


@pytest.mark.parametrize("name,ctx,value", [
    # 36 iterations of the window: 15 prefilled, 35 retired a step; 17 requests admitted
    ("queue_wait_ms.batch", SERVE_CTX, 2.5289072352941178),
    ("prefill_ms.batch", SERVE_CTX, 2.1339008666666666),
    ("dispatch_gap_ms.batch", SERVE_CTX, 1.0885122857142857),
    ("device_wait_ms.batch", SERVE_CTX, 0.20004611428571428),
    # steps 3..9 of 12: two of warm-up, then the window's seven
    ("step_data_wait_ms.ingest", TRAIN_CTX, 0.10242857142857142),
    ("host_to_device_ms.ingest", TRAIN_CTX, 0.41728571428571426),
    ("report_ms.ingest", TRAIN_CTX, 1.0662857142857143),
])
def test_each_reader_on_the_recorded_sample(monkeypatch, name, ctx, value):
    monkeypatch.setattr(loops, "directory", lambda: SAMPLE)
    assert reader(name)(ctx) == pytest.approx(value, rel=1e-9)


@pytest.mark.parametrize("name", [n for names in NEW.values() for n in names])
def test_a_reader_finds_nothing_without_a_file(monkeypatch, tmp_path, name):
    ctx = SERVE_CTX if name.endswith(".batch") else TRAIN_CTX
    monkeypatch.setattr(loops, "directory", lambda: None)  # the program wrote no loops/
    assert reader(name)(ctx) is None
    monkeypatch.setattr(loops, "directory", lambda: str(tmp_path))  # or an empty one
    assert reader(name)(ctx) is None


def test_records_outside_the_window_and_merged_steps_are_not_read(monkeypatch, tmp_path):
    monkeypatch.setattr(loops, "directory", lambda: SAMPLE)
    assert loops.device_wait_ms({"window": (1.0, 2.0)}) is None
    assert len(loops.engine_steps(SERVE_CTX)) == 36
    # fewer step records than the window had steps: they cannot be placed
    assert loops.stage_ms({**TRAIN_CTX, "durations": [0.02] * 11}, "report_ms") is None
    # a block of merged sub-floor steps has no position either
    recs = [json.loads(line) for line in open(os.path.join(SAMPLE, "train-bench-rank0.jsonl"))]
    recs[4]["merged"] = 3
    with open(tmp_path / "train-bench-rank0.jsonl", "w") as f:
        f.writelines(json.dumps(r) + "\n" for r in recs)
    assert loops.stage_ms(TRAIN_CTX, "report_ms", where=str(tmp_path)) is None
    # a rotated file is read before the one that replaced it, a cut line skipped
    with open(tmp_path / "llm-a-1.jsonl.1", "w") as f:
        f.write(json.dumps({"kind": "llm_step", "step": 1}) + "\n")
    with open(tmp_path / "llm-a-1.jsonl", "w") as f:
        f.write(json.dumps({"kind": "llm_step", "step": 2}) + "\n" + '{"kind": "llm_st')
    assert [r["step"] for r in loops.load("llm-", "llm_step", str(tmp_path))] == [1, 2]


def test_the_session_directory_is_the_one_the_program_kept(monkeypatch, tmp_path):
    from ray_tpu._private import looplog

    monkeypatch.setattr(looplog, "last_dir", None)
    # no cluster of this process wrote records: nothing is looked for elsewhere
    (tmp_path / f"session_20260101-000000_{os.getpid()}" / "loops").mkdir(parents=True)
    monkeypatch.setenv("RAY_TPU_SESSION_DIR_ROOT", str(tmp_path))
    assert loops.directory() is None and loops.queue_wait_ms(SERVE_CTX) is None
    kept = tmp_path / "session_20260101-000000_1" / "loops"
    monkeypatch.setattr(looplog, "last_dir", str(kept))
    assert loops.directory() is None  # kept, and gone since
    kept.mkdir(parents=True)
    assert loops.directory() == str(kept)


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return tiny.build(str(tmp_path_factory.mktemp("bench_loops")))


@pytest.mark.parametrize("workload,real", [("tiny-batch", "serve-gptj6b-batch"), ("tiny-ingest", "train-gptj4l-ingest")])
def test_a_rehearsal_of_each_cell_prints_the_new_metrics(tree, workload, real):
    proc = tiny.run_cell(tree, workload, trace=1, seconds=3.0)
    assert proc.returncode == 3, proc.stdout[-3000:] + proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    for name in NEW[real]:
        assert line["metrics"][name]["unit"] == "ms" and line["metrics"][name]["value"] >= 0, name
    if workload == "tiny-batch":
        # the parts of a decode step, as the loop records split it, against the histogram's whole
        m = {k: v["value"] for k, v in line["metrics"].items()}
        assert m["device_wait_ms.batch"] + m["dispatch_gap_ms.batch"] < m["decode_step_ms.batch"] * 1.05
