"""The readers of a start (``harness/start.py`` and the six files of
``layer_metrics/`` over it) on the recorded sample kept beside them
(``harness/start_sample/``: a rehearsal of each cell kind as the head wrote
it: the engine's ``llm_start``, its ``compile`` records and a dozen loop
iterations; the trainer's first twelve steps and its ``compile`` records), on
the older sample, which has no such record, and without a file."""

import json
import os

import pytest

import tiny  # noqa: I001 - benchmarks/tests is on sys.path under pytest (rootdir conftest)
from benchmarks.harness import loops, start

SAMPLE = os.path.join(tiny.ROOT, "benchmarks", "harness", "start_sample")
OLD_SAMPLE = os.path.join(tiny.ROOT, "benchmarks", "harness", "loops_sample")
# a window after the warm-up's last compilation and before the comparison's; the run began 8 s before it
SERVE_CTX = {"window": (1790770651.0, 1790770655.0), "e2e": {"setup_s": 8.0}}
# steps 3..9 of 12: two of warm-up, then the window's seven; the run began 9 s before the first of them
TRAIN_CTX = {"cell": {"traffic": {"warmup_steps": 2}}, "durations": [0.02] * 7, "e2e": {"setup_s": 9.0}}
OLD_SERVE_CTX = {"window": (1790521482.09, 1790521482.17), "e2e": {"setup_s": 8.0}}
SERVING = ("start_process_s", "start_backend_s", "start_weights_s", "start_lowering_s", "start_compile_s",
           "compiles_in_window")
TRAINING = ("start_process_s", "start_lowering_s", "start_compile_s")
CASES = [(n, SERVE_CTX) for n in SERVING] + [(n, TRAIN_CTX) for n in TRAINING]


@pytest.mark.parametrize("name,ctx,value", [
    ("start_process_s", SERVE_CTX, 2.536835788),  # 643.0 -> t_init
    ("start_backend_s", SERVE_CTX, 0.051974529),
    ("start_weights_s", SERVE_CTX, 3.10235123),
    # 27 records before the window: the weights' jit, two eager fills, three buckets, two of the loop's programs
    ("start_lowering_s", SERVE_CTX, 0.7823317050933838),
    ("start_compile_s", SERVE_CTX, 2.846874952316284),
    ("compiles_in_window", SERVE_CTX, 0),
    ("start_process_s", TRAIN_CTX, 2.689211811),
    ("start_lowering_s", TRAIN_CTX, 0.8336062431335449),
    ("start_compile_s", TRAIN_CTX, 3.3115005493164062),
])
def test_each_reader_on_the_recorded_sample(monkeypatch, name, ctx, value):
    monkeypatch.setattr(loops, "directory", lambda: SAMPLE)
    assert tiny.reader(name)(ctx) == pytest.approx(value, rel=1e-9)


@pytest.mark.parametrize("name,ctx", [(n, OLD_SERVE_CTX) for n in SERVING] + [(n, TRAIN_CTX) for n in TRAINING])
def test_a_reader_finds_nothing_in_the_records_of_an_older_program(monkeypatch, name, ctx):
    monkeypatch.setattr(loops, "directory", lambda: OLD_SAMPLE)
    assert loops.engine_steps(OLD_SERVE_CTX) and loops.window_steps(TRAIN_CTX)  # its other records are read
    assert tiny.reader(name)(ctx) is None


@pytest.mark.parametrize("name,ctx", CASES)
def test_a_reader_finds_nothing_without_a_file(monkeypatch, tmp_path, name, ctx):
    monkeypatch.setattr(loops, "directory", lambda: None)
    assert tiny.reader(name)(ctx) is None
    monkeypatch.setattr(loops, "directory", lambda: str(tmp_path))
    assert tiny.reader(name)(ctx) is None


def test_the_parts_lie_where_the_records_say(monkeypatch):
    monkeypatch.setattr(loops, "directory", lambda: SAMPLE)
    # a window laid over the second bucket's warm-up counts its compilation, and leaves what came later out of the start
    early = {**SERVE_CTX, "window": (1790770649.85, 1790770650.3)}
    assert start.compiles_in_window(early) == 1
    assert start.compile_seconds(early, ("compile",)) < start.compile_seconds(SERVE_CTX, ("compile",))
    # the comparison's programs, compiled after the window on another thread, are in neither
    recs = start.compile_records(SERVE_CTX)
    late = [r for r in recs if r["where"] == "other"]
    assert late and all(r["t"] > SERVE_CTX["window"][1] * 1e9 for r in late)
    whole = sum(r["seconds"] for r in recs if r["stage"] == "compile" and r["where"] != "other")
    assert start.compile_seconds(SERVE_CTX, ("compile",)) == pytest.approx(whole)
    # loads from the cache lie inside the backend's seconds and are not a stage of their own in either sum
    assert start.compile_seconds(SERVE_CTX, ("cache_load",)) == 0
    # the start's stamps: the phases in order, and together no longer than the set-up they are parts of
    st = start.llm_start()
    assert start.run_start_ns(SERVE_CTX) < st["t_init"] < st["t_ready"] < start.window_ns(SERVE_CTX)[0]
    parts = [tiny.reader(n)(SERVE_CTX) for n in ("start_process_s", "start_backend_s", "start_weights_s")]
    assert all(p > 0 for p in parts) and sum(parts) < SERVE_CTX["e2e"]["setup_s"]
    # training: the window is the step records' own; a compile after it (the comparison's) is left out
    t0, t1 = start.window_ns(TRAIN_CTX)
    steps = loops.window_steps(TRAIN_CTX)
    assert (t0, t1) == (steps[0]["t0_ns"], steps[-1]["t2_ns"]) and len(steps) == 7
    assert any(r["t"] > t1 for r in start.compile_records(TRAIN_CTX)) and start.compiles_in_window(TRAIN_CTX) == 0
    # steps that cannot be placed (fewer records than the window had steps): nothing is read
    assert start.compile_seconds({**TRAIN_CTX, "durations": [0.02] * 11}, ("compile",)) is None


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return tiny.build(str(tmp_path_factory.mktemp("bench_start")))


@pytest.mark.parametrize("workload,names", [("tiny-batch", SERVING), ("tiny-ingest", TRAINING)])
def test_a_rehearsal_of_each_cell_kind_prints_the_parts_of_its_start(tree, workload, names):
    proc = tiny.run_cell(tree, workload, trace=1, seconds=3.0)
    assert proc.returncode == 3, proc.stdout[-3000:] + proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    m = {k: v["value"] for k, v in line["metrics"].items()}
    for name in names:
        assert line["metrics"][name]["unit"] == ("events" if name == "compiles_in_window" else "s"), name
        assert m[name] >= 0, name
    assert m["start_lowering_s"] > 0 and m["start_compile_s"] > 0  # compile events happen on the CPU too
    if workload == "tiny-batch":
        assert m["compiles_in_window"] == 0  # every bucket was warmed
        assert "no result" not in proc.stdout
