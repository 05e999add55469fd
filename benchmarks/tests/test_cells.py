"""One full run of each cell kind end to end at tiny size, on the CPU, from a
temporary copy of the benchmark (``tiny.py``); and the proof that a
configuration, a mix and a per-layer metric are added as new files plus
appended entries, with no file that is there edited."""

import json
import os

import pytest

import tiny  # noqa: I001 - benchmarks/tests is on sys.path under pytest (rootdir conftest)

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
DEVICE_KEYS = {"platform", "kind", "count", "memory_peak_bytes"}

MADE_UP_CONFIG = {**tiny.CONFIGS["tiny-serve"], "n_layer": 3, "n_inner": 256, "vocab_size": 300,
                  "engine": {"block_size": 8, "num_blocks": 64, "max_batch": 2, "max_blocks_per_seq": 8}}
MADE_UP_MIX = {**tiny.TRAFFIC["tiny-batch"], "callers": 3,
               "prompt_len": {"lo": 5, "hi": 20, "count": 4}, "output_len": {"lo": 3, "hi": 9, "count": 4}}
MADE_UP_READER = '''"""A made-up per-layer metric: requests the client saw end in the window."""


def read(ctx):
    t0, t1 = ctx["window"]
    return float(sum(1 for r in ctx["records"] if r["arrivals"] and t0 <= r["arrivals"][-1] < t1))
'''


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    dest = str(tmp_path_factory.mktemp("bench"))
    metric = os.path.join(tiny.ROOT, "benchmarks", "layer_metrics", "batch_occupancy.py")
    tiny.build(
        dest, extra_cells=[("made-up-cell", "made-up", "made-up-mix", 1)],
        extra_configs={"made-up": MADE_UP_CONFIG}, extra_traffic={"made-up-mix": MADE_UP_MIX},
        extra_files={"layer_metrics/made_up.requests_done.py": MADE_UP_READER,
                     "layer_metrics/batch_occupancy.made_up.py": open(metric).read()},
        extra_per_layer=[{"name": "made_up.requests_done", "unit": "requests", "better": "higher",
                          "source": "host_clock", "layer": "entry", "moves": "serve_tokens_per_s",
                          "workloads": ["made-up-cell"]},
                         {"name": "batch_occupancy.made_up", "unit": "%", "better": "higher",
                          "source": "program_counter", "layer": "engine", "moves": "serve_tokens_per_s",
                          "workloads": ["made-up-cell"]}],
    )
    # the made-up cell serves under serve_tokens_per_s: an entry appended, nothing edited
    bench = json.load(open(os.path.join(dest, "BENCHMARK.json")))
    for m in bench["end_to_end"]:
        if m["name"] == "serve_tokens_per_s":
            m["workloads"].append("made-up-cell")
    json.dump(bench, open(os.path.join(dest, "BENCHMARK.json"), "w"))
    return dest


def last_line(proc):
    assert proc.returncode == 3, proc.stdout[-3000:] + proc.stderr[-3000:]  # 3: a rehearsal, never a chip result
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] > 0
    return line


@pytest.mark.parametrize("workload,devices,metrics", [
    ("tiny-batch", 1, {"serve_tokens_per_s", "setup_s"}),
    ("tiny-online", 1, {"serve_tokens_per_s", "setup_s"}),
    ("tiny-ingest", 1, {"train_tokens_per_s", "setup_s"}),
    ("tiny-mesh", 4, {"train_tokens_per_s", "setup_s"}),
])
def test_each_cell_kind_ends_in_the_contracts_line(tree, workload, devices, metrics):
    line = last_line(tiny.run_cell(tree, workload, trace=0, devices=devices))
    assert set(line) == RESULT_KEYS and set(line["device"]) == DEVICE_KEYS
    assert set(line["metrics"]) == metrics
    assert all(m["value"] > 0 and m["unit"] for m in line["metrics"].values())
    assert line["device"]["count"] == devices


@pytest.mark.parametrize("workload,devices,some", [
    # program times (train_step_ms, the rooflines) need the chip's "XLA Modules" line:
    # off the chip their readers find nothing and the metrics are left out
    ("tiny-online", 1, {"batch_occupancy", "decode_step_ms.batch"}),
    ("tiny-ingest", 1, {"data_wait_ms.ingest"}),
])
def test_a_traced_run_reports_per_layer_metrics_and_device_time(tree, workload, devices, some):
    line = last_line(tiny.run_cell(tree, workload, trace=1, seconds=3.0, devices=devices))
    assert set(line) == RESULT_KEYS | {"breakdown"}
    assert some <= set(line["metrics"])
    assert 0 < line["device"]["busy_s"] <= line["device"]["window_s"]
    assert 1 <= len(line["breakdown"]["device_ops"]) <= 10 and len(line["breakdown"]["idle_gaps"]) <= 10


def test_a_cell_is_added_by_files_and_entries_alone(tree):
    end_to_end = last_line(tiny.run_cell(tree, "made-up-cell", trace=0))
    assert set(end_to_end["metrics"]) == {"serve_tokens_per_s", "setup_s"}
    traced = last_line(tiny.run_cell(tree, "made-up-cell", trace=1, seconds=3.0))
    assert set(traced["metrics"]) >= {"made_up.requests_done", "batch_occupancy.made_up"}
    assert traced["metrics"]["made_up.requests_done"]["value"] > 0


def test_without_the_chip_there_is_no_result(tree):
    """No ``--rehearse``: the run finds no TPU, prints no result line and
    exits non-zero; and nothing it started is left."""
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=tiny.ROOT)
    proc = subprocess.Popen(
        [sys.executable, os.path.join(tree, "benchmarks", "run.py"), "--workload", "tiny-batch",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tree, env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True, start_new_session=True)
    out, err = proc.communicate(timeout=300)
    assert proc.returncode not in (0, 3), out + err
    assert "needs 1 TPU chip" in err
    assert not any(line.startswith("{") for line in out.splitlines())
    leftover = subprocess.run(["pgrep", "-s", str(proc.pid)], capture_output=True, text=True).stdout.split()
    assert not leftover, leftover
