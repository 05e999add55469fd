"""``BENCHMARK.json`` against the parts of the contract a file can be checked
for, and against the files it names."""

import json
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_keys_names_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert BENCH["paths"] == ["benchmarks"] and 1 <= BENCH["run_seconds"] <= 51
    names = [x["name"] for g in ("configs", "workloads", "end_to_end", "per_layer") for x in BENCH[g]]
    assert all(NAME.match(n) for n in names)
    for g in ("configs", "workloads"):
        assert len({x["name"] for x in BENCH[g]}) == len(BENCH[g])
    metrics = BENCH["end_to_end"] + BENCH["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) <= max(1, len(BENCH["workloads"]) // 4)
    assert all(len(w["why"]) <= 200 and NAME.match(w["traffic"]) for w in BENCH["workloads"])
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_every_cell_has_its_files_and_its_metrics():
    configs = {c["name"]: c for c in BENCH["configs"]}
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    cells = [w["name"] for w in BENCH["workloads"]]
    assert {w["config"] for w in BENCH["workloads"]} == set(configs)  # each configuration used by some cell
    for w in BENCH["workloads"]:
        cfg = json.load(open(os.path.join(ROOT, configs[w["config"]]["file"])))
        assert cfg["chips"] == w["chips"] and cfg["reduced"] == configs[w["config"]]["reduced"]
        assert not any(k.endswith(("_dim", "_rank")) or k in ("n_embd", "n_inner", "n_head") for k in cfg["reduced"])
        assert os.path.exists(os.path.join(ROOT, "benchmarks", "traffic", w["traffic"] + ".json"))
        mine = [m for m in BENCH["end_to_end"] if w["name"] in m.get("workloads", cells)]
        assert "setup_s" in [m["name"] for m in mine] and len(mine) >= 2
        layer = [m for m in BENCH["per_layer"] if w["name"] in m.get("workloads", cells)]
        assert layer
        for m in layer:
            assert os.path.exists(os.path.join(ROOT, "benchmarks", "layer_metrics", m["name"] + ".py"))
            assert w["name"] in e2e[m["moves"]].get("workloads", cells)  # the cell reports what the metric moves
