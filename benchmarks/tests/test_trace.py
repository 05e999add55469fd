"""The reduction from a profiler trace to numbers, on hand-made intervals and
on the recorded trace kept beside it: ``trace/sample.xplane.pb`` is a quarter
of a second cut from a traced run of ``serve-gptj6b-batch`` on a TPU v5e
(PR 24): four decode steps and one 128-token prefill, with the host frames
that cover them."""

import os

import pytest

from benchmarks.trace import reduce as R

SAMPLE = os.path.join(os.path.dirname(os.path.abspath(R.__file__)), "sample.xplane.pb")


def test_union_and_gaps_on_hand_made_intervals():
    busy = R.union_ns([(10, 20), (15, 30), (40, 50), (50, 55), (70, 80)])
    assert busy == [[10, 30], [40, 55], [70, 80]]
    assert R.gaps_ns(busy, 0, 100) == [(0, 10), (30, 40), (55, 70), (80, 100)]
    assert R.gaps_ns(busy, 12, 75) == [(30, 40), (55, 70)]


def test_self_time_takes_nested_operations_off_their_parent():
    ops = [("while.5", "m", 0, 100), ("fusion.1", "m", 10, 30), ("fusion.2", "m", 30, 90),
           ("copy", "m", 40, 50), ("tail", "m", 120, 130)]
    assert {n: t for n, _, t in R.self_times(ops)} == {
        "while.5": 20, "fusion.1": 20, "fusion.2": 50, "copy": 10, "tail": 10}


def test_names():
    assert R.op_name("%fusion.97 = s32[8]{0:T(128)S(1)} fusion(s32[8]{0:T(128)} %tokens.1), kind=kLoop") == "fusion.97"
    assert R.op_name("dot_general.1") == "dot_general.1"
    assert R.module_name("jit_decode_step_greedy(10993726291758083018)") == "jit_decode_step_greedy"


@pytest.fixture(scope="module")
def reduced():
    return R.reduce_events(R.load_events(SAMPLE))


def test_recorded_trace_programs_and_busy_time(reduced):
    mods = reduced["modules"]
    assert mods["jit_decode_step_greedy"]["count"] == 4 and mods["jit_prefill"]["count"] == 1
    assert mods["jit_decode_step_greedy"]["total_s"] / 4 == pytest.approx(0.0434372, rel=1e-4)
    assert mods["jit_prefill"]["total_s"] == pytest.approx(0.0274193, rel=1e-4)
    assert reduced["devices"] == 1 and reduced["collective_s"] == 0.0
    assert reduced["window_s"] == pytest.approx(0.2499559, rel=1e-5)
    assert reduced["busy_s"] == pytest.approx(0.2368444, rel=1e-5)
    # operations' self times add up to the busy time: nothing counted twice
    assert sum(reduced["ops_s"].values()) == pytest.approx(reduced["busy_s"], rel=1e-3)
    assert mods["jit_decode_step_greedy"]["ops_s"] <= mods["jit_decode_step_greedy"]["total_s"]


def test_recorded_trace_breakdown(reduced):
    ops, gaps = reduced["breakdown"]["device_ops"], reduced["breakdown"]["idle_gaps"]
    assert len(ops) == 10 and ops[0][0] == "jit_decode_step_greedy/bitcast-convert_convert_fusion.2"
    assert all(a[1] >= b[1] for a, b in zip(ops, ops[1:]))
    idle = reduced["window_s"] - reduced["busy_s"]
    assert sum(s for _, s in gaps) == pytest.approx(idle, rel=1e-6)
    # the device waits while the engine's loop retires a step and dispatches the next
    assert gaps[0][0].startswith("engine.py") and gaps[0][1] > 0.5 * idle
