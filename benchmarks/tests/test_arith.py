import pytest

from benchmarks.harness import arith


def test_whole_step_rate_never_counts_a_cut_step():
    # steps of 0.75 s; the window [1.0, 4.0] cuts the step that starts at 0.75 and the one that ends at 4.5
    starts = [0.0, 0.75, 1.5, 2.25, 3.0, 3.75]
    ends = [0.75, 1.5, 2.25, 3.0, 3.75, 4.5]
    rate, n = arith.whole_step_rate(ends, starts, 16384, 1.0, 4.0)
    assert n == 3 and rate == pytest.approx(16384 / 0.75)
    # steps x tokens / window would have said 3 * 16384 / 3.0: 25% off
    assert rate != pytest.approx(3 * 16384 / 3.0)
    with pytest.raises(ValueError):
        arith.whole_step_rate([5.0], [4.5], 1, 1.0, 4.0)


def test_tokens_are_counted_by_arrival_time_not_by_finished_requests():
    a = [[0.5, 1.0, 1.5, 2.0], [1.9, 2.1, 9.0]]  # the second request ends long after the window
    assert arith.tokens_in_window(a, 1.0, 2.0) == 3  # 1.0, 1.5, 1.9; the end is exclusive
    assert arith.slice_rates(a, 0.0, 3.0, 1.0) == [1.0, 3.0, 2.0]
    assert arith.slice_rates(a, 0.0, 2.5, 1.0) == [1.0, 3.0, 4.0]  # a short last slice is a rate too


def test_gaps_and_percentiles_on_a_hand_made_timeline():
    a = [[0.0, 0.05, 0.10, 0.30], [0.0, 0.06]]
    gaps = arith.token_gaps(a, 0.0, 1.0)
    assert sorted(round(g, 3) for g in gaps) == [0.05, 0.05, 0.06, 0.2]
    assert arith.token_gaps(a, 0.07, 0.2) == [pytest.approx(0.05)]  # only the gap that ends at 0.10
    assert arith.percentile([1, 2, 3, 4, 5], 50) == 3
    assert arith.percentile(list(range(101)), 99) == 99
    assert arith.percentile([10, 20], 75) == 17.5
    assert arith.mean([1, 2, 6]) == 3
    with pytest.raises(ValueError):
        arith.percentile([], 50)


def test_the_heartbeat_notes_only_stalls_and_reads_them_by_window():
    import time

    from benchmarks.harness.common import Heartbeat

    heart = Heartbeat()
    time.sleep(0.3)
    assert heart.stop() == []  # it woke on time throughout
    heart.stalls = [(99.0, 0.5), (103.0, 1.25), (146.0, 0.3)]
    assert heart.within(100.0, 145.0) == [(3.0, 1.25)]
