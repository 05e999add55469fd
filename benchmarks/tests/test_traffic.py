import json
import os

import pytest

from benchmarks.harness import traffic as tr

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEEDS = [0, 7, 2**31 + 11, 4_000_000_007]


def mix(name):
    return json.load(open(os.path.join(BENCH, "traffic", name + ".json")))


def test_stratified_lengths_are_the_declared_quantiles():
    assert tr.stratified_lengths({"lo": 64, "hi": 256, "count": 3}) == [64, 128, 256]
    batch = tr.stratified_lengths(mix("batch")["prompt_len"])
    assert len(batch) == 24 and batch[0] == 64 and batch[-1] == 256 and batch == sorted(batch)


ONLINE = {"prompt_len": {"lo": 32, "hi": 512, "count": 48}, "output_len": {"lo": 16, "hi": 128, "count": 48}}


@pytest.mark.parametrize("m", [mix("batch"), ONLINE], ids=["batch", "48-lengths"])
def test_every_seed_carries_the_same_work_in_another_order(m):
    cycles = [tr.request_cycle(m, s) for s in SEEDS]
    for c in cycles:
        assert sorted(p for p, _ in c) == tr.stratified_lengths(m["prompt_len"])
        assert sorted(o for _, o in c) == tr.stratified_lengths(m["output_len"])
    assert len({tuple(c) for c in cycles}) == len(SEEDS)  # another order for each seed
    assert tr.request_cycle(m, 7) == tr.request_cycle(m, 7)


def test_requests_take_tokens_from_seed_and_index_and_cycle_their_lengths():
    m = mix("batch")
    a, b = tr.request(m, 7, 3, 50400), tr.request(m, 7, 3, 50400)
    assert a == b and len(a["prompt"]) == tr.request_cycle(m, 7)[3][0]
    assert tr.request(m, 8, 3, 50400)["prompt"] != a["prompt"]
    again = tr.request(m, 7, 3 + 24, 50400)
    assert len(again["prompt"]) == len(a["prompt"]) and again["prompt"] != a["prompt"]
    assert all(1 <= t < 50399 for t in a["prompt"])
    # no request of the batch cell can be shed: 12 callers x worst case fits the pool
    worst = m["prompt_len"]["hi"] + m["output_len"]["hi"]
    assert m["callers"] * -(-worst // 16) <= 383


def test_arrivals_keep_the_count_whatever_the_seed():
    import numpy as np

    m = {"arrivals": {"process": "poisson", "rate_per_s": 5.0}}
    t = tr.arrival_times(m, 2**31 + 5, 4000.0)
    assert t == tr.arrival_times(m, 2**31 + 5, 4000.0) and t != tr.arrival_times(m, 6, 4000.0)
    # the same number of requests whatever the seed: the load does not depend on it
    assert len(t) == len(tr.arrival_times(m, 6, 4000.0)) == 20000
    assert len(tr.arrival_times({"arrivals": {"process": "poisson", "rate_per_s": 1.2}}, 9, 48.0)) == 58
    gaps = np.diff(t)
    assert abs(gaps.std() / gaps.mean() - 1.0) < 0.15  # exponential gaps
    assert all(0 <= x < 4000.0 for x in t) and t == sorted(t)
    with pytest.raises(ValueError):
        tr.arrival_times({"arrivals": {"process": "gamma", "rate_per_s": 1.0}}, 1, 10.0)


def test_training_rows_and_buckets():
    d = tr.token_batches(2**32 + 1, 512, 16, 32)
    assert d["tokens"].shape == (16, 32) and (d["targets"][:, :-1] == d["tokens"][:, 1:]).all()
    assert [tr.prefill_bucket(n) for n in (1, 8, 9, 64, 65, 256, 512)] == [8, 8, 16, 64, 128, 256, 512]
