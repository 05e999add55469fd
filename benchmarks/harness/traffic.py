"""The one general traffic generator. A mix is a data file of parameters
(``traffic/<name>.json``); this module turns it and ``--seed`` into requests
and arrival times. Every seed gets the same set of lengths (the quantiles of
the declared log-uniform distribution), in another order and with other token
ids: the amount of work does not depend on the seed.
"""

from __future__ import annotations

import numpy as np


def stratified_lengths(spec: dict) -> list:
    """``count`` lengths log-spaced over ``lo``..``hi`` inclusive."""
    lo, hi, n = int(spec["lo"]), int(spec["hi"]), int(spec["count"])
    if n == 1:
        return [lo]
    return [int(round(lo * (hi / lo) ** (i / (n - 1)))) for i in range(n)]


def request_cycle(traffic: dict, seed: int) -> list:
    """One cycle of (prompt_len, output_len) pairs: both stratified lists,
    each shuffled by the seed, paired by position."""
    rng = np.random.default_rng([int(seed), 1])
    prompts = stratified_lengths(traffic["prompt_len"])
    outputs = stratified_lengths(traffic["output_len"])
    if len(prompts) != len(outputs):
        raise ValueError("prompt_len.count and output_len.count must agree")
    return list(zip(rng.permutation(prompts).tolist(), rng.permutation(outputs).tolist()))


def request(traffic: dict, seed: int, index: int, vocab_size: int, cycle=None) -> dict:
    """The ``index``-th request of the run: lengths from the cycle, token ids
    from (seed, index)."""
    cycle = cycle or request_cycle(traffic, seed)
    p, o = cycle[index % len(cycle)]
    rng = np.random.default_rng([int(seed), 2, int(index)])
    return {
        "index": index,
        "prompt": rng.integers(1, vocab_size - 1, p).tolist(),
        "max_new_tokens": int(o),
    }


def arrival_times(traffic: dict, seed: int, horizon_s: float) -> list:
    """Open loop: seconds, from the start of the ramp, at which requests are
    due. Every seed sends the same number of requests, ``rate_per_s`` x
    ``horizon_s`` rounded, so that the load does not depend on the seed; only
    their times do. ``arrivals.process`` ``poisson`` is a Poisson process
    conditioned on that count: the times are uniform over the horizon, in
    order."""
    arr = traffic["arrivals"]
    if arr["process"] != "poisson":
        raise ValueError(f"unknown arrival process {arr['process']!r}")
    n = int(round(float(arr["rate_per_s"]) * horizon_s))
    rng = np.random.default_rng([int(seed), 3])
    return np.sort(rng.uniform(0.0, horizon_s, n)).tolist()


def token_batches(seed: int, vocab_size: int, rows: int, seq: int) -> dict:
    """Training data: ``rows`` sequences of ``seq`` seeded token ids with
    their next-token targets."""
    rng = np.random.default_rng([int(seed), 4])
    tokens = rng.integers(0, vocab_size - 1, (rows, seq), dtype=np.int32)
    return {"tokens": tokens, "targets": np.roll(tokens, -1, axis=1)}


def prefill_bucket(n: int, bucket_min: int = 8) -> int:
    """The engine's padded prompt length: the power of two at or above n."""
    b = bucket_min
    while b < n:
        b *= 2
    return b
