"""The program's own loop records, read after the cluster is gone.

The serving engine keeps one record per iteration of its loop and one per
request that ended; the trainer's step plane one per step. The head writes
them, as they land, to ``<session_dir>/loops/*.jsonl``: one JSON object a
line that names its ``kind`` and its fields, stamps in ``time.time_ns()``
(``ray_tpu/_private/looplog.py`` is the schema). The readers below find the
session this process ran and reduce the records of the measured window. On
a program that writes no such files every reader returns None, and the line
leaves the metric out.
"""

from __future__ import annotations

import glob
import json
import os

from benchmarks.harness import arith


def directory():
    """``<session_dir>/loops`` of the cluster this process ran, which the
    program keeps on its module after shutdown; None where it kept none."""
    try:
        from ray_tpu._private import looplog
    except ImportError:  # a program older than its loop records
        return None
    where = looplog.last_dir
    return where if where and os.path.isdir(where) else None


def load(prefix: str, kind: str, where=None) -> list:
    """Every record of ``kind`` in the files ``<prefix>*.jsonl`` (a rotated
    ``.1`` first), in the order written. Empty without a directory."""
    where = where or directory()
    if not where:
        return []
    out = []
    # a file's rotated predecessor (<name>.1) before the file itself
    paths = sorted(glob.glob(os.path.join(where, prefix + "*.jsonl*")),
                   key=lambda p: (p.removesuffix(".1"), not p.endswith(".1")))
    for path in paths:
        with open(path) as f:
            for line in f:
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue  # a line cut short by a killed head
                if rec.get("kind") == kind:
                    out.append(rec)
    return out


# -- serving: the engine's loop --------------------------------------------


def _in_window(ctx, recs, stamp: str) -> list:
    """Records whose ``stamp`` lies in the untraced part of the window, as
    the counters are read."""
    t0, t1 = (int(t * 1e9) for t in ctx["window"])
    return [r for r in recs if t0 <= r[stamp] < t1]


def engine_steps(ctx, where=None) -> list:
    return _in_window(ctx, load("llm-", "llm_step", where), "t_loop")


def mean_ms(spans_ns):
    """Mean of durations in nanoseconds, in milliseconds; None of none."""
    spans_ns = list(spans_ns)
    return arith.mean(spans_ns) / 1e6 if spans_ns else None


def queue_wait_ms(ctx, where=None):
    """Mean ``llm.queue_wait`` (submit to popped from the waiting queue) of
    the requests admitted in the window."""
    reqs = _in_window(ctx, [r for r in load("llm-", "llm_request", where) if r["t_admit"]], "t_admit")
    return mean_ms(r["t_admit"] - r["t_submit"] for r in reqs)


def prefill_ms(ctx, where=None):
    """Mean time an iteration's prefills hold the loop, and with it every
    running stream, over the iterations that prefilled."""
    return mean_ms(r["t_admit_end"] - r["t_loop"] for r in engine_steps(ctx, where) if r["prefills"])


def dispatch_gap_ms(ctx, where=None):
    """Mean host time between a step's result reaching the host and the next
    step's dispatch returning: what the device waits for."""
    return mean_ms(r["t_dispatch_end"] - r["t_result"] for r in engine_steps(ctx, where)
                   if r["t_result"] and r["t_dispatch_end"])


def device_wait_ms(ctx, where=None):
    """Mean time the engine thread blocks for the in-flight step's result."""
    return mean_ms(r["t_result"] - r["t_admit_end"] for r in engine_steps(ctx, where) if r["t_result"])


# -- training: the step plane ----------------------------------------------


def window_steps(ctx, where=None):
    """The step records of the window's plain steps, by position: the mix's
    ``warmup_steps``, then as many as the window had (``durations``). None
    where they cannot be placed (no file, or steps merged into blocks)."""
    recs = sorted((r for r in load("train-", "train_step", where) if r.get("rank") == 0), key=lambda r: r["step"])
    n_warm, n = int(ctx["cell"]["traffic"]["warmup_steps"]), len(ctx["durations"])
    if not recs or any(r.get("merged", 1) != 1 for r in recs) or len(recs) < n_warm + n:
        return None
    return recs[n_warm:n_warm + n]


def stage_ms(ctx, stage: str, where=None):
    """Mean of one step-plane stage over the window's steps."""
    steps = window_steps(ctx, where)
    if not steps or any(stage not in r["stages"] for r in steps):
        return None
    return arith.mean([r["stages"][stage] for r in steps])
