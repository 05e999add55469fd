"""A token's way out of the replica and the controller's health probe, from the
records the program writes of them (``ray_tpu/_private/looplog.py``): one
``llm_stream`` a token stream of the engine (in ``loops/llm-*.jsonl``), one
``serve_stream`` a streamed response in the caller's hands and one
``serve_probe`` a health probe of a replica (both in ``loops/serve-*.jsonl``).
A stream's record folds each segment of a token's way (``held``, ``wake``,
``send``, ``transit``) into count, sum and maximum in ns; the readers here take
the streams whose last stamp lies in the untraced part of the window, as the
counters are read, and give a token's mean. ``send`` and ``transit`` overlap by
the sender's ``conn.send``; nothing is subtracted. On a program that writes no
such record every reader returns None."""

from __future__ import annotations

from benchmarks.harness import loops
from benchmarks.harness.start import window_ns


def ended_in_window(ctx, prefix: str, kind: str, stamp: str, where=None) -> list:
    """The records of ``kind`` whose last stamp (``stamp``) lies in the window."""
    t0, t1 = window_ns(ctx)
    return [r for r in loops.load(prefix, kind, where) if t0 <= r[stamp] < t1]


def segment_ms(recs: list, segment: str):
    """A token's mean in one segment over the streams: the sums over the counts, in ms."""
    n = sum(r[segment + "_n"] for r in recs)
    return sum(r[segment + "_sum"] for r in recs) / n / 1e6 if n else None


def engine_segment_ms(ctx, segment: str, where=None):
    """``held``, ``wake`` or ``send`` of the engine's streams that ended in the window."""
    return segment_ms(ended_in_window(ctx, "llm-", "llm_stream", "t_last_back", where), segment)


def caller_streams(ctx, where=None) -> list:
    return ended_in_window(ctx, "serve-", "serve_stream", "t_last_got", where)


def transit_ms(ctx, where=None):
    return segment_ms(caller_streams(ctx, where), "transit")


def gap_max_ms(ctx, where=None):
    """The longest time between two items of one stream in its caller's hands."""
    recs = caller_streams(ctx, where)
    return max(r["gap_max"] for r in recs) / 1e6 if recs else None


def health_probe_ms(ctx, where=None):
    """The longest round trip of the probes that were, or could still have
    been, in flight inside the window: sent before its end and no longer than
    their budget before its start (the controller probes once a period, 5 s,
    so a short window may see no probe sent). A probe that was never answered
    reads its budget."""
    t0, t1 = window_ns(ctx)
    trips = [(r["t_answered"] - r["t_sent"]) / 1e6 if r["t_answered"] else r["budget_s"] * 1e3
             for r in loops.load("serve-", "serve_probe", where)
             if r["t_sent"] < t1 and r["t_sent"] + int(r["budget_s"] * 1e9) >= t0]
    return max(trips) if trips else None
