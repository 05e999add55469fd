"""Entry: the longest time between two items of one stream in its caller's
hands, over the streams that ended in the window (the largest ``gap_max`` of
their ``serve_stream`` records). In a sound cell a step and a prefill, tens of
ms; a replica that drops out of its handle shows here in seconds. Moves
``serve_tokens_per_s``."""

from benchmarks.harness import streams


def read(ctx):
    return streams.gap_max_ms(ctx)
