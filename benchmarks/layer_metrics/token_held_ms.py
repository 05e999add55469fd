"""Engine: a token's mean time from its step's result on the host (a first
token: ``t_first``) to the ``put`` into its stream's queue, on the loop's
thread: the next step's dispatch, which the loop makes before it delivers, and
the retire of the other rows. From the ``llm_stream`` records of the streams
that ended in the window: sum of ``held_sum`` over sum of ``held_n``. Moves
``serve_tokens_per_s``."""

from benchmarks.harness import streams


def read(ctx):
    return streams.engine_segment_ms(ctx, "held")
